"""Median time to the first token, from when the request was *due*, over every
request due inside the window that was answered. Recorded, decides no PR: at
about one request a second the median of a window's 62 waits, which come in
tick-sized steps, spread 5% and 13% in two sets of runs of one code and one
schedule on the chip (PERF.md Findings, PR 24)."""
from benchmark.harness import stats


def read(records):
    p = stats.percentile(records.ttft_samples(), 50.0)
    return None if p is None else 1e3 * p
