"""Mean time to the first token (from due). Recorded, decides no PR."""
from benchmark.harness import stats


def read(records):
    m = stats.mean(records.ttft_samples())
    return None if m is None else 1e3 * m
