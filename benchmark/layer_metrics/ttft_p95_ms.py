"""The tail of the time to the first token (from due). Recorded, decides no
PR: at about one request a second a window holds too few to steady it."""
from benchmark.harness import stats


def read(records):
    p = stats.percentile(records.ttft_samples(), 95.0)
    return None if p is None else 1e3 * p
