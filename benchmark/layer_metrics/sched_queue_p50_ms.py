"""Median of the scheduler's own ``Request.queue_seconds`` (submit to
admission into a slot) over the window's requests."""
from benchmark.harness import stats


def read(records):
    p = stats.percentile([r.queue_s for r in records.requests if r.in_window and r.queue_s is not None], 50.0)
    return None if p is None else 1e3 * p
