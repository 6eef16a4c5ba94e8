"""Median wall time of one training step, synced by pulling the loss to the
host, over the window."""
from benchmark.harness import stats


def read(records):
    idx = records.inside(records.step_end)
    p = stats.percentile([records.step_seconds[i] for i in idx], 50.0)
    return None if p is None else 1e3 * p
