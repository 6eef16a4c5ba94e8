"""Tokens trained per second per chip, between step boundaries: the steps
inside the window over the measured time from its opening boundary to its
closing one, over the chips of the cell."""
from benchmark.harness import stats


def read(records):
    if not records.step_end:
        return None
    rate = stats.rate_on_ticks(records.step_end, [records.tokens_per_step] * len(records.step_end),
                               records.window_open, records.seconds)
    return None if rate is None else rate / records.chips
