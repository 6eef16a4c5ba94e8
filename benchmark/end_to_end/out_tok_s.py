"""Tokens generated per second, counted on tick boundaries: the tokens that
the ticks inside the window emitted over the host-clock time from the opening
boundary to the closing one. No partial tick, no division by ``--seconds``;
every emitted token counts, whether or not its request finished."""
from benchmark.harness import stats


def read(records):
    if not records.tick_end:
        return None
    return stats.rate_on_ticks(records.tick_end, records.tick_tokens, records.window_open, records.seconds)
