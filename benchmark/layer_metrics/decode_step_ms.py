"""Median wall time of one ``DecodeEngine.decode_step()`` call, which returns
with its tokens on the host (so the time is synced), over the window's ticks
that dispatched no prefill (a chunk is launched without a sync, and the next
decode step would wait for it and be charged its time)."""
from benchmark.harness import stats


def read(records):
    idx = [i for i in records.inside(records.tick_end)
           if records.tick_decoding[i] and not records.tick_prefill_dispatches[i]]
    p = stats.percentile([records.tick_parts[i]["decode_s"] for i in idx], 50.0)
    return None if p is None else 1e3 * p
