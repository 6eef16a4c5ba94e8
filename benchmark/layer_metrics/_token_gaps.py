"""What the four readers of the program's own token gaps share: the gaps of
the window's scheduler ticks, each with its count and with the prefill
programs the device ran inside it.

The program stamps a token where it arrives (the end of the engine's
``infer.decode_sync``) and keeps, on each ``infer.sched.step`` span record,
the tick's distinct gaps as ``gaps`` = ``[[gap_ns, count, chunks], ...]``.
``chunks`` is the engine's own count of the prefill programs it dispatched
inside the gap (the device runs one stream in launch order): between the
launches of the two decode steps whose tokens bound it or, for a request's
first gap, after the program that sampled its first token. A program whose
records carry no such attribute — the parent's — gives None here, and the
readers then return None.
"""
import numpy as np

from benchmark.harness import stats
from benchmark.layer_metrics import _program


def window_gaps(records):
    """``(gap_ns, count, chunks)`` as three arrays over the distinct gaps of
    the window's ticks, or None where no tick of the window notes its gaps.
    Arrays of length 0 where the ticks do and no token arrived."""
    spans = _program.window_spans(records)
    if spans is None:
        return None
    noted = [s.attrs["gaps"] for s in spans if s.name == _program.SCHED_TICK and s.attrs and "gaps" in s.attrs]
    if not noted:
        return None
    table = np.asarray([entry for gaps in noted for entry in gaps], dtype=np.int64).reshape(-1, 3)
    return table[:, 0], table[:, 1], table[:, 2]


def p95_ns(gaps, counts):
    """The 95th percentile of the gaps, each taken ``count`` times: the same
    percentile, over the same sample, as the outside ``itl_p95_ms``."""
    return stats.percentile(np.repeat(gaps, counts), 95.0)


def share_pct(records, at_least):
    """Share of the window's gaps, by count, with ``at_least`` prefill
    programs or more inside; None where the program notes no gaps or the
    window holds none."""
    got = window_gaps(records)
    if got is None or not got[1].sum():
        return None
    _, counts, chunks = got
    return 100.0 * float(counts[chunks >= at_least].sum()) / float(counts.sum())
