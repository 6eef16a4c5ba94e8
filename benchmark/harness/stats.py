"""The arithmetic every metric reader shares. Plain Python and numpy; no JAX."""
from __future__ import annotations

import statistics
from typing import List, Optional, Sequence, Tuple

import numpy as np


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100) by linear interpolation between order
    statistics (numpy's default), or None of nothing."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


def median(values: Sequence[float]) -> Optional[float]:
    return percentile(values, 50.0)


def mean(values: Sequence[float]) -> Optional[float]:
    return float(np.mean(values)) if len(values) else None


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the median,
    quartiles as ``statistics.quantiles(values, n=4)`` gives them — the
    spread the builder's contract sets bounds from."""
    q1, _, q3 = statistics.quantiles(list(values), n=4)
    return (q3 - q1) / abs(statistics.median(values))


def tick_window(tick_ends: Sequence[float], not_before: float, seconds: float) -> Optional[Tuple[int, int]]:
    """The window on tick boundaries. It opens at the first tick end at or
    after ``not_before`` and closes at the last tick end at or before
    open + ``seconds``. Returns ``(i_open, i_close)`` as indices into
    ``tick_ends``; ticks ``i_open+1 .. i_close`` lie inside. None if fewer
    than one tick fits."""
    ends = np.asarray(tick_ends, dtype=np.float64)
    after = np.nonzero(ends >= not_before)[0]
    if len(after) == 0:
        return None
    i_open = int(after[0])
    inside = np.nonzero(ends <= ends[i_open] + seconds)[0]
    i_close = int(inside[-1])
    if i_close <= i_open:
        return None
    return i_open, i_close


def rate_on_ticks(tick_ends: Sequence[float], tick_counts: Sequence[float],
                  not_before: float, seconds: float) -> Optional[float]:
    """Units completed per second between tick boundaries: what the ticks
    inside the window produced over the measured time between the opening and
    the closing boundary. No partial tick, no division by the argument."""
    w = tick_window(tick_ends, not_before, seconds)
    if w is None:
        return None
    i_open, i_close = w
    done = float(np.sum(np.asarray(tick_counts, dtype=np.float64)[i_open + 1:i_close + 1]))
    return done / (tick_ends[i_close] - tick_ends[i_open])


def longest_gaps(tick_ends: Sequence[float], parts: Sequence[dict], k: int = 10) -> List[dict]:
    """The ``k`` longest gaps between consecutive tick ends, each with the
    tick's index and what the host did in it (``parts[i]``: seconds by name)."""
    ends = np.asarray(tick_ends, dtype=np.float64)
    gaps = np.diff(ends)
    order = np.argsort(-gaps)[:k]
    return [{"tick": int(i + 1), "gap_s": float(gaps[i]),
             **{name: float(v) for name, v in parts[i + 1].items()}} for i in order]
