"""How many prefill chunks a gap at the 95th percentile holds: the mean, by
count, of the prefill programs the device ran inside a gap (the third member
of a tick's ``gaps`` entries: the engine's own count between the two decode
steps whose tokens bound the gap) over the window's gaps ranked between the
94th and the 96th percentile by length. Where the p95 sits on one stair of the
chunk stair this is that stair's number (2.0: a p95 gap is two chunks and a
step); between two stairs it lies between theirs; 0.0 where the p95 sits at
the bare tick. Not the mean of the whole tail: the few gaps far above the p95
hold more chunks than a p95 gap does. From the program's span ring."""
import numpy as np

from benchmark.layer_metrics import _token_gaps

BAND = (0.94, 0.96)


def read(records):
    got = _token_gaps.window_gaps(records)
    if got is None or not got[1].sum():
        return None
    gaps, counts, chunks = got
    order = np.argsort(gaps, kind="stable")
    upto = np.cumsum(counts[order]).astype(np.float64)       # rank of each distinct gap's last token, shortest gap first
    total = upto[-1]
    # how much of each entry's run of ranks lies inside the band
    inside = np.minimum(upto, BAND[1] * total) - np.maximum(upto - counts[order], BAND[0] * total)
    inside = np.clip(inside, 0.0, None)
    return float((chunks[order] * inside).sum() / inside.sum())
