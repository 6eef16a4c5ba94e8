"""The 95th percentile of the gaps between consecutive tokens of one request
as the program itself stamps them: each token where it arrives (the end of the
engine's ``infer.decode_sync``), each tick's distinct gaps with their counts on
its ``infer.sched.step`` span record, over the ticks that ended in the window.
The inside twin of the judged ``itl_p95_ms``, whose stamps are the host clock
after ``fleet.step()`` returned. From the program's span ring."""
from benchmark.layer_metrics import _token_gaps


def read(records):
    got = _token_gaps.window_gaps(records)
    if got is None:
        return None
    p = _token_gaps.p95_ns(got[0], got[1])
    return None if p is None else p / 1e6
