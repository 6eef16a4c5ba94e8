"""Share of the window's token gaps, by count, inside which the device ran
at least one prefill chunk (the engine's own count, kept with each gap on the
tick's ``infer.sched.step`` span record). From the program's span ring."""
from benchmark.layer_metrics import _token_gaps


def read(records):
    return _token_gaps.share_pct(records, 1)
