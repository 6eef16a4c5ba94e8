"""Share of the window's token gaps, by count, inside which the device ran
two prefill chunks or more: what a budget of one chunk a tick would bring to
0. From the program's span ring."""
from benchmark.layer_metrics import _token_gaps


def read(records):
    return _token_gaps.share_pct(records, 2)
