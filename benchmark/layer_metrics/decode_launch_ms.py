"""Median of the engine's ``infer.decode_launch`` span over the window: from
the entry of ``decode_step()`` to the return of the dispatch (three small
host-to-device copies and the launch). From the program's span ring."""
from benchmark.layer_metrics import _program


def read(records):
    spans = _program.window_spans(records)
    return None if spans is None else _program.median_ms(spans, "infer.decode_launch")
