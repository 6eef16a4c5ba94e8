"""Median of the engine's own ``infer.decode_step`` span (entry to tokens on
the host) over the window's scheduler ticks that dispatched no prefill: the
inside twin of the outside clock ``decode_step_ms``, taken over the same
ticks. From the program's span ring."""
from benchmark.harness import stats
from benchmark.layer_metrics import _program


def read(records):
    spans = _program.window_spans(records)
    if spans is None:
        return None
    ticks, inside = _program.engine_spans_by_tick(spans)
    steps = []
    for t in ticks:
        names = [s.name for s in inside[t.span_id]]
        if not any(n.startswith("infer.prefill") for n in names):
            steps.extend(s.end_ns - s.start_ns for s in inside[t.span_id] if s.name == "infer.decode_step")
    p = stats.median(steps)
    return None if p is None else p / 1e6
