"""The scheduler's own time in a tick: median over the window's ticks of the
``infer.sched.step`` span minus the engine's spans opened under it (prefill
dispatches, the decode step) — admission, prefill bookkeeping, the drain of
the tokens, ledger GC. From the program's span ring."""
from benchmark.harness import stats
from benchmark.layer_metrics import _program


def read(records):
    spans = _program.window_spans(records)
    if spans is None:
        return None
    ticks, inside = _program.engine_spans_by_tick(spans)
    p = stats.median([(t.end_ns - t.start_ns)
                      - _program.covered(t.start_ns, t.end_ns, [(s.start_ns, s.end_ns) for s in inside[t.span_id]])
                      for t in ticks])
    return None if p is None else p / 1e6
