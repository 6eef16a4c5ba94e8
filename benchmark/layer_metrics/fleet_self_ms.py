"""The fleet's own time in a tick: median over the window's ticks of the
``infer.fleet.step`` span minus what its children (one ``infer.sched.step``
per replica) cover — harvest, ledger GC, heartbeat check, SLO hook. From the
program's span ring."""
from benchmark.layer_metrics import _program


def read(records):
    spans = _program.window_spans(records)
    return None if spans is None else _program.median_self_ms(spans, _program.TICK)
