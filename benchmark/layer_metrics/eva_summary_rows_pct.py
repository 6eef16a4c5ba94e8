"""How much of the context EVA's compression serves: summary rows over all
rows a layer attends in a decode step (the ``eva_summary_rows`` and
``eva_ring_rows`` the program notes on each ``infer.decode_step`` record),
the mean over the window's decode steps that decoded. None where the program
notes no such rows."""
from benchmark.layer_metrics import _program


def read(records):
    spans = _program.window_spans(records)
    if spans is None:
        return None
    shares = [s.attrs["eva_summary_rows"] / (s.attrs["eva_summary_rows"] + s.attrs["eva_ring_rows"]) for s in spans
              if s.name == "infer.decode_step" and s.attrs and s.attrs.get("eva_ring_rows")]
    return 100.0 * sum(shares) / len(shares) if shares else None
