"""Device milliseconds per training step in the LayerNorms, the blocks' two and
the final one (scope ``norm``) — forward, recomputed and transposed ops alike.
Each traced op is joined to its scope through the step program's own HLO
metadata (``_program.py``)."""
from benchmark.layer_metrics import _program


def read(records):
    return _program.train_part_ms(records, "norm")
