"""Device milliseconds per training step in the MLP with its residual add
(scope ``mlp``) — forward, recomputed and transposed ops alike. Each traced op
is joined to its scope through the step program's own HLO metadata
(``_program.py``)."""
from benchmark.layer_metrics import _program


def read(records):
    return _program.train_part_ms(records, "mlp")
