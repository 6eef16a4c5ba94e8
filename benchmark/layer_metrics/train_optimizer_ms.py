"""Device milliseconds per training step in the optimizer's update and in the
cast of its f32 master weights to the compute type, with that cast's transpose
(scopes ``optimizer``, ``amp_cast``). Each traced op is joined to its scope
through the step program's own HLO metadata (``_program.py``)."""
from benchmark.layer_metrics import _program


def read(records):
    return _program.train_part_ms(records, "optimizer")
