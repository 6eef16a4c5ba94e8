"""Device milliseconds per training step in collectives and in every op whose
metadata names no scope of the model: what the split by model part cannot
place. Each traced op is joined to its scope through the step program's own HLO
metadata (``_program.py``)."""
from benchmark.layer_metrics import _program


def read(records):
    return _program.train_part_ms(records, "unscoped")
