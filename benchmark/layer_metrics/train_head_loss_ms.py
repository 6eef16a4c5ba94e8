"""Device milliseconds per training step in the output head, the loss, and the
embeddings, whose table the head shares (scopes ``head_loss``, ``embed``) —
forward and transposed ops alike. Each traced op is joined to its scope through
the step program's own HLO metadata (``_program.py``)."""
from benchmark.layer_metrics import _program


def read(records):
    return _program.train_part_ms(records, "head_loss")
