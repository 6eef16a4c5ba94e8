"""Device milliseconds per training step in attention: the qkv projection, the
attention core and the output projection with its residual add (scopes
``attn_qkv``, ``attn_core``, ``attn_out``) — forward, recomputed and transposed
ops alike. Each traced op is joined to its scope through the step program's own
HLO metadata (``_program.py``)."""
from benchmark.layer_metrics import _program


def read(records):
    return _program.train_part_ms(records, "attn")
