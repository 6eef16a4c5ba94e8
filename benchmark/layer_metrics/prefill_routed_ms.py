"""Device milliseconds per execution of the chunk programs (an intermediate
chunk and the final chunk of a prompt) in the part the family calls
``routed``: the router and the held experts' grouped matmuls over a chunk's
(token, expert) pairs. None for a family that has no such part."""
from benchmark.layer_metrics import _program


def read(records):
    return _program.part_ms(records, records.cell.family.CHUNK_PROGRAMS, "routed")
