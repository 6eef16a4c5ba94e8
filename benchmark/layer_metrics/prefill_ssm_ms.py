"""Device milliseconds per execution of the chunk programs (an intermediate
chunk and the final chunk of a prompt) in the part the family calls ``ssm``:
the state-space mixers' projections, convolution, chunkwise scan and gated
norm over a chunk's rows. None for a family that has no such part."""
from benchmark.layer_metrics import _program


def read(records):
    return _program.part_ms(records, records.cell.family.CHUNK_PROGRAMS, "ssm")
