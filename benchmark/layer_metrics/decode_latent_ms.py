"""Device milliseconds per execution of the decode program in the part the
family calls ``latent`` (the family's ``PART_OF_SCOPE``): latent attention
over the cached rows ``[c | k_r]`` with the new row's write. None for a family
that has no such part."""
from benchmark.layer_metrics import _program


def read(records):
    return _program.decode_part_ms(records, "latent")
