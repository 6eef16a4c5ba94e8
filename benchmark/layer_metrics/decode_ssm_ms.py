"""Device milliseconds per execution of the decode program in the part the
family calls ``ssm`` (the family's ``PART_OF_SCOPE``): the state-space mixers'
input projection, convolution, one-token recurrence over every slot's state,
gated norm and output projection. None for a family that has no such part."""
from benchmark.layer_metrics import _program


def read(records):
    return _program.decode_part_ms(records, "ssm")
