"""Device milliseconds per execution of the decode program in the part the
family calls ``routed`` (the family's ``PART_OF_SCOPE``); None for a family
that has no such part."""
from benchmark.layer_metrics import _program


def read(records):
    return _program.decode_part_ms(records, "routed")
