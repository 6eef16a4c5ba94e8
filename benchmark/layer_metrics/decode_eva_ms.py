"""Device milliseconds per execution of the decode program in the part the
family calls ``eva`` (the family's ``PART_OF_SCOPE``): EVA attention's core —
each layer's ring row written, a closing chunk's summary formed, and the live
summary and ring rows attended (one ``eva_decode`` kernel a layer on the TPU).
None for a family that has no such part."""
from benchmark.layer_metrics import _program


def read(records):
    return _program.decode_part_ms(records, "eva")
