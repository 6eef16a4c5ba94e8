"""The state-space mixers of one chunk against the compute roof: by the
family's ``ssm_chunk_floor_s`` the operations the mixers' two projections and
the recurrence's least form need for the rows a chunk program computes (the
configuration's ``prefill_chunk``; a final chunk computes its padding rows
too, and they are counted as it computes them) at the chip's peak rate, over
the device time of the chunk programs' ``ssm`` part. The recurrence is counted
at what an inner chunk of one row would do, less than any longer one, so no
implementation reads over 100 %. None where the family has no such part."""
from benchmark.layer_metrics import _program


def read(records):
    floor = getattr(records.cell.family, "ssm_chunk_floor_s", None)
    if records.trace is None or floor is None:
        return None
    ms = _program.part_ms(records, records.cell.family.CHUNK_PROGRAMS, "ssm")
    if not ms:
        return None
    return 100.0 * floor(records.cell.config, int(records.cell.config["serving"]["prefill_chunk"]), records.peaks) / (ms / 1e3)
