"""The state-space mixers of one decode step against their HBM floor: by the
family's ``ssm_step_floor_s`` every mixer's weights read once and each decoding
slot's state and convolution tail read and written once (the slots that decoded
in the traced ticks, from the per-tick log) at the chip's peak bandwidth, over
the device time of the decode program's ``ssm`` part. The algorithm's count,
the same whatever implements the step. None where the family has no such part."""
from benchmark.layer_metrics import _program


def read(records):
    floor = getattr(records.cell.family, "ssm_step_floor_s", None)
    if records.trace is None or floor is None:
        return None
    ms = _program.decode_part_ms(records, "ssm")
    decoding = [records.tick_decoding[i] for i in records.in_trace(records.tick_end) if records.tick_decoding[i]]
    if not ms or not decoding:
        return None
    return 100.0 * floor(records.cell.config, sum(decoding) / len(decoding), records.peaks) / (ms / 1e3)
