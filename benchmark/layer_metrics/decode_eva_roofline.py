"""EVA attention's core in one decode step against its HBM floor: by the
family's ``eva_step_floor_s`` the live ring and summary rows every layer
attends, and the rows and summaries it writes — as the program counted them on
the traced ``infer.decode_step`` records — moved once at the chip's peak
bandwidth, over the device time of the decode program's ``eva`` part. The
algorithm's count, the same whatever implements the core. None where the
family has no such part or the program counted nothing."""
from benchmark.layer_metrics import _program


def read(records):
    floor = getattr(records.cell.family, "eva_step_floor_s", None)
    if records.trace is None or floor is None:
        return None
    ms = _program.decode_part_ms(records, "eva")
    floor_s = floor(records.cell.config, records, records.peaks)
    if not ms or floor_s is None:
        return None
    return 100.0 * floor_s / (ms / 1e3)
