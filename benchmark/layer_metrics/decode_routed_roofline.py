"""The routed experts' path of one decode step against its HBM floor: the
bytes it has to read (every layer's router and the weights of the experts the
traced steps' tokens hit, by the family's ``routed_step_bytes`` from the
program's own count) at the chip's peak bandwidth, over the device time of the
decode program's ``routed`` part. The same work whatever implements the path.
None where the family has no routed part or nothing was counted."""
from benchmark.layer_metrics import _program


def read(records):
    needed = getattr(records.cell.family, "routed_step_bytes", None)
    if records.trace is None or needed is None:
        return None
    ms = _program.decode_part_ms(records, "routed")
    nbytes = needed(records.cell.config, records)
    if not ms or nbytes is None:
        return None
    return 100.0 * (nbytes / records.peaks["hbm_bytes_per_s"]) / (ms / 1e3)
