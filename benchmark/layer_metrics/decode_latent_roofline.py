"""Latent attention of one decode step against its roofline: the least time
the chip could take over the live cached rows of the traced steps — by the
family's ``latent_step_floor_s``, the larger of the rows' bytes at the peak
bandwidth and of absorbed attention's operations over them at the peak rate —
over the device time of the decode program's ``latent`` part. The same count
whatever implements the attention. None where the family has no latent part."""
from benchmark.layer_metrics import _program


def read(records):
    floor = getattr(records.cell.family, "latent_step_floor_s", None)
    if records.trace is None or floor is None:
        return None
    ms = _program.decode_part_ms(records, "latent")
    idx = [i for i in records.in_trace(records.tick_end) if records.tick_decoding[i]]
    if not ms or not idx:
        return None
    live_rows = sum(records.tick_live_rows[i] for i in idx) / len(idx)
    return 100.0 * floor(records.cell.config, live_rows, records.peaks) / (ms / 1e3)
