"""One decode step against its HBM floor: the bytes the step has to read
(every weight once and the live rows of the cache, from shapes, by the
family's ``decode_step_bytes``) at the chip's peak bandwidth, over the median
device time of the decode program in the trace. Bound by memory: at these
batch sizes the step's operations are far under the compute roof."""
from benchmark.harness import stats


def read(records):
    t = records.trace
    if t is None:
        return None
    _, durations = t.module_like(records.cell.family.DECODE_PROGRAM)
    idx = [i for i in records.in_trace(records.tick_end) if records.tick_decoding[i]]
    if not durations or not idx:
        return None
    live_rows = sum(records.tick_live_rows[i] for i in idx) / len(idx)
    floor_s = records.cell.family.decode_step_bytes(records.cell.config, live_rows) / records.peaks["hbm_bytes_per_s"]
    return 100.0 * floor_s / (stats.median(durations) / 1e9)
