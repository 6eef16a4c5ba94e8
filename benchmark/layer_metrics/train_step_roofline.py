"""One training step against the compute roof: the operations forward and
backward require (the family's ``train_flops_per_token``: 6 per matmul
parameter plus causal attention, nothing recomputed) at the chips' peak bf16
rate, over the median device time of the step program in the trace."""
from benchmark.harness import stats


def read(records):
    t = records.trace
    if t is None:
        return None
    _, durations = t.module_like(records.cell.family.TRAIN_PROGRAM)
    if not durations:
        return None
    seq = int(records.cell.traffic["seq"])
    flops = records.cell.family.train_flops_per_token(records.cell.config, seq) * records.tokens_per_step
    floor_s = flops / (records.peaks["bf16_flops_per_s"] * records.chips)
    return 100.0 * floor_s / (stats.median(durations) / 1e9)
