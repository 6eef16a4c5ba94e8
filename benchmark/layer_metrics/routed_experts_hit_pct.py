"""Share of the held experts, over all layers, that at least one token of a
decode step chose: what the program counted with each step's tokens
(``experts_hit`` on its ``infer.decode_step`` span records), mean over the
window's decode steps. A step reads an expert's weights only if it is hit, so
this is the share of the experts' bytes a step has to read. None where the
family or the program counts nothing."""


def read(records):
    family = records.cell.family
    per_step = getattr(family, "experts_hit_per_step", None)
    hit = per_step(records, traced=False) if per_step is not None else None
    if hit is None:
        return None
    z = family.dims(records.cell.config)
    return 100.0 * hit / (z["held"][1] * z["L"])
