"""What the readers of the program's own spans and scopes share: the span
records of the window, taken from the program's in-memory ring, and the map
from a traced device op to the model part that produced it.

Both come from what the program keeps while it runs
(``paddle_tpu.observability.spans.recent`` and
``paddle_tpu.observability.introspect.op_scopes``). A program that has
neither gives None here, and the readers then return None: the harness
leaves the metric out of the line.

A span record has ``name``, ``start_ns``, ``end_ns`` (``time.perf_counter_ns()``,
the clock of ``records.window_open``), ``span_id`` and ``parent_id``.
"""
import re

from benchmark.harness import stats, trace

TICK = "infer.fleet.step"
SCHED_TICK = "infer.sched.step"
# spans the layers above the engine open; every other ``infer.`` span is the engine's
ABOVE_ENGINE = ("infer.fleet.", "infer.sched.")

# ``jax.named_scope`` names in the train step -> the part a metric reports.
# ``embed`` goes with the head (the tied table); ``amp_cast`` — the cast of
# the f32 master weights to the compute type and its transpose — goes with
# the optimizer, which is why there are f32 masters.
PART_OF_SCOPE = {"attn_qkv": "attn", "attn_core": "attn", "attn_out": "attn", "mlp": "mlp", "norm": "norm",
                 "head_loss": "head_loss", "embed": "head_loss", "optimizer": "optimizer", "amp_cast": "optimizer"}
PARTS = ("attn", "mlp", "norm", "head_loss", "optimizer", "unscoped")
TRAIN_PROGRAM_SCOPES = "train_step/step"
_WORD = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")


# ------------------------------------------------------------------ spans
def window_spans(records):
    """The program's span records that ended inside the measured window,
    oldest first, or None if the program keeps no ring."""
    try:
        from paddle_tpu.observability import spans
    except ImportError:
        return None
    recent = getattr(spans, "recent", None)
    if recent is None:
        return None
    return recent(since_ns=int(records.window_open * 1e9), until_ns=int(records.window_close * 1e9))


def covered(lo, hi, intervals):
    """Length of the union of ``intervals`` inside ``[lo, hi]``."""
    return trace.total(trace.clip(trace.union(intervals), lo, hi))


def is_engine(span):
    return span.name.startswith("infer.") and not span.name.startswith(ABOVE_ENGINE)


def median_ms(spans, name):
    """Median duration of the spans called ``name``."""
    p = stats.median([s.end_ns - s.start_ns for s in spans if s.name == name])
    return None if p is None else p / 1e6


def median_self_ms(spans, name):
    """Median over the spans called ``name`` of duration minus the union of
    the direct children's intervals."""
    children = {}
    for s in spans:
        children.setdefault(s.parent_id, []).append((s.start_ns, s.end_ns))
    p = stats.median([(s.end_ns - s.start_ns) - covered(s.start_ns, s.end_ns, children.get(s.span_id, ()))
                      for s in spans if s.name == name])
    return None if p is None else p / 1e6


def engine_spans_by_tick(spans):
    """``{scheduler tick's span_id: [its outermost engine spans]}`` and the
    ticks themselves: an engine span belongs to the scheduler tick it was
    opened under, through however many scheduler spans lie between."""
    by_id = {s.span_id: s for s in spans}
    ticks = [s for s in spans if s.name == SCHED_TICK]
    inside = {t.span_id: [] for t in ticks}
    for s in spans:
        if not is_engine(s):
            continue
        up = by_id.get(s.parent_id)
        if up is None or is_engine(up):
            continue                    # not outermost, or its tick ended after the window
        while up is not None and up.name != SCHED_TICK:
            up = by_id.get(up.parent_id)
        if up is not None:
            inside[up.span_id].append(s)
    return ticks, inside


# ------------------------------------------------------------------ scopes
def part_of(op_name):
    """The part whose scope comes first in an ``op_name`` path
    (``jit(_step)/jit(main)/transpose(jvp(attn_core))/dot_general`` -> attn),
    forward, recomputed and transposed ops alike; ``unscoped`` if none."""
    for word in _WORD.findall(op_name or ""):
        if word in PART_OF_SCOPE:
            return PART_OF_SCOPE[word]
    return "unscoped"


def train_parts_ms(records):
    """Device milliseconds per training step by model part, or None. Each
    label of the trace's ``op_ns`` (``name kind shape``) is looked up by its
    instruction name in the step program's scopes; collectives and ops with
    no scope are ``unscoped``. Divided by the step program's executions in
    the traced part."""
    t = records.trace
    if t is None:
        return None
    try:
        from paddle_tpu.observability import introspect
    except ImportError:
        return None
    op_scopes = getattr(introspect, "op_scopes", None)
    scopes = op_scopes().get(TRAIN_PROGRAM_SCOPES) if op_scopes is not None else None
    _, runs = t.module_like(records.cell.family.TRAIN_PROGRAM)
    if not scopes or not runs:
        return None
    out = dict.fromkeys(PARTS, 0.0)
    for label, ns in t.op_ns.items():
        name, _, rest = label.partition(" ")
        part = "unscoped" if trace.is_collective(rest.partition(" ")[0]) else part_of(scopes.get(name))
        out[part] += ns / 1e6 / len(runs)
    return out


def train_part_ms(records, part):
    parts = train_parts_ms(records)
    return None if parts is None else parts[part]
