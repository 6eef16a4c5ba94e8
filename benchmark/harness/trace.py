"""Recording a profiler trace and reducing it to what the metric readers use.

``record(dir)`` brackets a traced window and marks it with a host annotation
(``WINDOW_SPAN``), so the reduction knows the window on the trace's own clock.
``reduce_xplane(path, span_prefixes)`` reads the ``.xplane.pb`` with
``jax.profiler.ProfileData`` and returns a :class:`TraceSummary`:

- busy: the union of the intervals in which an operation ran on a device
  (line ``XLA Ops`` of each ``/device:TPU:<n>`` plane), clipped to the window,
  per device and averaged;
- time by operation (name, kind and result shape from the HLO text), summed
  and averaged over devices;
- idle gaps (the complement of busy in the window), each attributed to the
  innermost host span that covers its middle, spans being the host-plane
  events whose names start with one of ``span_prefixes``; ``no_span`` else;
- exposed collective time: time inside a collective with no other
  operation running on that device;
- program executions (line ``XLA Modules``) with their device durations.

Device and host events share the profiler's clock to about a millisecond
(seen on the v5e: a program's first operation is stamped ~1 ms before the
host call that launched it), so gaps shorter than ``MIN_GAP_NS`` are summed
as idle but not attributed.
"""
from __future__ import annotations

import contextlib
import functools
import glob
import gzip
import os
import re
import shutil
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.traced_window"
MIN_GAP_NS = 100_000.0
COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter", "collective-permute",
                    "all-to-all", "collective-broadcast", "ragged-all-to-all")
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")

Interval = Tuple[float, float]
TRACE_SECONDS = 5.0        # how much of the window a traced run records


@dataclass
class TraceSummary:
    window_ns: Interval
    devices: List[int]
    busy_ns: Dict[int, float]                 # device -> busy inside the window
    op_ns: Dict[str, float]                   # op label -> ns, mean over devices
    gap_ns: Dict[str, float]                  # host span (or no_span/short) -> idle ns, mean over devices
    collective_ns: Dict[int, float]           # device -> union of collective intervals
    collective_exposed_ns: Dict[int, float]   # device -> collective time with nothing else running
    modules: Dict[str, List[float]] = field(default_factory=dict)  # program -> device durations (ns), device 0
    spans: Dict[str, List[float]] = field(default_factory=dict)    # host span -> durations (ns)

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return sum(self.busy_ns.values()) / max(1, len(self.busy_ns)) / 1e9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def top_ops(self, k: int = 10) -> List[list]:
        return [[n, v / 1e9] for n, v in sorted(self.op_ns.items(), key=lambda kv: -kv[1])[:k]]

    def top_gaps(self, k: int = 10) -> List[list]:
        return [[n, v / 1e9] for n, v in sorted(self.gap_ns.items(), key=lambda kv: -kv[1])[:k]]

    def module_like(self, part: Optional[str]) -> Tuple[Optional[str], List[float]]:
        """The program whose name holds ``part`` (the one with most total time
        if several, or of all programs if ``part`` is None)."""
        best = None
        for name, durs in self.modules.items():
            if part is not None and part not in name:
                continue
            if best is None or sum(durs) > sum(self.modules[best]):
                best = name
        return best, (self.modules[best] if best is not None else [])


# ---------------------------------------------------------------- recording
@contextlib.contextmanager
def record(trace_dir: str):
    """Trace what runs inside the block. The python tracer is off (it would
    record every call); host annotations stay on."""
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str, on: bool):
    """A host span from the benchmark's own files, only in a traced run."""
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


# ---------------------------------------------------------------- intervals
def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if min(b, hi) > max(a, lo)]


def total(intervals: Sequence[Interval]) -> float:
    return float(sum(b - a for a, b in intervals))


def complement(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, at = [], lo
    for a, b in merged:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the merged intervals ``a`` not covered by the merged ``b``."""
    out: List[Interval] = []
    for lo, hi in a:
        out.extend(complement(clip(b, lo, hi), lo, hi))
    return out


# ---------------------------------------------------------------- op names
@functools.lru_cache(maxsize=None)
def parse_op(text: str) -> Tuple[str, str, str]:
    """(name, kind, result shape) of an HLO instruction as the trace names it:
    ``%fusion.12 = bf16[8,128]{1,0:T(8,128)} fusion(...), kind=kLoop``. A name
    that is not HLO text comes back whole, with empty kind and shape."""
    if " = " not in text:
        return text.lstrip("%"), "", ""
    name, rest = text.split(" = ", 1)
    if rest.startswith("("):  # tuple result: skip to its closing parenthesis
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        shape, rest = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        shape, _, rest = rest.partition(" ")
    kind = rest.split("(", 1)[0].strip()
    shape = re.sub(r"\{[^{}]*\}", "", shape)  # layouts off: bf16[8,128]
    return name.lstrip("%"), kind, shape


@functools.lru_cache(maxsize=None)
def op_label(text: str) -> str:
    """``name kind shape`` for the breakdown; of a tuple result the first
    member and how many follow."""
    name, kind, shape = parse_op(text)
    if shape.startswith("("):
        members = shape[1:-1].split(", ")
        shape = members[0] if len(members) == 1 else f"({members[0]}, +{len(members) - 1})"
    return " ".join(p for p in (name, kind, shape) if p)


def is_collective(kind: str) -> bool:
    return any(kind == c or kind.startswith(c + "-") or kind.startswith(c + ".") for c in COLLECTIVE_KINDS)


def attribute(a: float, b: float, spans: Sequence[Tuple[float, float, str]]) -> Dict[str, float]:
    """Split the gap ``[a, b]`` at the boundaries of the host spans that touch
    it and give each piece to the innermost (shortest) span covering it, or
    to ``no_span``."""
    touching = [(s, e, name) for s, e, name in spans if s < b and e > a]
    cuts = sorted({a, b, *(t for s, e, _ in touching for t in (s, e) if a < t < b)})
    out: Dict[str, float] = {}
    for lo, hi in zip(cuts, cuts[1:]):
        mid = 0.5 * (lo + hi)
        covering = [(e - s, name) for s, e, name in touching if s <= mid <= e]
        label = min(covering)[1] if covering else "no_span"
        out[label] = out.get(label, 0.0) + (hi - lo)
    return out


# ---------------------------------------------------------------- reduction
def load_profile(path: str):
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def reduce_xplane(path: str, span_prefixes: Sequence[str] = ()) -> TraceSummary:
    return reduce_profile(load_profile(path), span_prefixes)


def reduce_profile(profile, span_prefixes: Sequence[str] = ()) -> TraceSummary:
    prefixes = tuple(span_prefixes)
    device_ops: Dict[int, List[Tuple[float, float, str]]] = {}
    device_async: Dict[int, List[Tuple[float, float, str]]] = {}
    modules: Dict[str, List[float]] = {}
    host_spans: List[Tuple[float, float, str]] = []
    window: Optional[Interval] = None
    for plane in profile.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device_ops[dev] = [(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events]
                elif line.name == "Async XLA Ops":
                    device_async[dev] = [(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events]
                elif line.name == "XLA Modules" and dev == min(device_ops or {dev: 0}):
                    for e in line.events:
                        modules.setdefault(re.sub(r"\(\d+\)$", "", e.name), []).append(e.duration_ns)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif prefixes and e.name.startswith(prefixes):
                        host_spans.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
    if not device_ops:
        raise ValueError("the trace has no /device:TPU:<n> plane with an 'XLA Ops' line")
    if window is None:  # a trace not made by record(): first to last device event
        window = (min(s for ops in device_ops.values() for s, _, _ in ops),
                  max(e for ops in device_ops.values() for _, e, _ in ops))
    lo, hi = window
    n = len(device_ops)
    busy_ns, coll_ns, exposed_ns = {}, {}, {}
    op_ns: Dict[str, float] = {}
    gap_ns: Dict[str, float] = {}
    spans: Dict[str, List[float]] = {}
    for s, e, name in host_spans:
        if e > lo and s < hi:
            spans.setdefault(name, []).append(e - s)
    for dev, ops in sorted(device_ops.items()):
        inside = [(max(s, lo), min(e, hi), name) for s, e, name in ops if min(e, hi) > max(s, lo)]
        merged = union([(s, e) for s, e, _ in inside])
        busy_ns[dev] = total(merged)
        coll, rest = [], []
        for s, e, name in inside:
            kind = parse_op(name)[1]
            op_ns[op_label(name)] = op_ns.get(op_label(name), 0.0) + (e - s) / n
            if kind.endswith(("-start", "-done")) and is_collective(kind):
                continue  # the async pair's own markers; its span is on the async line
            (coll if is_collective(kind) else rest).append((s, e))
        for s, e, name in device_async.get(dev, []):
            if is_collective(parse_op(name)[1]) and min(e, hi) > max(s, lo):
                coll.append((max(s, lo), min(e, hi)))
        coll_u = union(coll)
        coll_ns[dev] = total(coll_u)
        exposed_ns[dev] = total(subtract(coll_u, union(rest)))
        for a, b in complement(merged, lo, hi):
            if b - a < MIN_GAP_NS:
                gap_ns["short_gaps"] = gap_ns.get("short_gaps", 0.0) + (b - a) / n
                continue
            for label, ns in attribute(a, b, host_spans).items():
                gap_ns[label] = gap_ns.get(label, 0.0) + ns / n
    return TraceSummary(window_ns=window, devices=sorted(device_ops), busy_ns=busy_ns, op_ns=op_ns,
                        gap_ns=gap_ns, collective_ns=coll_ns, collective_exposed_ns=exposed_ns,
                        modules=modules, spans=spans)




class TracedPart:
    """Traces the last ``TRACE_SECONDS`` of the window. The profiler is started
    inside the window (tens of milliseconds) and stopped by ``finish()`` after
    it: stopping takes seconds, which would otherwise stall the very run the
    per-layer metrics are read from."""

    def __init__(self, on: bool, trace_dir: str, records, seconds: float):
        self.on, self.dir, self.records = on, trace_dir, records
        self.start_after = seconds - min(TRACE_SECONDS, max(0.5, seconds / 2.0))
        self.stack = contextlib.ExitStack()
        self.first = None

    @property
    def running(self) -> bool:
        return self.first is not None

    def after_unit(self, unit_index: int, now: float):
        """Call after every tick or step of the window."""
        if self.on and self.first is None and now - self.records.window_open >= self.start_after:
            self.stack.enter_context(record(self.dir))
            self.first = unit_index + 1

    def finish(self, last_unit_index: int):
        if self.first is None:
            return
        self.stack.close()
        self.records.traced = (self.first, last_unit_index)
        self.records.trace = reduce_xplane(find_xplane(self.dir), self.records.cell.family.HOST_SPAN_PREFIXES)
