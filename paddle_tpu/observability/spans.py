"""The span: one timed section with a start, an end and a parent.

``with span("infer.sched.step"): ...`` records one :class:`Span` — name,
``start_ns``/``end_ns`` on ``time.perf_counter_ns()``, its own id, the id of
the span that was open on the same thread when it started (one thread-local
stack), and optionally a trace id and a few small attributes. On exit the
span

1. closes its :class:`paddle_tpu.profiler.RecordEvent` — a
   ``jax.profiler.TraceAnnotation``, so the same interval lies on the
   profiler's clock beside the device ops (plus the native host tracer and
   ``Profiler.export``'s chrome trace while a session is active);
2. is appended to a bounded in-memory ring — :func:`recent` reads it back,
   :func:`self_time` gives each span's duration minus what its children
   cover;
3. records its duration into the histogram metric of the same name;
4. when it belongs to a trace (its own ``trace_id``, or the enclosing
   context's — :mod:`.trace`), emits the run-log ``span`` event.

:func:`.trace.trace_span` and :func:`.trace.span_event` build the same record
and leave through the same exit path.

Gated by ``FLAGS_monitor``: when the flag is off, ``span(...)`` returns a
shared no-op context whose enter/exit are two attribute lookups — the hot
paths keep their instrumentation unconditionally.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Dict, Iterable, List, Optional

from ..framework.flags import flag
from . import metrics

__all__ = ["span", "Span", "recent", "self_time", "RING_CAPACITY"]

# A serving tick is eight spans, and one or two more for each prefill chunk it
# dispatches. The busiest 51-s window is the longgen cell's: 6,861-6,897 ticks
# since the tick runs one step ahead (PERF.md section 5, PR 33), about 55,000
# spans; the chat cell's is 4,845-4,855 ticks. The ring holds four of the
# busiest, so a decode step several times shorter still leaves a whole window
# in it, and what falls out is counted (``trace.spans_evicted``): a reader can
# tell a window that lost its first ticks from a whole one.
RING_CAPACITY = 262144
_RING: deque = deque(maxlen=RING_CAPACITY)
_IDS = itertools.count(1)


class _Stack(threading.local):
    def __init__(self):
        self.open = []   # innermost last: Span, or trace._Attach (a context without a duration)


_TLS = _Stack()


class Span:
    """One timed section. Use via ``with span(name): ...``; after exit
    ``start_ns``/``end_ns``/``seconds`` hold the interval and ``error`` is
    True when the body raised.

    ``span_id`` is a process-wide integer, or a deterministic 16-hex id
    (:func:`.trace.new_span_id`) when the span belongs to a trace and so
    reaches the run log. ``parent_id`` is the id of whatever was innermost on
    this thread's stack at entry.

    Exit is **exception-safe**: a raising body still pops the stack, closes
    the RecordEvent (so the chrome-trace nesting stays balanced for the next
    span), reaches the ring and the histogram, and emits its run-log event
    with ``error=true``. The original exception always propagates."""

    __slots__ = ("name", "start_ns", "end_ns", "span_id", "parent_id", "trace_id",
                 "attrs", "error", "_re")

    def __init__(self, name: str, trace_id: Optional[str] = None, attrs: Optional[dict] = None):
        self.name = name
        self.trace_id = trace_id
        self.attrs = attrs
        self.start_ns = self.end_ns = 0
        self.span_id = self.parent_id = None
        self.error = False
        self._re = None

    @property
    def seconds(self) -> Optional[float]:
        return (self.end_ns - self.start_ns) / 1e9 if self.end_ns else None

    def note(self, **attrs):
        """Keep a few more small values on the record (what the section counted)."""
        self.attrs = {**self.attrs, **attrs} if self.attrs else attrs  # noqa: PTA104 (host-side, never traced)

    def _link(self):
        """Take parent and trace from the innermost open entry; a span of a
        trace gets the id its run-log event will carry."""
        stack = _TLS.open
        if stack:
            self.parent_id = stack[-1].span_id  # noqa: PTA104 (host-side, never traced)
            if self.trace_id is None:
                self.trace_id = stack[-1].trace_id  # noqa: PTA104 (host-side, never traced)
        if self.trace_id is not None:
            from . import trace as _trace

            if _trace.enabled():
                self.span_id = _trace.new_span_id()  # noqa: PTA104 (host-side, never traced)
                return
        self.span_id = next(_IDS)

    def __enter__(self):
        from ..profiler import RecordEvent

        self._link()
        _TLS.open.append(self)
        self._re = RecordEvent(self.name)
        self._re.begin()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.end_ns = time.perf_counter_ns()
        self.error = exc_type is not None
        try:
            self._re.end()
        finally:
            self._re = None
            _TLS.open.pop()
            self._record()
        return False

    def _record(self):
        """The one exit path: ring, histogram, run-log event."""
        if len(_RING) == RING_CAPACITY:
            metrics.counter_inc("trace.spans_evicted")
        _RING.append(self)
        metrics.observe(self.name, (self.end_ns - self.start_ns) / 1e9)
        if isinstance(self.span_id, str):
            from . import runlog as _runlog

            metrics.counter_inc("trace.spans")
            parent = self.parent_id if isinstance(self.parent_id, str) else None
            _runlog.emit("span", name=self.name, trace=self.trace_id, span=self.span_id,
                         parent=parent, start=self.start_ns / 1e9, seconds=self.seconds,
                         error=self.error, **(self.attrs or {}))


class _NullSpan:
    """Shared no-op span for FLAGS_monitor=0 (enter/exit do nothing)."""

    __slots__ = ()
    name = ""
    trace_id = span_id = parent_id = seconds = attrs = None
    start_ns = end_ns = 0
    error = False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def note(self, **attrs):
        pass


_NULL = _NullSpan()


def span(name: str, **attrs):
    """A timed section context: real :class:`Span` when FLAGS_monitor is
    on, the shared no-op otherwise. ``attrs`` are a few small values kept on
    the record (``slots=8``)."""
    if not flag("FLAGS_monitor"):
        return _NULL
    return Span(name, attrs=attrs or None)


def recent(since_ns: int = 0, until_ns: Optional[int] = None) -> List[Span]:
    """Finished spans still in the ring that ended after ``since_ns`` (and
    at or before ``until_ns``), oldest first, on ``time.perf_counter_ns()``."""
    return [s for s in list(_RING)
            if s.end_ns > since_ns and (until_ns is None or s.end_ns <= until_ns)]


def self_time(records: Iterable[Span]) -> Dict[object, int]:
    """``span_id -> ns`` for every record: its duration minus the union of
    its children's intervals (the records whose ``parent_id`` it is), each
    clipped to the parent, so overlapping children are subtracted once."""
    records = list(records)
    children: Dict[object, list] = {}
    for r in records:
        children.setdefault(r.parent_id, []).append(r)  # noqa: PTA104 (host-side, never traced)
    out = {}
    for r in records:
        covered, at = 0, r.start_ns
        for c in sorted(children.get(r.span_id, ()), key=lambda c: c.start_ns):
            lo, hi = max(c.start_ns, at), min(c.end_ns, r.end_ns)
            if hi > lo:
                covered += hi - lo
                at = hi
        out[r.span_id] = (r.end_ns - r.start_ns) - covered  # noqa: PTA104 (host-side, never traced)
    return out
