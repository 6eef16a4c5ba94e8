"""Distributed tracing plane: deterministic trace/span ids + context
propagation, the cross-process half of the PR-4 telemetry spine.

One **trace id** names one logical operation end-to-end, across every
process that touches it: a fleet request carries its id from
``ServingFleet.submit`` through Router placement, scheduler admission,
per-chunk prefill, fused decode dispatches, requeue-after-kill, and
delivery; ``run_resilient`` stamps one id on a whole supervised run so
every step event and every incident (HOLD, rollback, rescale, resume) of
that run correlates. A **span** is one timed section
(:class:`.spans.Span` — this module adds no span class and no stack of its
own); one that belongs to a trace is emitted as a ``span`` run-log event::

    {"event": "span", "name": ..., "trace": ..., "span": ...,
     "parent": ..., "start": ..., "seconds": ..., "error": false, ...attrs}

(``start`` on the process's ``time.perf_counter()``, like the ring's)

so ``observability report --merge`` / ``observability trace`` reconstruct
one request's whole path from N processes' run logs.

Ids are **deterministic**: seeded via
:func:`paddle_tpu.framework.random.host_generator` on (``paddle.seed``,
tag, ``PADDLE_TRAINER_ID``) — rank/replica-decorrelated (distinct ranks
draw independent streams) yet bitwise-replayable under chaos tests, the
same discipline as the retry-jitter stream.

Clock alignment for merged timelines: :func:`sync_clocks` publishes this
process's wall-clock epoch to a TCPStore under ``__obs__/<rank>/epoch``,
reads every peer's, and records the offset against rank 0 as a
``clock_sync`` run-log event — the merge CLI shifts each process's
timeline by that offset so cross-host skew doesn't scramble the lanes.

Gated by ``FLAGS_trace`` AND ``FLAGS_monitor``: when either is off no ids
are allocated and no span events are emitted.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Dict, Optional, Tuple

from ..framework.flags import flag
from . import metrics
from . import runlog as _runlog
from . import spans as _spans

__all__ = [
    "enabled", "new_trace_id", "new_span_id", "current_trace",
    "current_span", "attach", "trace_span", "span_event", "sync_clocks",
    "EPOCH_KEY_PREFIX",
]

EPOCH_KEY_PREFIX = "__obs__"


# One numpy generator per (seed, tag, rank): host_generator returns an
# identically-seeded stream per call, so successive ids must come from a
# cached generator, not a fresh one.
_GENS: Dict[Tuple[int, str], object] = {}
_GEN_LOCK = threading.Lock()


def enabled() -> bool:
    """Tracing is on: both the telemetry spine and the trace plane."""
    return bool(flag("FLAGS_monitor")) and bool(flag("FLAGS_trace"))


def _rank() -> str:
    return os.environ.get("PADDLE_TRAINER_ID", "0")


def _gen(tag: str):
    from ..framework import random as _random

    key = (_random._STATE.seed_value, tag)
    g = _GENS.get(key)
    if g is None:
        with _GEN_LOCK:
            g = _GENS.get(key)
            if g is None:
                g = _GENS[key] = _random.host_generator(f"trace/{tag}/{_rank()}")  # noqa: PTA104 (host-side, never traced)
    return g


def _hex_id(tag: str) -> str:
    import numpy as np

    return f"{int(_gen(tag).integers(1, 2 ** 64, dtype=np.uint64)):016x}"


def new_trace_id(tag: str = "trace") -> Optional[str]:
    """A fresh 16-hex trace id, or None when tracing is off. Deterministic:
    same seed + same tag + same rank ⇒ the same id sequence (chaos replays
    reproduce the exact trace graph); distinct ranks/replicas decorrelate
    through the rank folded into the generator tag."""
    if not enabled():
        return None
    metrics.counter_inc("trace.traces")
    return _hex_id(tag)


def new_span_id() -> str:
    return _hex_id("span")


def current_trace() -> Optional[str]:
    """The innermost open context's trace id, or None."""
    stack = _spans._TLS.open
    return stack[-1].trace_id if stack else None


def current_span() -> Optional[str]:
    """The innermost open span's (or attached context's) id, or None."""
    stack = _spans._TLS.open
    return stack[-1].span_id if stack else None


class _Attach:
    """Context manager installing (trace_id, span_id) as the current trace
    context for the block: an entry on the spans' own thread-local stack
    with no duration of its own — exception-safe, the stack pops whether or
    not the body raised."""

    __slots__ = ("trace_id", "span_id", "_pushed")

    def __init__(self, trace_id: Optional[str], span_id: Optional[str]):
        self.trace_id = trace_id
        self.span_id = span_id
        self._pushed = False

    def __enter__(self):
        if self.trace_id is not None:
            _spans._TLS.open.append(self)  # noqa: PTA104 (host-side, never traced)
            self._pushed = True  # noqa: PTA104 (host-side, never traced)
        return self

    def __exit__(self, *exc):
        if self._pushed:
            _spans._TLS.open.pop()  # noqa: PTA104 (host-side, never traced)
            self._pushed = False  # noqa: PTA104 (host-side, never traced)
        return False


def attach(trace_id: Optional[str], span_id: Optional[str] = None) -> _Attach:
    """Install an EXISTING trace id (e.g. a fleet request's) as the ambient
    context: spans opened inside the block link to it. ``trace_id=None``
    (tracing off) attaches nothing — the block runs untraced."""
    return _Attach(trace_id, span_id)


_NULL = _spans._NULL


def trace_span(name: str, trace_id: Optional[str] = None, **attrs):
    """A trace-linked timed section: a :class:`.spans.Span` carrying
    ``trace_id`` (or the ambient trace) when tracing is enabled — it emits
    its ``span`` run-log event on exit like any span of a trace — and the
    shared no-op otherwise."""
    if not enabled():
        return _NULL
    return _spans.Span(name, trace_id=trace_id, attrs=attrs or None)


def span_event(name: str, trace_id: Optional[str], seconds: Optional[float] = None,
               error: bool = False, **attrs) -> Optional[str]:
    """Record one span that the caller timed itself (the cheap spelling for
    hot loops — per-chunk prefill): the same record as a ``with`` span,
    ending now and ``seconds`` long, through the same exit path. Returns
    the span id, or None when tracing is off or there is no trace to link
    it to."""
    if not enabled() or (trace_id is None and current_trace() is None):
        return None
    sp = _spans.Span(name, trace_id=trace_id, attrs=attrs or None)
    sp._link()
    sp.end_ns = time.perf_counter_ns()
    sp.start_ns = sp.end_ns - int((seconds or 0.0) * 1e9)
    sp.error = bool(error)
    sp._record()
    return sp.span_id


def sync_clocks(store, rank: int, world_size: int,
                timeout: Optional[float] = None,
                epoch: Optional[float] = None) -> float:
    """Publish this process's wall-clock epoch under
    ``__obs__/<rank>/epoch`` and compute its offset against rank 0's.

    Every participating process calls this once (any time after the store
    rendezvous); the offset — ``own_epoch - rank0_epoch`` — lands in the
    run log as a ``clock_sync`` event, which ``observability report
    --merge`` / ``observability trace`` read to shift each process's
    timeline onto rank 0's clock. ``epoch`` overrides the sampled wall
    clock (tests inject known skew). Returns the offset in seconds."""
    own = time.time() if epoch is None else float(epoch)
    store.set(f"{EPOCH_KEY_PREFIX}/{rank}/epoch", repr(own))
    epochs = {}
    for peer in range(int(world_size)):
        if peer == rank:
            epochs[peer] = own  # noqa: PTA104 (host-side, never traced)
            continue
        raw = store.get(f"{EPOCH_KEY_PREFIX}/{peer}/epoch", timeout=timeout)
        epochs[peer] = float(raw if isinstance(raw, str) else raw.decode())  # noqa: PTA104 (host-side, never traced)
    offset = own - epochs[0]
    _runlog.emit("clock_sync", rank=int(rank), epoch=own, offset=offset,
                 world_size=int(world_size))
    return offset
