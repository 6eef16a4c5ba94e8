"""Profiler (parity: python/paddle/profiler/profiler.py:271 + C++
platform/profiler).

TPU-first: wraps ``jax.profiler`` — device traces come from XLA/xplane
(the CUPTI analog), host annotations from ``RecordEvent`` →
``jax.profiler.TraceAnnotation`` AND the native host tracer
(csrc/host_tracer.cc ≈ platform/profiler/host_tracer.cc), whose events export
as a chrome trace (chrometracing_logger.cc parity) via ``Profiler.export``.

The dispatch counters that used to live here (PR 3) are now views over the
:mod:`paddle_tpu.observability.metrics` registry — one store for counters,
gauges and histograms; ``counter_inc``/``counters``/``reset_counters`` keep
their exact signatures.
"""
from __future__ import annotations

import contextlib
import time
import warnings
from collections import defaultdict
from enum import Enum
from typing import Optional

import jax


_nlib = None  # cached handle; only Profiler.start pays the one-time build


def _native(build: bool = False):
    """The native tracer lib (None until something asked for it with
    ``build=True``).

    ``build=False`` (the per-RecordEvent path) never compiles and never takes
    the build lock — it only returns an already-loaded handle, so hot-loop
    annotations cost one cached check when profiling is off.
    """
    global _nlib
    if _nlib is not None or not build:
        return _nlib
    from ..framework import native

    _nlib = native.load_native()  # a failed build raises: an error, not a skip
    return _nlib


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1
    TPU = 2


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class RecordEvent:
    """Host-side annotation (parity: platform/profiler/event_tracing.h
    RecordEvent) that also shows up in the device trace."""

    def __init__(self, name: str, event_type=None):
        self.name = name
        self._ann = None
        self.begin_ns = None
        self.end_ns = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False

    def begin(self):
        self.begin_ns = time.perf_counter_ns()
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        lib = _native()
        if lib is not None and lib.pt_trace_enabled():
            lib.pt_trace_begin(self.name.encode(), b"host")
            self._native_open = True

    def end(self):
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if getattr(self, "_native_open", False):
            _native().pt_trace_end()
            self._native_open = False
        self.end_ns = time.perf_counter_ns()
        # spans belong to a profiling session; without one, the buffer must
        # not grow — long training loops annotate every step and would
        # otherwise leak one tuple per span forever
        if _session_active:
            _HOST_EVENTS[self.name].append((self.begin_ns, self.end_ns))


_HOST_EVENTS = defaultdict(list)
_session_active = False  # set by Profiler.start/stop: gates _HOST_EVENTS

# ---------------------------------------------------------------- counters
# Monotonic dispatch counters (reference: the op/run counts platform/profiler
# keeps per tracer), now backed by the observability metrics registry:
#   executor.runs / executor.cache_hits / executor.cache_misses /
#   executor.compiles / executor.donated_runs — Executor.run bookkeeping
#   train_step.dispatches / train_step.steps — TrainStep __call__/run_steps
# ``run_steps(k)`` adds 1 dispatch and k steps: dispatches-per-step is the
# amortization ratio (tests/test_multistep.py pins the counts).


def counter_inc(name: str, n: int = 1) -> None:
    """Bump a named dispatch counter by ``n``."""
    from ..observability import metrics

    metrics.counter_inc(name, n)


def counters(prefix: str = "") -> dict:
    """Snapshot of the counters, optionally filtered by name prefix."""
    from ..observability import metrics

    return metrics.counters(prefix)


def reset_counters(prefix: str = "") -> None:
    """Zero the counters (those matching ``prefix`` when given)."""
    from ..observability import metrics

    metrics.reset_counters(prefix)


class Profiler:
    def __init__(self, targets=None, scheduler=None, on_trace_ready=None, timer_only=False, record_shapes=False, profile_memory=False, with_flops=False):
        self.timer_only = timer_only
        self.log_dir = None
        self._running = False
        self._t0 = None
        self._t1 = None
        self._step_marks = []  # perf_counter_ns at each step() boundary

    def start(self):
        import tempfile

        global _session_active
        _HOST_EVENTS.clear()  # spans belong to one profiling session
        lib = _native(build=True)
        if lib is not None:
            lib.pt_trace_clear()
            lib.pt_trace_enable(1)
        if not self.timer_only:
            self.log_dir = tempfile.mkdtemp(prefix="paddle_tpu_prof_")
            jax.profiler.start_trace(self.log_dir)
        self._running = True
        _session_active = True
        self._t0 = time.perf_counter()
        self._t1 = None
        self._step_marks = [time.perf_counter_ns()]

    def stop(self):
        global _session_active
        if self._t0 is None:
            warnings.warn("Profiler.stop() called but start() never ran; "
                          "no profiling session to stop (no-op)", stacklevel=2)
            return
        if self._running and not self.timer_only:
            jax.profiler.stop_trace()
        lib = _native()
        if lib is not None:
            lib.pt_trace_enable(0)
        self._running = False
        _session_active = False
        self._t1 = time.perf_counter()

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    def step(self, num_samples=None):
        """Mark a training-step boundary (reference Profiler.step drives the
        scheduler state machine; here it records the boundary so summaries
        report per-step timings). Bumps the ``profiler.steps`` counter and,
        during a session, appends the elapsed step span to the host trace
        (exported as a ``profiler.step`` span in the chrome trace)."""
        counter_inc("profiler.steps")
        if not self._running:
            return
        now = time.perf_counter_ns()
        prev = self._step_marks[-1] if self._step_marks else now
        self._step_marks.append(now)
        _HOST_EVENTS["profiler.step"].append((prev, now))
        lib = _native()
        if lib is not None and lib.pt_trace_enabled():
            lib.pt_trace_instant(b"profiler.step", b"host")

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False, time_unit="ms"):
        if self._t0 is None:
            out = "no profiling session (start() never ran)"
            print(out)
            return out
        end = self._t1 if self._t1 is not None else time.perf_counter()
        lines = [f"wall time: {(end - self._t0) * 1000:.2f} ms"]
        if self.log_dir:
            lines.append(f"device trace: {self.log_dir} (open with TensorBoard/perfetto)")
        if len(self._step_marks) > 1:
            spans = [(e - b) / 1e6 for b, e in zip(self._step_marks, self._step_marks[1:])]
            lines.append(f"steps: {len(spans)} mean={sum(spans) / len(spans):.3f} ms "
                         f"min={min(spans):.3f} ms max={max(spans):.3f} ms")
        for name, spans in _HOST_EVENTS.items():
            total_ms = sum(e - b for b, e in spans) / 1e6
            lines.append(f"{name}: calls={len(spans)} total={total_ms:.3f} ms")
        out = "\n".join(lines)
        print(out)
        return out

    def export(self, path, format="json"):
        """Write the host-event chrome trace to ``path`` (device trace stays
        in ``self.log_dir`` as an xplane for TensorBoard/perfetto)."""
        if self._t0 is None:
            warnings.warn("Profiler.export() called but start() never ran; "
                          "nothing to export (no-op)", stacklevel=2)
            return None
        lib = _native(build=True)
        if lib is not None:
            if lib.pt_trace_export(str(path).encode(), b"paddle_tpu") != 0:
                raise OSError(f"failed to export trace to {path}")
            return path
        # no native toolchain: still honor the contract from python-side spans
        import json

        events = [{"name": "process_name", "ph": "M", "pid": 0, "tid": 0,
                   "args": {"name": "paddle_tpu"}}]
        for name, spans in _HOST_EVENTS.items():
            for b, e in spans:
                events.append({"name": name, "cat": "host", "ph": "X", "pid": 0,
                               "tid": 0, "ts": b / 1000, "dur": (e - b) / 1000})
        with open(path, "w") as f:
            json.dump({"traceEvents": events}, f)
        return path


@contextlib.contextmanager
def profile(log_dir: Optional[str] = None):
    """Simple context: jax.profiler.trace wrapper."""
    import tempfile

    d = log_dir or tempfile.mkdtemp(prefix="paddle_tpu_prof_")
    with jax.profiler.trace(d):
        yield d


def export_chrome_tracing(dir_name: str, worker_name=None):
    def handler(prof):
        return dir_name

    return handler


def start_profiler(state="All", tracer_option="Default"):
    jax.profiler.start_trace("/tmp/paddle_tpu_profile")


def stop_profiler(sorted_key=None, profile_path="/tmp/profile"):
    jax.profiler.stop_trace()
