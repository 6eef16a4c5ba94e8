"""Cost model (parity: python/paddle/cost_model/cost_model.py).

The reference profiles a static Program per-op through the C++ profiler and
serves op time/memory tables to auto-parallel planners. TPU-first: the
whole-program cost comes from the XLA compiler itself —
``Compiled.cost_analysis()`` (flops, bytes accessed, estimated time) plus
``memory_analysis()`` (argument/output/temp allocation) computed on the
lowered executable, no measurement run needed. ``static_cost_data`` serves
the same role as the reference's static_op_benchmark.json: per-op
analytical costs extracted from the compiled module.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional

import jax
import numpy as np

__all__ = ["CostModel", "HardwareSpec", "hardware_spec", "predict_step_time"]


@dataclass(frozen=True)
class HardwareSpec:
    """Roofline constants for one device class: peak matmul throughput,
    HBM bandwidth, and interconnect (ICI/host) bandwidth. Deliberately
    coarse — the auto-parallel planner only needs costs that *rank*
    candidate plans correctly, not cycle-accurate latencies; the dominant
    signal (reshard bytes vs compute) survives a 2x constant error."""

    name: str
    flops_per_sec: float
    hbm_bytes_per_sec: float
    ici_bytes_per_sec: float


#: peaks keyed by ``jax.Device.device_kind``; a kind that is not listed is an
#: error, never a default
_KNOWN_HARDWARE = {
    # one TPU v5e chip, published peaks (Google Cloud documentation, "TPU
    # v5e"): 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s chip-to-chip ICI
    "TPU v5 lite": HardwareSpec("TPU v5 lite", 1.97e14, 8.19e11, 2.0e11),
    # host CPU (the tests' virtual mesh): not peaks — the constants only
    # matter relative to each other; comms (loopback "collectives") are
    # priced well below compute bandwidth so a reshard-heavy plan still
    # ranks worse than a clean one
    "cpu": HardwareSpec("cpu", 5.0e10, 3.0e10, 1.0e10),
}


def hardware_spec(device_kind: Optional[str] = None) -> HardwareSpec:
    """The roofline constants for ``device_kind`` (default: the kind of
    ``jax.devices()[0]``). Raises ``KeyError`` for a kind with no row."""
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind
    try:
        return _KNOWN_HARDWARE[device_kind]
    except KeyError:
        raise KeyError(
            f"no HardwareSpec for device kind {device_kind!r}; known: "
            f"{sorted(_KNOWN_HARDWARE)} — add its published peaks with their "
            "source to cost_model._KNOWN_HARDWARE") from None


def predict_step_time(flops: Optional[float], bytes_accessed: Optional[float],
                      comm_bytes: float = 0.0,
                      hw: Optional[HardwareSpec] = None) -> Dict[str, float]:
    """Analytical step-time estimate from compiled-program stats.

    Classic roofline: compute and HBM traffic overlap (the slower one
    bounds the kernel), collectives are serialized on top (XLA's
    latency-hiding scheduler overlaps some of it, so this is a pessimistic
    bound — fine for *ranking* plans, which is all the planner needs).
    Returns the component seconds plus ``total_s``.
    """
    if hw is None:
        hw = hardware_spec()
    compute_s = float(flops or 0.0) / hw.flops_per_sec
    memory_s = float(bytes_accessed or 0.0) / hw.hbm_bytes_per_sec
    comm_s = float(comm_bytes or 0.0) / hw.ici_bytes_per_sec
    return {
        "compute_s": compute_s,
        "memory_s": memory_s,
        "comm_s": comm_s,
        "total_s": max(compute_s, memory_s) + comm_s,
    }


class CostModel:
    def __init__(self):
        self._last = None

    def profile_measure(self, main_program=None, startup_program=None, device="tpu", fetch_cost_list=("time",), *, feed=None, fetch_list=None, fn=None, args=None):
        """Cost-estimate a program (reference profile_measure runs it under
        the profiler; here XLA's analytical model prices the compiled HLO).

        Either pass a recorded static ``main_program`` (+ example ``feed`` /
        ``fetch_list``) or a raw callable ``fn`` + example ``args``.
        """
        if fn is not None:
            lowered = jax.jit(fn).lower(*args)
        else:
            if main_program is None:
                raise ValueError("pass main_program= or fn=/args=")
            import jax.numpy as jnp

            from ..framework.core import unwrap
            from ..static import Executor

            exe = Executor()
            if startup_program is not None:
                exe.run(startup_program)
            prog = main_program
            feed_arrays = {k: jnp.asarray(unwrap(v)) for k, v in (feed or {}).items()}
            if "__rng_key__" in prog.feeds:
                feed_arrays["__rng_key__"] = jnp.uint32(1)
            if "__train_flag__" in prog.feeds:
                feed_arrays["__train_flag__"] = jnp.uint32(1)
            from ..framework.core import Tensor as _T
            from ..framework.static_trace import is_symbolic

            fetch_names = [f._value.name if isinstance(f, _T) and is_symbolic(f._value) else f
                           for f in (fetch_list or [])]
            train = prog.optimizer is not None or bool(prog.grad_vars)
            refs = prog.tensor_refs()
            if train and prog.grad_vars:
                params = [t for t in refs if id(t) in prog.grad_vars]
            elif train:
                params = [t for t in refs if not t.stop_gradient]
            else:
                params = []
            pids = {id(t) for t in params}
            others = [t for t in refs if id(t) not in pids]
            jit_fn = exe._build(prog, tuple(sorted(feed_arrays)), fetch_names, params, others, train)
            state = None
            if train and prog.optimizer is not None:
                ptree = {i: p._value for i, p in enumerate(params)}
                state = {"opt": prog.optimizer.core.init(ptree), "step": jnp.zeros((), jnp.int32)}
            lowered = jit_fn.lower(feed_arrays, tuple(p._value for p in params),
                                   tuple(t._value for t in others), state)
        compiled = lowered.compile()
        cost = compiled.cost_analysis() or {}
        mem = compiled.memory_analysis()
        out = {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
            "utilization": {k: float(v) for k, v in cost.items() if "utilization" in k},
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "raw": {k: float(v) for k, v in cost.items()},
        }
        self._last = out
        return out

    def static_cost_data(self) -> Optional[Dict]:
        """The last analysis (reference reads static_op_benchmark.json)."""
        return self._last

    def get_static_op_time(self, op_name: str, forward=True, dtype="float32"):
        """Per-op static costs are folded into whole-program XLA analysis on
        TPU; expose the aggregate instead of a per-op table."""
        if self._last is None:
            raise RuntimeError("run profile_measure first")
        return self._last["raw"]
