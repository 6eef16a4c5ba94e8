"""The device a run is on: found, checked, described. No CPU fallback."""
from __future__ import annotations

import os
import sys


class NoAccelerator(RuntimeError):
    pass


def compile_cache_dir(root: str) -> str:
    """Where the persistent compilation cache goes: where the environment
    says, else one fixed directory in the checkout (the path is part of the
    cache's key). The program's own switch is used, so the program and the
    benchmark agree on one directory."""
    from paddle_tpu.framework import flags

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        flags.set_flags({"FLAGS_compile_cache_dir": os.path.join(root, ".compile_cache")})
    return flags.ensure_compile_cache()


def require_tpu(chips: int):
    """The first ``chips`` TPU devices, or NoAccelerator."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:  # no backend could be initialised
        raise NoAccelerator(str(e)) from e
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"jax.devices()[0] is {devices[0].platform!r} ({devices[0].device_kind!r}), not a TPU")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, jax.devices() has {len(devices)}")
    return devices[:chips]


def memory_peak_bytes(devices) -> int:
    """The allocator's ``peak_bytes_in_use`` on the fullest of ``devices``."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" not in stats:
            raise RuntimeError(f"{d} reports no peak_bytes_in_use")
        peak = max(peak, int(stats["peak_bytes_in_use"]))
    return peak


def describe(devices, trace=None) -> dict:
    """The ``device`` object of the result line."""
    out = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": memory_peak_bytes(devices)}
    if trace is not None:
        out["busy_s"] = trace.busy_s
        out["window_s"] = trace.window_s
    return out


def log(*parts):
    print(*parts, file=sys.stderr, flush=True)
