"""Device memory observability (parity: paddle.device.cuda
max_memory_allocated/max_memory_reserved/memory_allocated/memory_reserved,
backed by memory/stats.h DEVICE_MEMORY_STAT_* in the reference).

TPU-first: numbers come straight from PJRT's per-device allocator
(``Device.memory_stats()``), so they are live HBM figures, not a shadow
counter. All APIs accept a device ordinal / "tpu:N" string / None (current
device).
"""
from __future__ import annotations

import os
from typing import Optional

import jax


def _device(device=None):
    devs = jax.devices()
    if device is None:
        return devs[0]
    if isinstance(device, int):
        return devs[device]
    if isinstance(device, str):
        idx = int(device.split(":")[1]) if ":" in device else 0
        return devs[idx]
    return device  # already a jax Device


def is_tpu() -> bool:
    """The one answer to "is this a TPU": the default backend's platform is
    ``"tpu"``. Kernel predicates, benches and examples all ask here."""
    return jax.default_backend() == "tpu"


def describe() -> dict:
    """``{"platform", "kind", "count"}`` of the devices JAX reports — what
    every result line and script banner names its run by."""
    d0 = jax.devices()[0]
    return {"platform": d0.platform, "kind": d0.device_kind, "count": len(jax.devices())}


def local_tpu_chips() -> int:
    """TPU chips attached to this host, counted on the PCI bus the way JAX's
    own TPU discovery does. No backend is initialised, so a launcher that
    must leave the chips to its children can ask."""
    from jax._src import hardware_utils

    return hardware_utils.num_available_tpu_chips_and_device_id()[0]


#: libtpu process grids for one-chip-per-process jobs on one host, as JAX's
#: multi-process test launcher sets them (jax/_src/test_multiprocess.py)
_PROCESS_BOUNDS = {1: "1,1,1", 4: "2,2,1"}


def chip_env(chip: int, job_procs: int = 1, first_port: int = 0) -> dict:
    """Environment that binds a child process to local TPU chip ``chip`` —
    libtpu's process-bounds variables, set the way JAX's multi-process test
    launcher sets them. A chip belongs to one process at a time: without
    these the first child takes every chip of the host and the rest fail or
    hang.

    ``job_procs == 1`` makes the child a one-chip host of its own (a serving
    replica). ``job_procs == n`` makes it process ``chip`` of one ``n``-chip
    job whose processes find each other on ``first_port .. first_port+n-1``
    (the workers of a collective launch)."""
    if job_procs not in _PROCESS_BOUNDS:
        raise RuntimeError(
            f"no libtpu process grid known for {job_procs} one-chip processes on one "
            f"host (known: {sorted(_PROCESS_BOUNDS)})")
    env = {
        "TPU_VISIBLE_CHIPS": str(chip),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": _PROCESS_BOUNDS[job_procs],
        "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
    }
    if job_procs > 1:
        env.update({
            "CLOUD_TPU_TASK_ID": str(chip),
            "TPU_PROCESS_PORT": str(first_port + chip),
            "TPU_PROCESS_ADDRESSES": ",".join(
                f"localhost:{first_port + i}" for i in range(job_procs)),
        })
    return env


def place_on_chips(n_procs: int, what: str) -> bool:
    """Whether ``n_procs`` local child processes must each be bound to a
    chip (``chip_env``): True on a TPU host unless ``JAX_PLATFORMS`` keeps
    the children off it. Refuses more processes than the host has chips."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    chips = local_tpu_chips()
    if chips == 0 or (platforms and "tpu" not in platforms.split(",")):
        return False
    if n_procs > chips:
        raise RuntimeError(
            f"{what}: {n_procs} processes on a host with {chips} TPU chip(s) — a chip "
            "belongs to one process at a time; start at most one process per chip "
            "(the in-process ServingFleet runs any number of replicas on one chip)")
    return True


_PEAK_LIVE: dict = {}  # device id -> watermark of the live-array accounting


def _live_buffer_bytes(d) -> int:
    """Sum of live jax.Array bytes resident on ``d`` — the accounting for
    backends whose PJRT client has no allocator stats (the CPU)."""
    total = 0
    for arr in jax.live_arrays():
        devs = arr.devices()
        if d in devs:
            total += arr.nbytes // len(devs)
    return total


def memory_stats(device=None) -> dict:
    """Raw PJRT allocator stats (bytes_in_use, peak_bytes_in_use,
    bytes_limit, largest_alloc_size, ...). A TPU that reports none is an
    error; the CPU backend has no allocator stats, so there the figures are
    live-array accounting with a process-local peak watermark, marked
    ``"source": "live_arrays"``."""
    d = _device(device)
    stats = d.memory_stats()
    if stats:
        return dict(stats)
    if d.platform == "tpu":
        raise RuntimeError(f"{d} reports no allocator memory stats")
    in_use = _live_buffer_bytes(d)
    peak = max(_PEAK_LIVE.get(d.id, 0), in_use)
    _PEAK_LIVE[d.id] = peak
    return {"bytes_in_use": in_use, "peak_bytes_in_use": peak, "bytes_limit": 0, "source": "live_arrays"}


def memory_allocated(device=None) -> int:
    """Live HBM bytes currently allocated on the device."""
    return int(memory_stats(device).get("bytes_in_use", 0))


def max_memory_allocated(device=None) -> int:
    """Peak HBM bytes allocated since device initialization."""
    return int(memory_stats(device).get("peak_bytes_in_use", 0))


def memory_reserved(device=None) -> int:
    """Bytes reserved by the allocator pool (== in_use under PJRT's BFC
    accounting when no pool stat is exposed)."""
    s = memory_stats(device)
    return int(s.get("pool_bytes", s.get("bytes_in_use", 0)))


def max_memory_reserved(device=None) -> int:
    s = memory_stats(device)
    return int(s.get("peak_pool_bytes", s.get("peak_bytes_in_use", 0)))


def device_memory_limit(device=None) -> int:
    """Total usable HBM on the device (bytes_limit)."""
    return int(memory_stats(device).get("bytes_limit", 0))


def empty_cache():
    """Parity no-op: PJRT owns the HBM pool; there is no user-drainable
    cache. Kept so monitoring code ports cleanly."""
    return None


def device_count() -> int:
    return jax.device_count()


def get_device_properties(device=None):
    d = _device(device)
    return {
        "name": getattr(d, "device_kind", d.platform),
        "platform": d.platform,
        "id": d.id,
        "process_index": d.process_index,
        "total_memory": device_memory_limit(d),
    }


# -- device-query surface (reference python/paddle/device/__init__.py) -------
# On this framework the only accelerator is the TPU via PJRT; the CUDA/XPU/
# NPU/MLU/IPU predicates exist for source compatibility and answer False.

from .framework.core import get_device, set_device  # noqa: E402,F401
from .framework.param_attr import (  # noqa: E402,F401
    CPUPlace,
    CUDAPinnedPlace,
    CUDAPlace,
    NPUPlace,
    TPUPlace,
)


class XPUPlace(TPUPlace):
    pass


class MLUPlace(TPUPlace):
    pass


class IPUPlace(TPUPlace):
    pass


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_npu() -> bool:
    return False


def is_compiled_with_mlu() -> bool:
    return False


def is_compiled_with_ipu() -> bool:
    return False


def is_compiled_with_cinn() -> bool:
    # XLA is the tensor compiler here; the CINN-specific toggle is False
    return False


def get_cudnn_version():
    return None  # no cuDNN on TPU


def get_all_device_type():
    import jax

    return sorted({d.platform for d in jax.devices()} | {"cpu"})


def get_all_custom_device_type():
    return [p for p in get_all_device_type() if p not in ("cpu", "gpu", "tpu")]


def get_available_device():
    import jax

    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_available_custom_device():
    return [d for d in get_available_device()
            if d.split(":")[0] not in ("cpu", "gpu", "tpu")]


class _CudaNamespace:
    """paddle.device.cuda compatibility: memory queries map to the PJRT
    allocator stats above (reference device/cuda/__init__.py)."""

    max_memory_allocated = staticmethod(max_memory_allocated)
    max_memory_reserved = staticmethod(max_memory_reserved)
    memory_allocated = staticmethod(memory_allocated)
    memory_reserved = staticmethod(memory_reserved)
    empty_cache = staticmethod(empty_cache)
    device_count = staticmethod(device_count)
    get_device_properties = staticmethod(get_device_properties)

    @staticmethod
    def synchronize(device=None):
        import jax

        jax.block_until_ready(jax.numpy.zeros(()))


cuda = _CudaNamespace()
