"""ctypes bindings to the paddle_tpu native runtime (csrc/).

The reference framework's runtime around the compute path is C++ (allocator
``memory/allocation/``, TCPStore ``distributed/store/tcp_store.cc``, profiler
``platform/profiler/``, data feed ``framework/data_feed.cc``); on TPU the
device side of all of that is PJRT/XLA, and the host side lives in ``csrc/``
as one C-ABI shared library built here on first use with g++ (the image has
no pybind11; ctypes keeps the binding dependency-free).

Build artifacts are cached under ``build/`` keyed by a hash of the sources, so
the first import after a source change recompiles once and every later import
dlopens the cached .so.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from pathlib import Path
from typing import Optional

_REPO_ROOT = Path(__file__).resolve().parent.parent.parent
_CSRC = _REPO_ROOT / "csrc"
_BUILD_DIR = _REPO_ROOT / "build"

_lib: Optional[ctypes.CDLL] = None
_lib_err: Optional[str] = None
_lock = threading.Lock()


def _source_hash() -> str:
    h = hashlib.sha256()
    for src in sorted(_CSRC.glob("*")):
        h.update(src.name.encode())
        h.update(src.read_bytes())
    return h.hexdigest()[:16]


def _compile() -> Path:
    _BUILD_DIR.mkdir(exist_ok=True)
    so = _BUILD_DIR / f"libpaddle_tpu_native-{_source_hash()}.so"
    if so.exists():
        return so
    srcs = sorted(str(p) for p in _CSRC.glob("*.cc"))
    tmp = so.with_suffix(f".so.tmp.{os.getpid()}")  # per-process: concurrent
    # builders each link their own file; os.replace publishes atomically
    cmd = ["g++", "-O2", "-fPIC", "-shared", "-std=c++17", "-pthread",
           *srcs, "-o", str(tmp)]
    subprocess.run(cmd, check=True, capture_output=True, text=True)
    os.replace(tmp, so)
    return so


def _declare(lib: ctypes.CDLL) -> None:
    c = ctypes
    sigs = {
        "pt_buffer_free": (None, [c.c_void_p]),
        # channel
        "pt_channel_create": (c.c_void_p, [c.c_uint64]),
        "pt_channel_put": (c.c_int, [c.c_void_p, c.c_void_p, c.c_uint64]),
        "pt_channel_get": (c.c_int64, [c.c_void_p, c.POINTER(c.c_void_p)]),
        "pt_channel_close": (None, [c.c_void_p]),
        "pt_channel_size": (c.c_uint64, [c.c_void_p]),
        "pt_channel_destroy": (None, [c.c_void_p]),
        # tracer
        "pt_trace_enable": (None, [c.c_int]),
        "pt_trace_enabled": (c.c_int, []),
        "pt_trace_begin": (None, [c.c_char_p, c.c_char_p]),
        "pt_trace_end": (None, []),
        "pt_trace_instant": (None, [c.c_char_p, c.c_char_p]),
        "pt_trace_counter": (None, [c.c_char_p, c.c_double]),
        "pt_trace_event_count": (c.c_uint64, []),
        "pt_trace_clear": (None, []),
        "pt_trace_export": (c.c_int, [c.c_char_p, c.c_char_p]),
        # stats
        "pt_stat_add": (None, [c.c_char_p, c.c_int64]),
        "pt_stat_set": (None, [c.c_char_p, c.c_int64]),
        "pt_stat_get": (c.c_int64, [c.c_char_p]),
        "pt_stat_peak": (c.c_int64, [c.c_char_p]),
        "pt_stat_reset": (None, [c.c_char_p]),
        "pt_stat_clear": (None, []),
        "pt_stat_names": (c.c_int64, [c.c_char_p, c.c_int64]),
        # arena
        "pt_arena_create": (c.c_void_p, [c.c_uint64]),
        "pt_arena_alloc": (c.c_void_p, [c.c_void_p, c.c_uint64]),
        "pt_arena_free": (c.c_int, [c.c_void_p, c.c_void_p]),
        "pt_arena_allocated": (c.c_uint64, [c.c_void_p]),
        "pt_arena_reserved": (c.c_uint64, [c.c_void_p]),
        "pt_arena_destroy": (None, [c.c_void_p]),
        # store
        "pt_store_server_start": (c.c_void_p, [c.c_int]),
        "pt_store_server_port": (c.c_int, [c.c_void_p]),
        "pt_store_server_stop": (None, [c.c_void_p]),
        "pt_store_client_create": (c.c_void_p, [c.c_char_p, c.c_int, c.c_int]),
        "pt_store_client_destroy": (None, [c.c_void_p]),
        "pt_store_set": (c.c_int, [c.c_void_p, c.c_char_p, c.c_void_p, c.c_uint64]),
        "pt_store_get": (c.c_int64, [c.c_void_p, c.c_char_p, c.POINTER(c.c_void_p), c.c_int]),
        "pt_store_add": (c.c_int64, [c.c_void_p, c.c_char_p, c.c_int64]),
        "pt_store_del": (c.c_int, [c.c_void_p, c.c_char_p]),
        "pt_store_num_keys": (c.c_int64, [c.c_void_p]),
        # feed
        "pt_feed_create": (c.c_void_p, [c.c_char_p, c.c_uint64, c.c_uint64, c.c_int,
                                        c.c_uint64, c.c_int, c.c_uint64, c.c_int]),
        "pt_feed_start_epoch": (None, [c.c_void_p]),
        "pt_feed_next": (c.c_uint64, [c.c_void_p, c.POINTER(c.c_void_p)]),
        "pt_feed_destroy": (None, [c.c_void_p]),
    }
    for name, (restype, argtypes) in sigs.items():
        fn = getattr(lib, name)
        fn.restype = restype
        fn.argtypes = argtypes


def load_native() -> ctypes.CDLL:
    """Build (cached) and dlopen the native library. Raises on failure."""
    global _lib, _lib_err
    with _lock:
        if _lib is not None:
            return _lib
        if _lib_err is not None:
            raise RuntimeError(f"native library unavailable: {_lib_err}")
        try:
            so = _compile()
            lib = ctypes.CDLL(str(so))
            _declare(lib)
            _lib = lib
            return lib
        except subprocess.CalledProcessError as e:  # pragma: no cover
            _lib_err = f"compile failed: {e.stderr[-2000:] if e.stderr else e}"
            raise RuntimeError(f"native library unavailable: {_lib_err}") from e
        except OSError as e:  # pragma: no cover
            _lib_err = str(e)
            raise RuntimeError(f"native library unavailable: {_lib_err}") from e


def _take_buffer(lib: ctypes.CDLL, ptr: ctypes.c_void_p, length: int) -> bytes:
    data = ctypes.string_at(ptr, length)
    lib.pt_buffer_free(ptr)
    return data


class Channel:
    """Bounded blocking byte channel (csrc/channel.h)."""

    def __init__(self, capacity: int = 8):
        self._lib = load_native()
        self._h = self._lib.pt_channel_create(capacity)

    def put(self, data: bytes) -> bool:
        return self._lib.pt_channel_put(self._h, data, len(data)) == 0

    def get(self) -> Optional[bytes]:
        out = ctypes.c_void_p()
        n = self._lib.pt_channel_get(self._h, ctypes.byref(out))
        if n < 0:
            return None
        return _take_buffer(self._lib, out, n)

    def close(self):
        self._lib.pt_channel_close(self._h)

    def __len__(self):
        return self._lib.pt_channel_size(self._h)

    def __del__(self):
        if getattr(self, "_h", None):
            self._lib.pt_channel_destroy(self._h)
            self._h = None


class HostArena:
    """Auto-growth best-fit host arena (csrc/arena.cc)."""

    def __init__(self, chunk_size: int = 8 << 20):
        self._lib = load_native()
        self._h = self._lib.pt_arena_create(chunk_size)

    def alloc(self, size: int) -> int:
        p = self._lib.pt_arena_alloc(self._h, size)
        if not p:
            raise MemoryError(f"host arena alloc of {size} bytes failed")
        return p

    def free(self, ptr: int) -> None:
        if self._lib.pt_arena_free(self._h, ptr) != 0:
            raise ValueError("pointer not owned by this arena")

    @property
    def allocated(self) -> int:
        return self._lib.pt_arena_allocated(self._h)

    @property
    def reserved(self) -> int:
        return self._lib.pt_arena_reserved(self._h)

    def __del__(self):
        if getattr(self, "_h", None):
            self._lib.pt_arena_destroy(self._h)
            self._h = None


# ----------------------------------------------------------------- stats API
def stat_add(name: str, delta: int) -> None:
    load_native().pt_stat_add(name.encode(), delta)


def stat_set(name: str, value: int) -> None:
    load_native().pt_stat_set(name.encode(), value)


def stat_get(name: str) -> int:
    return load_native().pt_stat_get(name.encode())


def stat_peak(name: str) -> int:
    return load_native().pt_stat_peak(name.encode())


def stat_names() -> list[str]:
    lib = load_native()
    need = lib.pt_stat_names(None, 0)
    buf = ctypes.create_string_buffer(need)
    lib.pt_stat_names(buf, need)
    s = buf.value.decode()
    return s.split("\n") if s else []
