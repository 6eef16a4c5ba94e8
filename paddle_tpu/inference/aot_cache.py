"""Persist AOT-compiled executables across process restarts.

A restarted engine pays the full prefill/decode compile family again before
it can serve its first token — the ROADMAP restart-latency leftover. When a
compile-cache directory is in force (``JAX_COMPILATION_CACHE_DIR``, else
``FLAGS_compile_cache_dir``), every serving program the engine
compiles is also serialized (``jax.experimental.serialize_executable`` —
the raw PJRT executable plus its call trees) under
``<dir>/serving/<key>.aotc``, keyed on the (kind, argument avals, engine
fingerprint, jax version, device) specialization. A fresh engine with the
same specialization loads the executable instead of recompiling: restart
``time_to_first_token`` drops to deserialize+dispatch cost (the benchmark's
``setup_s`` holds it: every cell starts from a warm compile cache).

The same store serves *training*: ``TrainStep`` and the static ``Executor``
round-trip their compiled step programs through ``<dir>/train_step/`` and
``<dir>/executor/`` (see ``observability.introspect.aot_compile``'s
``cache_scope``), keyed on the lowered program text — a warm restart (or an
elastic resume onto a mesh the planner already evaluated) skips straight to
dispatch, which is what cuts ``time_to_first_step``.

Everything here is best-effort: backends without executable serialization,
version drift, or a corrupt file all degrade to the normal compile path —
persistence must never break dispatch. Writes are atomic
(temp + ``os.replace``) so concurrent engines can share a directory.
"""
from __future__ import annotations

import functools
import hashlib
import os
import pickle
import zlib
from pathlib import Path
from typing import Any, Optional

__all__ = ["cache_dir", "make_key", "load", "store"]

_FORMAT = "aotc-v2"  # v2: zlib-compressed entries


def cache_dir(scope: str = "serving") -> Optional[Path]:
    """The executable cache directory for ``scope`` (serving / train_step /
    executor / ...) under the compile-cache directory in force
    (``framework.flags.compile_cache_dir``), or None when there is none."""
    from ..framework.flags import compile_cache_dir

    d = compile_cache_dir()
    if not d:
        return None
    return Path(d) / scope


@functools.lru_cache(maxsize=1)
def _code_version() -> str:
    """Hash of the package's own source files. The serving keys name a
    program by its kind, shapes and config, not by its text, and the store
    outlives the process — and, under a cache directory placed from
    outside, the checkout: without this an engine would load, and run, the
    executable an older version of the code compiled under the same key."""
    h = hashlib.sha256()
    root = Path(__file__).resolve().parent.parent
    for path in sorted(root.rglob("*.py")):
        h.update(str(path.relative_to(root)).encode())
        h.update(path.read_bytes())
    return h.hexdigest()[:16]


def _device(device=None):
    import jax

    return device if device is not None else jax.local_devices()[0]


def make_key(kind: str, sig: Any, fingerprint: str, device=None) -> str:
    """Stable content key for one compiled specialization: the program kind
    (prefill / decode / decode_xD / spec_decode / chunk / ...), the argument
    avals, the engine's config fingerprint (model dims, sampling config,
    dtypes, kv-cache dtype, and the speculative draft config + spec_k — the
    host scalars baked into the trace), the jax version, this package's
    code version, and the device the executable was built for (default: the
    first local device) — an executable is assigned to its device at
    compile time and loads only there, so each device keeps its own
    entries."""
    import jax

    d = _device(device)
    payload = repr((_FORMAT, kind, sig, fingerprint, jax.__version__, _code_version(),
                    d.platform, d.device_kind, d.id))
    return hashlib.sha256(payload.encode()).hexdigest()[:32]


def load(key: str, scope: str = "serving", device=None):
    """Deserialize + load the executable stored under ``key`` onto
    ``device`` (the one ``make_key`` was given); None on any miss or
    failure (caller compiles normally)."""
    d = cache_dir(scope)
    if d is None:
        return None
    path = d / f"{key}.aotc"
    if not path.exists():
        return None
    try:
        from jax.experimental.serialize_executable import deserialize_and_load

        dev = _device(device)
        payload, in_tree, out_tree = pickle.loads(zlib.decompress(path.read_bytes()))
        # without execution_devices the executable is loaded for every
        # device of the backend and refuses one-device arguments
        return deserialize_and_load(payload, in_tree, out_tree,
                                    backend=dev.client, execution_devices=[dev])
    except Exception:
        return None


def store(key: str, compiled, scope: str = "serving") -> bool:
    """Serialize ``compiled`` (an XLA ``Compiled`` from ``lower().compile()``)
    under ``key``. False (and no file) when the backend can't serialize
    executables or the directory is unwritable."""
    d = cache_dir(scope)
    if d is None:
        return False
    tmp = None
    try:
        from jax.experimental.serialize_executable import serialize

        payload, in_tree, out_tree = serialize(compiled)
        d.mkdir(parents=True, exist_ok=True)
        tmp = d / f".{key}.{os.getpid()}.tmp"
        # level 1: a serialized TPU executable is hundreds of MB raw and
        # mostly compressible; the cache directory is often size-capped
        tmp.write_bytes(zlib.compress(pickle.dumps((payload, in_tree, out_tree)), 1))
        os.replace(tmp, d / f"{key}.aotc")
        return True
    except Exception:
        if tmp is not None:
            try:
                tmp.unlink()
            except OSError:
                pass
        return False
