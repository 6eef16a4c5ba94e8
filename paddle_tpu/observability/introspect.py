"""Compiled-program introspection: what does each specialization cost?

At every Executor/TrainStep compile the runtime lowers through jax.jit's
AOT path (``.lower(...).compile()``) so the XLA ``Compiled`` handle — the
only object that answers ``cost_analysis()``/``memory_analysis()`` — is
retained instead of being buried in jit's internal cache. The analysis is
flattened (CPU builds omit fields) into one dict::

    {"flops", "bytes_accessed", "argument_bytes", "output_bytes",
     "temp_bytes", "peak_bytes", "generated_code_bytes"}

``Executor.explain()`` / ``TrainStep.explain()`` return one such row per
cached specialization; :func:`format_cost_table` renders them for humans.

The retained handles also join a device trace back to the model:
:func:`op_scopes` maps each compiled program's HLO instruction names
(``fusion.939`` — what a profiler's ``XLA Ops`` event is called) to the
``op_name`` path in that instruction's metadata, which carries the
``jax.named_scope`` names of the code that produced it
(``jit(_step)/jit(main)/transpose(jvp(norm))/...``). The trace itself has no
``op_name``, so the join goes through the program's own optimized HLO text.
"""
from __future__ import annotations

import re
import time
from typing import Any, Dict, List, Optional, Tuple

__all__ = ["cost_summary", "aot_compile", "format_cost_table", "note_program",
           "op_scopes", "parse_op_names"]

_PARTITION_RE = None

# in-process executable memo behind FLAGS_compile_cache_dir: (scope, text
# hash) -> (Compiled, info). The cross-mesh warm-start store — the planner
# compiles candidate programs during the elastic HOLD window and the
# resumed TrainStep (same process) dispatches the memoized executable with
# zero recompile. Bounded; single-device programs ALSO persist to disk.
_EXEC_MEMO: dict = {}
_EXEC_MEMO_CAP = 8


# program name ("train_step/step", "infer/decode", ...) -> the executable's
# Compiled handle until op_scopes() first reads it, then the parsed map. A
# handle holds the executable and its signature — no argument, no state
# buffer — so it outlives the TrainStep or engine that built it at no cost in
# device memory beyond the program's code.
_PROGRAMS: Dict[str, Any] = {}
_HLO_OP_NAME = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = [^\n]*?metadata=\{[^}\n]*?op_name="([^"]*)"', re.M)


def note_program(name: str, compiled) -> None:
    """Remember the executable behind program ``name`` for :func:`op_scopes`.
    Called where a program is compiled or AOT-loaded; costs one dict write —
    the HLO text is read on the first ``op_scopes()`` call, not here. A later
    specialization of the same program replaces the earlier one."""
    if hasattr(compiled, "as_text"):
        _PROGRAMS[name] = compiled  # noqa: PTA104 (host-side, never traced)


def parse_op_names(hlo_text: str) -> Dict[str, str]:
    """``instruction name -> op_name`` for every instruction of an optimized
    HLO module's text that carries ``metadata={op_name="..."}``."""
    return dict(_HLO_OP_NAME.findall(hlo_text))


def op_scopes() -> Dict[str, Dict[str, str]]:
    """Per program this process has compiled or AOT-loaded, the map from HLO
    instruction name to the ``op_name`` path of its metadata (see the module
    docstring). Parsed from the retained handle's optimized HLO text on the
    first call that finds it; the handle is dropped once parsed."""
    out = {}
    for name, entry in list(_PROGRAMS.items()):  # noqa: PTA102 (host-side, never traced)
        if not isinstance(entry, dict):
            entry = _PROGRAMS[name] = parse_op_names(entry.as_text())  # noqa: PTA104 (host-side, never traced)
        out[name] = entry  # noqa: PTA104 (host-side, never traced)
    return out


def _no_persistent_compile_cache():
    """Context: jax's persistent compilation cache off for one compile.
    Serializing multi-device CPU executables corrupts the heap on this jax
    build — the cache must only see single-device programs."""
    import contextlib

    import jax

    @contextlib.contextmanager
    def cm():
        current = jax.config.jax_compilation_cache_dir
        if not current:
            yield
            return
        jax.config.update("jax_compilation_cache_dir", None)
        try:
            yield
        finally:
            jax.config.update("jax_compilation_cache_dir", current)

    return cm()


def _is_single_device(lowered_text: str) -> bool:
    """True when the lowered StableHLO module targets one device
    (``mhlo.num_partitions * mhlo.num_replicas == 1``); unknown counts as
    multi-device (conservative — skips executable serialization)."""
    global _PARTITION_RE  # noqa: PTA105 (host-side, never traced)
    if _PARTITION_RE is None:
        import re

        _PARTITION_RE = re.compile(
            r"mhlo\.num_(partitions|replicas)\s*=\s*(\d+)")
    found = {m.group(1): int(m.group(2))
             for m in _PARTITION_RE.finditer(lowered_text[:4096])}
    if not found:
        return False
    return found.get("partitions", 1) * found.get("replicas", 1) == 1


_PRIVATE_FUNC = re.compile(r"func\.func private @([\w.]+)")
_SYMBOL = re.compile(r"@([\w.]+)")


def _program_key(lowered_text: str) -> str:
    """The lowered text with its private functions renamed in order of
    definition. jax numbers them (``@_where_32``) from a counter that two
    lowerings of one program do not share — the planner's, on abstract
    arguments, and the dispatch's, on arrays, differ in nothing else — so the
    raw text names an executable once and never finds it again."""
    names = {n: f"f{i}" for i, n in enumerate(_PRIVATE_FUNC.findall(lowered_text))}
    return _SYMBOL.sub(lambda m: "@" + names.get(m.group(1), m.group(1)), lowered_text)


def cost_summary(compiled) -> Dict[str, Any]:
    """Normalized cost/memory analysis of one XLA ``Compiled`` executable.
    Every field degrades to None when the backend does not report it, so
    CPU-only CI sees the same schema as TPU."""
    cost = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    arg = getattr(mem, "argument_size_in_bytes", None)
    out_b = getattr(mem, "output_size_in_bytes", None)
    tmp = getattr(mem, "temp_size_in_bytes", None)
    gen = getattr(mem, "generated_code_size_in_bytes", None)
    peak = None
    known = [b for b in (arg, out_b, tmp) if b is not None]
    if known:
        # XLA's own peak stat when present; else the live-set upper bound
        peak = getattr(mem, "peak_memory_in_bytes", None) or sum(known)
    return {
        "flops": float(cost["flops"]) if "flops" in cost else None,
        "bytes_accessed": float(cost["bytes accessed"]) if "bytes accessed" in cost else None,
        "argument_bytes": arg,
        "output_bytes": out_b,
        "temp_bytes": tmp,
        "peak_bytes": peak,
        "generated_code_bytes": gen,
    }


def aot_compile(jitfn, args: Tuple,
                cache_scope: Optional[str] = None) -> Tuple[Optional[Any], Dict[str, Any]]:
    """Lower + compile ``jitfn`` on ``args`` through the AOT path.

    Returns ``(compiled, info)`` where ``compiled`` is the callable XLA
    executable (donation/sharding from the jit wrapper preserved) and
    ``info`` is :func:`cost_summary` plus ``compile_seconds``. On any
    failure returns ``(None, {...})`` so callers fall back to the plain
    jitted call — introspection must never break dispatch.

    With ``cache_scope`` (and ``FLAGS_compile_cache_dir`` set), the
    executable round-trips through the on-disk AOT store
    (``inference.aot_cache``) under ``<dir>/<cache_scope>/``, keyed on the
    *lowered program text* (:func:`_program_key`) — identical trace, identical
    executable, no fingerprint guessing. A hit skips the XLA compile entirely
    (``info["from_disk_cache"] = True``); a fresh compile is serialized
    back (``info["aot_cache_stored"] = True``) so the next process restart
    — or an elastic resume onto a mesh the planner already evaluated —
    starts warm. Best-effort like everything here: serialization failures
    degrade to the normal compile.
    """
    import jax

    t0 = time.perf_counter()
    try:
        lowered = jitfn.lower(*args)
    except Exception as exc:  # AOT unsupported for this fn/args shape
        return None, {"compile_seconds": time.perf_counter() - t0,
                      "aot_error": f"{type(exc).__name__}: {exc}"}
    # Executable serialization is only trusted for SINGLE-device programs:
    # serializing a multi-device CPU executable (ours via
    # serialize_executable, jax's via the persistent compilation cache)
    # corrupts the process heap on this jax build. Multi-device warm starts
    # come from the in-process memo instead — the planner compiles the
    # winning program during the elastic HOLD window, same process.
    text = None
    single_device = None
    persistent_cache_on = bool(jax.config.jax_compilation_cache_dir)
    if persistent_cache_on or cache_scope is not None:
        try:
            text = _program_key(lowered.as_text())
            single_device = _is_single_device(text)
        except Exception:
            text = None
    key = None
    if cache_scope is not None and text is not None:
        from ..inference import aot_cache

        if aot_cache.cache_dir(cache_scope) is not None:
            memo = _EXEC_MEMO.get((cache_scope, text))
            if memo is not None:
                compiled, info = memo
                info = dict(info)
                info["compile_seconds"] = time.perf_counter() - t0  # noqa: PTA104 (host-side, never traced)
                info["from_memory_cache"] = True  # noqa: PTA104 (host-side, never traced)
                info["from_disk_cache"] = True  # same counter semantics  # noqa: PTA104 (host-side, never traced)
                return compiled, info
            if single_device:
                key = aot_cache.make_key(cache_scope, text, "")
                loaded = aot_cache.load(key, scope=cache_scope)
                if loaded is not None:
                    try:
                        info = cost_summary(loaded)
                    except Exception:
                        info = {}
                    info["compile_seconds"] = time.perf_counter() - t0  # noqa: PTA104 (host-side, never traced)
                    info["from_disk_cache"] = True  # noqa: PTA104 (host-side, never traced)
                    return loaded, info
        else:
            cache_scope = None  # no cache dir: skip memo insertion too
    try:
        if single_device is False and persistent_cache_on:
            with _no_persistent_compile_cache():
                compiled = lowered.compile()
        else:
            compiled = lowered.compile()
    except Exception as exc:
        return None, {"compile_seconds": time.perf_counter() - t0,
                      "aot_error": f"{type(exc).__name__}: {exc}"}
    info = cost_summary(compiled)
    info["compile_seconds"] = time.perf_counter() - t0
    if key is not None:
        from ..inference import aot_cache

        if aot_cache.store(key, compiled, scope=cache_scope):
            info["aot_cache_stored"] = True  # noqa: PTA104 (host-side, never traced)
    if cache_scope is not None and text is not None:
        _EXEC_MEMO[(cache_scope, text)] = (compiled, dict(info))  # noqa: PTA104 (host-side, never traced)
        while len(_EXEC_MEMO) > _EXEC_MEMO_CAP:
            _EXEC_MEMO.pop(next(iter(_EXEC_MEMO)))  # noqa: PTA104 (host-side, never traced)
    return compiled, info


_COLUMNS = (
    ("flops", "GFLOP", 1e9),
    ("bytes_accessed", "MB moved", 1e6),
    ("peak_bytes", "MB peak", 1e6),
    ("compile_seconds", "compile s", 1.0),
)


def format_cost_table(rows: List[dict], title: str = "specialization") -> str:
    """Human-readable per-specialization cost table from ``explain()`` rows."""
    if not rows:
        return "(no compiled specializations)"
    header = [title] + [label for _, label, _ in _COLUMNS]
    body = []
    for row in rows:
        cells = [str(row.get("label", row.get("key", "?")))]
        for field, _, scale in _COLUMNS:  # noqa: PTA102 (host-side, never traced)
            v = row.get(field)
            cells.append("-" if v is None else f"{v / scale:.3f}")  # noqa: PTA104 (host-side, never traced)
        body.append(cells)  # noqa: PTA104 (host-side, never traced)
    widths = [max(len(r[i]) for r in [header] + body) for i in range(len(header))]
    fmt = "  ".join(f"{{:<{w}}}" for w in widths)
    lines = [fmt.format(*header), fmt.format(*["-" * w for w in widths])]
    lines += [fmt.format(*r) for r in body]
    return "\n".join(lines)
