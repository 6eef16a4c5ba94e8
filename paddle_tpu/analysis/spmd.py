"""SPMD sharding analyzer: ``PTA2xx`` passes over lowered programs.

The Program-IR passes (``PTA0xx``) and the AST linter (``PTA1xx``) both look
at what the *user wrote*; nothing inspected what actually runs on the chips.
A mis-placed ``PartitionSpec`` silently turns into a full all-gather, a
per-token collective in the serving decode loop, or an OOM discovered
minutes into compile. These passes walk the lowered-but-not-yet-dispatched
program — the post-GSPMD HLO retained by the observability AOT
``lower().compile()`` capture — plus the sharding annotations the runtime
already holds (``dist_spec`` params, ``TrainStep`` state shardings), and
turn each hazard into a structured :class:`~.diagnostics.Diagnostic`
**before dispatch**:

  PTA201  implicit full-gather of a sharded array (replication blow-up,
          with estimated bytes moved per device per dispatch)
  PTA202  spec-mismatch reshard between producer and consumer (a
          collective XLA inserted to feed a contraction)
  PTA203  collective inside a serving decode program (fires every token)
  PTA204  per-device memory estimate exceeds ``FLAGS_hbm_budget_mb`` [error]
  PTA205  cross-rank collective-schedule divergence (op-sequence/shape
          fingerprint exchanged through ``TCPStore``)            [error]
  PTA206  large parameter left fully replicated on a multi-device mesh

Entry points:
  ``shard_check(compiled, ...)``      — the ``FLAGS_shard_check`` wiring
  ``analyze_compiled(compiled, ...)`` — one executable -> SpmdReport
  ``verify_collective_schedule(...)`` — the PTA205 cross-rank exchange
  ``python -m paddle_tpu.analysis --hlo dump.txt`` — files/CLI

The JSON side (:meth:`SpmdReport.to_json`) is deliberately machine-first:
resharding bytes, collective schedule and per-device memory for any
candidate mesh/spec assignment — the evaluator the ROADMAP's cost-model
auto-parallel planner searches against.
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from . import hlo as _hlo
from .diagnostics import Diagnostic, ProgramAnalysisError

__all__ = [
    "ShardCheckOptions",
    "SpmdReport",
    "analyze_hlo_text",
    "analyze_params",
    "analyze_compiled",
    "analyze_jit",
    "verify_collective_schedule",
    "shard_check",
]

#: op_name tails that mark a collective as inserted to SERVE a contraction:
#: the producer's layout did not match what the consumer needed, i.e. a
#: producer/consumer PartitionSpec mismatch (PTA202). Deliberate user
#: collectives (lax.ppermute in the pipeline, MoE all_to_all) carry their
#: own op_name and are not reshards.
_CONTRACTION_TOKENS = ("dot_general", "dot", "conv", "einsum")


@dataclass
class ShardCheckOptions:
    """Per-check knobs. ``None`` budget defers to ``FLAGS_hbm_budget_mb``
    (0 = unlimited). The byte thresholds tier severity: a finding below the
    threshold is reported as ``info`` (visible in the JSON verdict, silent
    in the warnings stream) — tiny-model gathers are noise, the same spec
    at production shapes is the finding."""

    hbm_budget_mb: Optional[float] = None
    allgather_warn_bytes: int = 1 << 20      # PTA201/PTA202 warning floor
    replicated_param_bytes: int = 8 << 20    # PTA206 floor
    decode: bool = False                     # serving decode program (PTA203)


def _budget_mb(options: ShardCheckOptions) -> float:
    if options.hbm_budget_mb is not None:
        return float(options.hbm_budget_mb)
    from ..framework.flags import flag

    return float(flag("FLAGS_hbm_budget_mb"))


# ------------------------------------------------------------------ passes
def _tiered(bytes_moved: int, floor: int) -> str:
    return "warning" if bytes_moved >= floor else "info"


def analyze_hlo_text(hlo_text: str, options: Optional[ShardCheckOptions] = None,
                     label: str = "") -> Tuple[List[Diagnostic], List[_hlo.HloCollective]]:
    """PTA201/PTA202/PTA203 over one lowered program's HLO text.

    Returns ``(diagnostics, collectives)`` — the collective list feeds the
    schedule fingerprint and the report JSON even when no pass fires.
    """
    options = options or ShardCheckOptions()
    collectives = _hlo.parse_collectives(hlo_text)
    diags: List[Diagnostic] = []
    where = f" in {label}" if label else ""
    for c in collectives:
        moved = _hlo.moved_bytes(c)
        forced_tail = c.op_name.rsplit("/", 1)[-1] if c.op_name else ""
        is_reshard = any(tok in forced_tail for tok in _CONTRACTION_TOKENS)
        if c.kind == "all-gather":
            diags.append(Diagnostic(
                "PTA201", _tiered(moved, options.allgather_warn_bytes),
                f"implicit full-gather{where}: {c.describe()} — a sharded "
                "value is materialized replicated on every device of the "
                "group",
                hint="add/align a with_sharding_constraint (or the param's "
                     "PartitionSpec) so the consumer reads the shard it "
                     "already holds; if the gather is intended (ZeRO-3 "
                     "weights), this is its per-dispatch cost",
                op=c.name, var=c.source or None))
        if is_reshard and c.kind in ("all-gather", "all-to-all",
                                     "collective-permute"):
            diags.append(Diagnostic(
                "PTA202", _tiered(moved, options.allgather_warn_bytes),
                f"spec-mismatch reshard{where}: producer sharding does not "
                f"match what '{forced_tail}' consumes — XLA inserted "
                f"{c.describe()}",
                hint="make the producer's output spec and the consumer's "
                     "operand spec agree (classic fix: column-parallel into "
                     "row-parallel, contracting dim sharded on both sides)",
                op=c.name, var=c.source or None))
        if options.decode:
            diags.append(Diagnostic(
                "PTA203", "warning",
                f"collective inside a serving decode program{where}: "
                f"{c.describe()} — the decode hot loop pays this on every "
                "generated token",
                hint="keep single-host decode programs collective-free; on "
                     "an mp-sharded engine, budget it deliberately (it "
                     "bounds per-token latency)",
                op=c.name, var=c.source or None))
    return diags, collectives


def analyze_params(params: Dict[str, Any], shardings: Dict[str, Any],
                   options: Optional[ShardCheckOptions] = None,
                   label: str = "") -> List[Diagnostic]:
    """PTA206: large params left fully replicated on a multi-device mesh.

    ``params`` maps name -> array (or anything with shape/dtype);
    ``shardings`` maps name -> NamedSharding / PartitionSpec.
    """
    import numpy as np

    options = options or ShardCheckOptions()
    diags: List[Diagnostic] = []
    where = f" in {label}" if label else ""
    for name, arr in params.items():
        sh = shardings.get(name)
        if sh is None:
            continue
        mesh = getattr(sh, "mesh", None)
        ndev = int(getattr(mesh, "size", 1) or 1)
        if ndev <= 1:
            continue
        replicated = getattr(sh, "is_fully_replicated", None)
        if replicated is None:  # bare PartitionSpec
            replicated = all(e is None for e in tuple(sh))
        if not replicated:
            continue
        nbytes = int(np.prod(arr.shape)) * np.dtype(arr.dtype).itemsize if hasattr(arr, "shape") else 0
        if nbytes < options.replicated_param_bytes:
            continue
        diags.append(Diagnostic(
            "PTA206", "warning",
            f"parameter {name!r}{where} ({tuple(arr.shape)}, ~{nbytes:,} "
            f"bytes) is fully replicated on a {ndev}-device mesh — "
            f"{ndev}x the HBM of a sharded layout",
            hint="give it a PartitionSpec over an existing mesh axis "
                 "(shard_tensor / dist_spec), or ZeRO-shard the optimizer "
                 "state over 'sdp'",
            var=name))
    return diags


# ------------------------------------------------------------------ report
@dataclass
class SpmdReport:
    """The machine-readable verdict for one lowered program."""

    label: str = ""
    kind: str = ""
    diagnostics: List[Diagnostic] = field(default_factory=list)
    collectives: List[_hlo.HloCollective] = field(default_factory=list)
    fingerprint: str = ""
    moved_bytes: int = 0
    peak_bytes: Optional[int] = None
    hbm_budget_mb: float = 0.0

    @property
    def errors(self) -> List[Diagnostic]:
        return [d for d in self.diagnostics if d.severity == "error"]

    def counts(self) -> Dict[str, int]:
        return _hlo.collective_counts(self.collectives)

    def summary(self) -> Dict[str, Any]:
        """Flat dict for run-log events / bench JSON / report rows."""
        sev = {s: sum(1 for d in self.diagnostics if d.severity == s)
               for s in ("info", "warning", "error")}
        return {
            "label": self.label,
            "kind": self.kind,
            "collectives": self.counts(),
            "collective_count": len(self.collectives),
            "reshard_bytes": self.moved_bytes,
            "moved_bytes_by_type": _hlo.moved_bytes_by_type(self.collectives),
            "peak_bytes": self.peak_bytes,
            "fingerprint": self.fingerprint,
            "codes": sorted({d.code for d in self.diagnostics}),
            "diagnostics": sev,
        }

    def to_json(self) -> Dict[str, Any]:
        """Full verdict: summary + per-collective rows + diagnostics — the
        objective-function record a mesh/spec search consumes."""
        out = self.summary()
        out["schedule"] = [{
            "kind": c.kind, "name": c.name, "index": c.index,
            "group_size": c.group_size, "num_groups": c.num_groups,
            "bytes_moved": _hlo.moved_bytes(c),
            "result_shapes": [f"{dt}{list(dims)}" for dt, dims in c.result_shapes],
            "op_name": c.op_name, "source": c.source,
        } for c in self.collectives]
        out["findings"] = [{
            "code": d.code, "severity": d.severity, "message": d.message,
            "hint": d.hint, "op": d.op, "var": d.var,
        } for d in self.diagnostics]
        return out


def analyze_compiled(compiled, label: str = "", kind: str = "",
                     options: Optional[ShardCheckOptions] = None,
                     params: Optional[Dict[str, Any]] = None,
                     param_shardings: Optional[Dict[str, Any]] = None) -> SpmdReport:
    """Run every locally-decidable PTA2xx pass over one XLA ``Compiled``
    executable (PTA205 needs the cross-rank exchange — see
    :func:`verify_collective_schedule`). Never raises on analysis gaps: an
    executable that exposes no HLO text or memory stats just yields an
    emptier report — the analyzer must not break dispatch.
    """
    options = options or ShardCheckOptions()
    report = SpmdReport(label=label, kind=kind)
    try:
        text = compiled.as_text()
    except Exception:
        text = ""
    if text:
        report.diagnostics, report.collectives = analyze_hlo_text(
            text, options, label=label)
        report.fingerprint = _hlo.schedule_fingerprint(report.collectives)
        report.moved_bytes = _hlo.total_moved_bytes(report.collectives)
    # PTA204: per-device memory estimate vs the HBM budget
    budget = _budget_mb(options)
    report.hbm_budget_mb = budget
    try:
        from ..observability.introspect import cost_summary

        report.peak_bytes = cost_summary(compiled).get("peak_bytes")
    except Exception:
        report.peak_bytes = None
    if budget and report.peak_bytes and report.peak_bytes > budget * (1 << 20):
        report.diagnostics.append(Diagnostic(
            "PTA204", "error",
            f"per-device memory estimate for {label or 'program'} is "
            f"~{report.peak_bytes / (1 << 20):.1f} MiB, over the "
            f"FLAGS_hbm_budget_mb budget of {budget:g} MiB — this OOMs at "
            "dispatch, not at annotation time",
            hint="shard the largest replicated tensors (PTA206 names them), "
                 "enable remat/offload, or raise the budget if the device "
                 "really has the headroom"))
    # PTA206: replicated large params
    if params and param_shardings:
        report.diagnostics.extend(
            analyze_params(params, param_shardings, options, label=label))
    return report


def analyze_jit(jitfn, args: Tuple, label: str = "",
                options: Optional[ShardCheckOptions] = None, **kw) -> SpmdReport:
    """Lower + compile ``jitfn`` on ``args`` (AOT, nothing dispatched) and
    analyze the executable — the pre-flight spelling for callers that have
    not compiled yet (``Engine.prepare``, tests, the planner)."""
    from ..observability.introspect import aot_compile

    compiled, _info = aot_compile(jitfn, args)
    if compiled is None:
        return SpmdReport(label=label, kind="aot-unavailable")
    return analyze_compiled(compiled, label=label, options=options, **kw)


# ---------------------------------------------------------------- PTA205
def verify_collective_schedule(store, rank: int, world_size: int,
                               report_or_fingerprint, tag: str = "spmd",
                               timeout: Optional[float] = None,
                               max_ops: int = 512) -> List[Diagnostic]:
    """PTA205: exchange each rank's collective-schedule fingerprint through
    a :class:`~paddle_tpu.distributed.store.TCPStore` and diagnose
    divergence BEFORE any collective is dispatched.

    A rank whose lowered program issues a different collective sequence
    (extra reshard, different shape, different order) deadlocks the whole
    job at runtime; ``diagnostic_barrier`` can only name the hang after it
    happens. Here every rank publishes ``(fingerprint, op signatures)``
    under ``__shard_check__/<tag>/<rank>`` and compares against every peer;
    mismatches come back as PTA205 **error** diagnostics naming the peer
    rank and the first divergent schedule position.

    ``tag`` must be fresh per checked program (e.g. include the
    specialization label) — store keys persist.
    """
    if isinstance(report_or_fingerprint, SpmdReport):
        ops = [c.signature() for c in report_or_fingerprint.collectives]
        fp = report_or_fingerprint.fingerprint
    else:
        fp, ops = str(report_or_fingerprint), []
    payload = json.dumps({"fp": fp, "n": len(ops), "ops": ops[:max_ops]})
    store.set(f"__shard_check__/{tag}/{rank}", payload)
    diags: List[Diagnostic] = []
    for peer in range(world_size):
        if peer == rank:
            continue
        raw = store.get(f"__shard_check__/{tag}/{peer}", timeout=timeout)
        theirs = json.loads(raw if isinstance(raw, str) else raw.decode())
        if theirs["fp"] == fp:
            continue
        their_ops = theirs.get("ops", [])
        pos = next((i for i, (a, b) in enumerate(zip(ops, their_ops)) if a != b),
                   min(len(ops), len(their_ops)))
        mine_at = ops[pos] if pos < len(ops) else "<end of schedule>"
        theirs_at = their_ops[pos] if pos < len(their_ops) else "<end of schedule>"
        diags.append(Diagnostic(
            "PTA205", "error",
            f"collective schedule diverges from rank {peer} at position "
            f"{pos}: rank {rank} issues {mine_at}, rank {peer} issues "
            f"{theirs_at} (rank {rank}: {len(ops)} collectives, rank "
            f"{peer}: {theirs.get('n', len(their_ops))}) — dispatching this "
            "program deadlocks the job",
            hint="the ranks lowered different programs: check per-rank "
                 "batch shapes, flags and code version; this is the "
                 "pre-flight form of the hang diagnostic_barrier reports "
                 "after the fact"))
    return diags


# ----------------------------------------------------------------- wiring
def shard_check(compiled, component: str, label: str = "", kind: str = "",
                options: Optional[ShardCheckOptions] = None,
                params: Optional[Dict[str, Any]] = None,
                param_shardings: Optional[Dict[str, Any]] = None,
                store=None, rank: int = 0, world_size: int = 1,
                raise_on_error: bool = True) -> SpmdReport:
    """The ``FLAGS_shard_check`` body, run once per new specialization
    (mirroring ``FLAGS_static_check``): analyze, count, log, then surface —
    warnings via the warnings module, error-severity findings (PTA204
    budget, PTA205 divergence) raise :class:`ProgramAnalysisError` *before*
    the executable is ever dispatched.
    """
    import warnings as _warnings

    from ..observability import runlog as _runlog
    from ..observability.metrics import counter_inc

    report = analyze_compiled(compiled, label=label, kind=kind,
                              options=options, params=params,
                              param_shardings=param_shardings)
    if store is not None and world_size > 1:
        report.diagnostics.extend(verify_collective_schedule(
            store, rank, world_size, report, tag=f"{component}/{label or kind}"))
    counter_inc("analysis.shard_checks")
    counter_inc("analysis.diagnostics", len(report.diagnostics))
    counter_inc("analysis.collectives", len(report.collectives))
    errors = report.errors
    if errors:
        counter_inc("analysis.errors", len(errors))
    _runlog.emit("shard_check", component=component, **report.summary())
    for d in report.diagnostics:
        if d.severity == "warning":
            _warnings.warn(f"FLAGS_shard_check: {d}", stacklevel=3)
    if errors and raise_on_error:
        # PTA204/205 abort the dispatch — leave a flight-recorder dump so
        # the post-mortem carries the analysis verdict and the event tail
        from ..observability import flightrec as _flightrec

        err = ProgramAnalysisError(errors)
        _flightrec.dump("analysis_error", err, component=component,
                        label=label, kind=kind,
                        codes=[d.code for d in errors])
        raise err
    return report
