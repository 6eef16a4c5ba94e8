"""Cost-model-driven auto-parallel planner.

Closes the loop ROADMAP has named since PR 9: the SPMD sharding analyzer
(``analysis/spmd.py`` PTA2xx) returns a machine-readable verdict — reshard
bytes, collective schedule, per-device memory — for *any* candidate
mesh/spec assignment from shapes alone. This module is the search on top:

1. **Enumerate** candidate plans: every factorization of the device count
   over the ``dp``/``sdp``/``mp``/``pp`` axes (the MULTICHIP dryrun
   families), crossed with PartitionSpec templates for the parameters
   (the model's own ``dist_spec`` annotations, fully replicated, or any
   user-supplied template) and the ZeRO stage over ``sdp``.
2. **Evaluate** each via the ``Engine.prepare(analyze=True)`` path: the
   step program is lowered on ``ShapeDtypeStruct``s under the candidate
   shardings — nothing is dispatched, no batch exists — and scored from
   the ``SpmdReport`` (ring-accounting reshard/collective bytes), the
   compiled-program cost analysis (flops, bytes accessed) and the
   per-device memory estimate vs ``FLAGS_hbm_budget_mb``. Plans whose
   static state-memory floor already exceeds the budget are pruned
   *before* compiling (the PTA204 rule applied pre-flight); plans whose
   compiled estimate overruns are marked infeasible by the analyzer's
   PTA204 error.
3. **Rank** by predicted step time (``cost_model.predict_step_time``
   roofline: max(compute, HBM) + collectives) — a mis-sharded spec's
   extra all-gathers surface as comm seconds, so it scores strictly worse
   than the clean twin.

Ranked plans are cached as JSON under ``FLAGS_compile_cache_dir/planner/``
keyed on (model fingerprint, device count, input shapes, search space):
a restart pays zero search. Because evaluation compiles the *same* lowered
program the real ``TrainStep`` will dispatch (and stores it in the AOT
executable cache under ``cache_scope="train_step"``), searching during an
elastic HOLD window warm-starts the new mesh's compilation: the resumed
step loads the executable instead of compiling.

Entry points::

    plans = planner.search(model, n_devices, inputs_spec=..., loss=...)
    step  = planner.build_step(model, opt, loss, plans[0])   # sharded TrainStep
    on_rescale = planner.elastic_replan(model, opt, loss, ...)  # run_resilient hook
    python -m paddle_tpu.distributed.planner --devices 8 --json
"""
from __future__ import annotations

import hashlib
import json
import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = [
    "Plan", "PlannerError", "mesh_shapes", "annotated_specs",
    "row_sharded_specs", "default_templates",
    "abstract_inputs", "search", "build_step", "elastic_replan",
    "format_plan_table", "main",
]

#: search axes in the canonical (topology.AXES) order; 'sep' is a
#: green-field sequence axis and 'pp' cannot SPMD-compile on the CPU
#: backend (pre-existing PartitionId limitation), so the default space is
#: dp × sdp × mp — pass axes=... to widen.
DEFAULT_AXES: Tuple[str, ...] = ("dp", "sdp", "mp")


class PlannerError(RuntimeError):
    """The search cannot run (no devices, missing specs, ...)."""


# ---------------------------------------------------------------- candidates
def mesh_shapes(n_devices: int, axes: Sequence[str] = DEFAULT_AXES) -> List[Dict[str, int]]:
    """Every ordered factorization of ``n_devices`` over ``axes`` as an
    axis-degree dict (degree-1 axes omitted). ``n_devices=4, axes=(dp,mp)``
    -> ``[{}:dp4, {dp:2,mp:2}, {mp:4}]``-style candidates."""
    axes = tuple(axes)

    def rec(remaining: int, rest: Tuple[str, ...]):
        if not rest:
            if remaining == 1:
                yield {}
            return
        ax = rest[0]
        d = 1
        while d <= remaining:
            if remaining % d == 0:
                for tail in rec(remaining // d, rest[1:]):
                    out = dict(tail)
                    if d > 1:
                        out[ax] = d  # noqa: PTA104 (host-side, never traced)
                    yield out
            d += 1

    seen, out = set(), []
    for m in rec(int(n_devices), axes):
        key = tuple(sorted(m.items()))
        if key not in seen:
            seen.add(key)  # noqa: PTA104 (host-side, never traced)
            out.append(m)  # noqa: PTA104 (host-side, never traced)
    return out


def annotated_specs(model) -> Dict[str, Any]:
    """The model's own ``dist_spec`` annotations (mp_layers /
    ``shard_tensor``) as a param-name -> PartitionSpec template."""
    return {n: p.dist_spec for n, p in model.named_parameters()
            if getattr(p, "dist_spec", None) is not None}


def row_sharded_specs(model) -> Dict[str, Any]:
    """Row-shard specs for params flagged ``_row_shard_axis``
    (``ShardedEmbedding`` tables): a production-vocab table replicated
    across the mesh is exactly the PTA206 waste finding, so the planner's
    default templates must never emit it."""
    from jax.sharding import PartitionSpec as P

    return {n: P(p._row_shard_axis) for n, p in model.named_parameters()
            if getattr(p, "_row_shard_axis", None)}


def default_templates(model) -> Dict[str, Dict[str, Any]]:
    """The template set ``search`` uses when none is supplied: the model's
    annotations (plus embedding row specs) and a replicated baseline —
    which still row-shards ``ShardedEmbedding`` tables, since replicating
    them is never a candidate worth scoring at production vocab sizes."""
    ann = annotated_specs(model)
    row = row_sharded_specs(model)
    templates: Dict[str, Dict[str, Any]] = (
        {"annotated": {**row, **ann}} if (ann or row) else {})
    templates.setdefault("replicated", dict(row))  # noqa: PTA104 (host-side, never traced)
    return templates


def _spec_entries(spec) -> List:
    """PartitionSpec -> JSON-able entry list (None | axis | [axes])."""
    out = []
    for e in tuple(spec):
        if e is None:
            out.append(None)  # noqa: PTA104 (host-side, never traced)
        elif isinstance(e, (tuple, list)):
            out.append(list(e))  # noqa: PTA104 (host-side, never traced)
        else:
            out.append(str(e))  # noqa: PTA104 (host-side, never traced)
    return out


def _entries_spec(entries: Sequence):
    from jax.sharding import PartitionSpec as P

    return P(*[tuple(e) if isinstance(e, list) else e for e in entries])


def abstract_inputs(specs, fill: int = 1) -> Tuple:
    """Input specs (static.InputSpec / ShapeDtypeStruct / arrays) ->
    ``jax.ShapeDtypeStruct`` tuple; dynamic dims (None / -1) are filled
    with ``fill`` (the device count divides every axis product by
    construction, so a fill of n_devices shards cleanly)."""
    import jax

    specs = specs if isinstance(specs, (list, tuple)) else [specs]
    out = []
    for s in specs:
        shape = tuple(int(d) if (d is not None and int(d) > 0) else int(fill)
                      for d in s.shape)
        out.append(jax.ShapeDtypeStruct(  # noqa: PTA104 (host-side, never traced)
            shape, np.dtype(getattr(s, "dtype", "float32"))))
    return tuple(out)


# ---------------------------------------------------------------------- Plan
@dataclass
class Plan:
    """One candidate (and, after evaluation, scored) parallel plan."""

    mesh: Dict[str, int]                  # axis -> degree (degree>1 only)
    template: str                         # spec-template name
    stage: int = 0                        # ZeRO stage over 'sdp'
    n_devices: int = 1
    param_specs: Dict[str, List] = field(default_factory=dict)
    # -- evaluation results -------------------------------------------------
    score: float = float("inf")           # predicted step seconds
    predicted_step_ms: Optional[float] = None
    compute_ms: Optional[float] = None
    comm_ms: Optional[float] = None
    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None
    comm_bytes: int = 0                   # est. reshard/collective bytes
    collectives: Dict[str, int] = field(default_factory=dict)
    peak_bytes: Optional[int] = None
    memory_floor_bytes: int = 0           # static state bytes / device
    feasible: bool = True
    pruned: str = ""                      # why infeasible, when not
    codes: List[str] = field(default_factory=list)  # PTA finding codes
    fingerprint: str = ""                 # collective-schedule digest
    compile_seconds: Optional[float] = None
    from_cache: bool = False              # summary came from the plan cache

    @property
    def label(self) -> str:
        mesh = "x".join(f"{a}{d}" for a, d in sorted(self.mesh.items())) or "single"
        tail = f"/zero{self.stage}" if self.stage else ""
        return f"{mesh}/{self.template}{tail}"

    def summary(self) -> Dict[str, Any]:
        """JSON-able record (the plan-cache row / bench ``plan`` payload)."""
        return {
            "label": self.label, "mesh": dict(self.mesh),
            "template": self.template, "stage": self.stage,
            "n_devices": self.n_devices, "param_specs": self.param_specs,
            "score": self.score if self.score != float("inf") else None,
            "predicted_step_ms": self.predicted_step_ms,
            "compute_ms": self.compute_ms, "comm_ms": self.comm_ms,
            "flops": self.flops, "bytes_accessed": self.bytes_accessed,
            "comm_bytes": self.comm_bytes, "collectives": dict(self.collectives),
            "peak_bytes": self.peak_bytes,
            "memory_floor_bytes": self.memory_floor_bytes,
            "feasible": self.feasible, "pruned": self.pruned,
            "codes": list(self.codes), "fingerprint": self.fingerprint,
            "compile_seconds": self.compile_seconds,
        }

    @classmethod
    def from_summary(cls, d: Dict[str, Any]) -> "Plan":
        plan = cls(mesh=dict(d.get("mesh") or {}),
                   template=d.get("template", "?"),
                   stage=int(d.get("stage", 0)),
                   n_devices=int(d.get("n_devices", 1)),
                   param_specs=dict(d.get("param_specs") or {}))
        plan.score = d["score"] if d.get("score") is not None else float("inf")
        for k in ("predicted_step_ms", "compute_ms", "comm_ms", "flops",
                  "bytes_accessed", "peak_bytes", "compile_seconds"):
            setattr(plan, k, d.get(k))
        plan.comm_bytes = int(d.get("comm_bytes") or 0)
        plan.collectives = dict(d.get("collectives") or {})
        plan.memory_floor_bytes = int(d.get("memory_floor_bytes") or 0)
        plan.feasible = bool(d.get("feasible", True))
        plan.pruned = d.get("pruned", "")
        plan.codes = list(d.get("codes") or [])
        plan.fingerprint = d.get("fingerprint", "")
        plan.from_cache = True
        return plan

    # ------------------------------------------------------------ builders
    def build_mesh(self, devices=None):
        """The jax Mesh this plan shards over (canonical dp/pp/sdp/mp/sep
        axis order via HybridCommunicateGroup)."""
        import jax

        from .topology import HybridCommunicateGroup

        devices = list(devices if devices is not None else jax.devices())
        if self.n_devices > len(devices):
            raise PlannerError(
                f"plan {self.label!r} needs {self.n_devices} devices, have "
                f"{len(devices)}")
        hcg = HybridCommunicateGroup(
            dp_degree=self.mesh.get("dp", 1), mp_degree=self.mesh.get("mp", 1),
            pp_degree=self.mesh.get("pp", 1),
            sharding_degree=self.mesh.get("sdp", 1),
            sep_degree=self.mesh.get("sep", 1), devices=devices)
        return hcg.mesh

    def resolved_specs(self) -> Dict[str, Any]:
        """param name -> PartitionSpec (decoded from the JSON entries)."""
        return {n: _entries_spec(e) for n, e in self.param_specs.items()}


# -------------------------------------------------------------- evaluation
def _fleet_mesh_scope(mesh):
    """Trace-time override of the global fleet mesh.

    The model forward reads ``fleet._hcg.mesh`` for its activation
    sharding constraints (gpt trunk carry pin, mp_layers ``_constraint``).
    A planner candidate evaluates on its OWN mesh, which may differ from —
    or outlive — whatever a previous ``fleet.init`` left behind; tracing
    under the global mesh then fails with incompatible device sets. This
    scope pins the constraint mesh to the candidate for the duration of a
    trace (only ``.mesh`` is read on the trace path).
    """
    import contextlib
    import types

    from .fleet import fleet as _fleet

    @contextlib.contextmanager
    def cm():
        prior = _fleet._hcg
        _fleet._hcg = types.SimpleNamespace(mesh=mesh)
        try:
            yield
        finally:
            _fleet._hcg = prior

    return cm()


def _scoped_step_fn(step, mesh):
    """``step._step`` wrapped so every TRACE of it (jit lower, scan body,
    re-specialization at dispatch time) sees the candidate mesh — not the
    global fleet state of whenever the trace happens to run."""

    def scoped_step(state, batch):
        with _fleet_mesh_scope(mesh):
            return step._step(state, batch)

    return scoped_step


def _sharded_jit(step, mesh, shardings, batch_sharding):
    """The exact jit the planner scores AND ``build_step`` dispatches —
    one construction site so the lowered program (and therefore the AOT
    executable-cache key) is identical between search and training."""
    import jax

    return jax.jit(_scoped_step_fn(step, mesh), donate_argnums=0,
                   in_shardings=(shardings, batch_sharding),
                   out_shardings=(shardings, None))


def _state_bytes_per_device(abstract_state, shardings) -> int:
    """Static per-device memory floor: the state tree's bytes after
    sharding (params + optimizer moments + buffers). The live-set peak is
    at least this — computable without lowering anything, so over-budget
    plans are pruned before a single compile."""
    from jax.tree_util import keystr, tree_flatten_with_path

    flat_sh = {keystr(p): s for p, s in tree_flatten_with_path(shardings)[0]}
    total = 0
    for path, leaf in tree_flatten_with_path(abstract_state)[0]:  # noqa: PTA102 (host-side, never traced)
        try:
            itemsize = np.dtype(leaf.dtype).itemsize
        except (TypeError, AttributeError):
            continue  # typed PRNG keys etc. — negligible  # noqa: PTA103 (host-side, never traced)
        shape = tuple(leaf.shape)
        sh = flat_sh.get(keystr(path))
        if sh is not None:
            try:
                shape = sh.shard_shape(shape)
            except Exception:
                pass
        total += int(np.prod(shape)) * itemsize
    return total


def _evaluate(plan: Plan, step, abstract_state, abstract_batch, devices,
              budget_mb: float, hw, options) -> Plan:
    """Score one candidate from shapes alone: lower + compile under the
    candidate shardings (AOT — nothing dispatched), run the SPMD analyzer,
    price the verdict with the roofline."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..analysis import spmd as _spmd
    from ..cost_model import predict_step_time
    from ..observability.introspect import aot_compile
    from ..observability.metrics import counter_inc
    from .sharding import param_placement, state_shardings

    counter_inc("planner.evaluations")
    mesh = plan.build_mesh(devices)
    mp_specs = plan.resolved_specs()
    shardings = state_shardings(step.state, mesh, stage=plan.stage,
                                mp_specs=mp_specs)
    plan.memory_floor_bytes = _state_bytes_per_device(abstract_state, shardings)
    if budget_mb and plan.memory_floor_bytes > budget_mb * (1 << 20):
        # PTA204 applied pre-flight: the state alone cannot fit, no point
        # paying a compile to learn the peak is even higher
        plan.feasible = False  # noqa: PTA104 (host-side, never traced)
        plan.pruned = (f"PTA204: static state floor "  # noqa: PTA104 (host-side, never traced)
                       f"~{plan.memory_floor_bytes / (1 << 20):.1f} MiB/device "
                       f"exceeds FLAGS_hbm_budget_mb={budget_mb:g}")
        counter_inc("planner.pruned")
        return plan
    batch_sharding = NamedSharding(mesh, P(("dp", "sdp")))
    # the trace below reads it: a ZeRO >= 2 candidate is scored on the
    # program build_step will dispatch (gather of the cast, scatter of grads)
    step._param_placement = param_placement(shardings, mp_specs)
    jitted = _sharded_jit(step, mesh, shardings, batch_sharding)
    compiled, info = aot_compile(jitted, (abstract_state, abstract_batch),
                                 cache_scope="train_step")
    plan.compile_seconds = info.get("compile_seconds")
    if compiled is None:
        plan.feasible = False  # noqa: PTA104 (host-side, never traced)
        plan.pruned = f"lower/compile failed: {info.get('aot_error', '?')}"  # noqa: PTA104 (host-side, never traced)
        counter_inc("planner.pruned")
        return plan
    opts = _spmd.ShardCheckOptions(
        hbm_budget_mb=budget_mb,
        allgather_warn_bytes=getattr(options, "allgather_warn_bytes", 1 << 20)
        if options is not None else 1 << 20)
    report = _spmd.analyze_compiled(
        compiled, label=plan.label, kind="plan", options=opts,
        params=abstract_state.get("params"),
        param_shardings=shardings.get("params"))
    plan.comm_bytes = report.moved_bytes
    plan.collectives = report.counts()
    plan.fingerprint = report.fingerprint
    plan.codes = sorted({d.code for d in report.diagnostics})
    plan.flops = info.get("flops")
    plan.bytes_accessed = info.get("bytes_accessed")
    plan.peak_bytes = report.peak_bytes
    if plan.peak_bytes is None:
        try:  # text-only floor when the backend reports no memory stats
            from ..analysis import hlo as _hlo

            plan.peak_bytes = _hlo.entry_memory_lower_bound(compiled.as_text())  # noqa: PTA104 (host-side, never traced)
        except Exception:
            plan.peak_bytes = None  # noqa: PTA104 (host-side, never traced)
    plan.feasible = not report.errors
    if report.errors:
        plan.pruned = "; ".join(f"{d.code}" for d in report.errors)  # noqa: PTA104 (host-side, never traced)
        counter_inc("planner.pruned")
    pred = predict_step_time(plan.flops, plan.bytes_accessed,
                             plan.comm_bytes, hw=hw)
    plan.score = pred["total_s"]
    plan.predicted_step_ms = pred["total_s"] * 1e3
    plan.compute_ms = max(pred["compute_s"], pred["memory_s"]) * 1e3
    plan.comm_ms = pred["comm_s"] * 1e3
    del compiled  # the executable (if cached) lives in the AOT store
    return plan


# ------------------------------------------------------------------- cache
def _model_fingerprint(model) -> str:
    rows = [type(model).__name__]
    for n, p in sorted(model.named_parameters()):  # noqa: PTA102 (host-side, never traced)
        dt = getattr(p, "dtype", None) or getattr(p._value, "dtype", "?")
        spec = getattr(p, "dist_spec", None)
        rows.append(f"{n}:{tuple(p.shape)}:{dt}:{spec}")  # noqa: PTA104 (host-side, never traced)
    return hashlib.sha256("|".join(rows).encode()).hexdigest()[:16]


def _cache_path(key: str):
    from ..framework.flags import compile_cache_dir

    d = compile_cache_dir()
    if not d:
        return None
    return os.path.join(d, "planner", f"{key}.json")


def _cache_key(model, n_devices, abstract_batch, template_names, stages,
               axes, meshes, budget_mb) -> str:
    import jax

    shapes = [f"{l.dtype}{list(l.shape)}"
              for l in _tree_leaves_safe(abstract_batch)]
    payload = repr(("plan-v1", _model_fingerprint(model), int(n_devices),
                    shapes, sorted(template_names), tuple(stages),
                    tuple(axes), meshes, float(budget_mb or 0),
                    jax.__version__, jax.default_backend()))
    return hashlib.sha256(payload.encode()).hexdigest()[:32]


def _tree_leaves_safe(tree):
    import jax

    return jax.tree_util.tree_leaves(tree)


# ------------------------------------------------------------------ search
def search(model, n_devices: int, *, inputs_spec, labels_spec=None,
           loss=None, optimizer=None, templates=None, meshes=None,
           stages: Sequence[int] = (2,), axes: Sequence[str] = DEFAULT_AXES,
           options=None, cache: bool = True, max_candidates: int = 32,
           devices=None, hw=None, seed: int = 0) -> List[Plan]:
    """Rank parallel plans for ``model`` on ``n_devices`` from shapes alone.

    ``inputs_spec``/``labels_spec`` are ``static.InputSpec``s (or anything
    with shape/dtype); dynamic dims are probed at the device count. ``loss``
    is required (the scored program is the full fwd+bwd+update step);
    ``optimizer`` defaults to AdamW. ``templates`` maps name ->
    {param: PartitionSpec} (or a callable of the model); default is the
    model's own annotations plus fully-replicated. ``meshes`` overrides the
    axis-factorization enumeration with an explicit candidate list.
    ``stages`` are the ZeRO stages tried when a candidate mesh has sdp > 1.

    Nothing is dispatched: every candidate is lowered+compiled on
    ``ShapeDtypeStruct``s and scored from the SpmdReport + cost analysis.
    Returns plans ranked best-first (feasible before infeasible, then
    predicted step time). With ``FLAGS_compile_cache_dir`` set the ranked
    list round-trips through the on-disk plan cache — a restart with the
    same (model, device count, shapes) pays zero search.
    """
    import jax

    from ..observability import runlog as _runlog
    from ..observability import span as _span
    from ..observability.metrics import counter_inc

    t0 = time.perf_counter()
    counter_inc("planner.searches")
    if inputs_spec is None:
        raise PlannerError("search needs inputs_spec (shapes are the input)")
    if loss is None:
        raise PlannerError("search needs loss (it scores the full training "
                           "step, not just the forward)")
    devices = list(devices if devices is not None else jax.devices())
    if int(n_devices) > len(devices):
        raise PlannerError(
            f"search over {n_devices} devices but only {len(devices)} are "
            "visible (CPU dryrun: XLA_FLAGS=--xla_force_host_platform_"
            "device_count=N)")
    n_devices = int(n_devices)

    # resolve the spec-template set
    if templates is None:
        templates = default_templates(model)
    resolved: Dict[str, Dict[str, Any]] = {}
    for name, t in templates.items():  # noqa: PTA102 (host-side, never traced)
        specs = t(model) if callable(t) else dict(t or {})
        resolved[name] = {k: _spec_entries(v) for k, v in specs.items()}  # noqa: PTA104 (host-side, never traced)

    mesh_list = list(meshes) if meshes is not None else mesh_shapes(n_devices, axes)
    candidates: List[Plan] = []
    for m in mesh_list:
        degrees = {a: int(d) for a, d in m.items() if int(d) > 1}
        need = int(np.prod(list(degrees.values()))) if degrees else 1
        if need != n_devices:
            raise PlannerError(
                f"mesh candidate {m} covers {need} devices, expected "
                f"{n_devices}")
        cand_stages = tuple(stages) if degrees.get("sdp", 1) > 1 else (0,)
        for tname in resolved:
            for stage in cand_stages:
                candidates.append(Plan(mesh=degrees, template=tname,  # noqa: PTA104 (host-side, never traced)
                                       stage=int(stage), n_devices=n_devices,
                                       param_specs=resolved[tname]))
    dropped = max(0, len(candidates) - int(max_candidates))
    candidates = candidates[:int(max_candidates)]
    counter_inc("planner.candidates", len(candidates))

    budget_mb = _budget_mb(options)

    # plan cache: a restart with the same key pays zero search
    key = _cache_key(model, n_devices,
                     abstract_inputs(inputs_spec, n_devices),
                     sorted(resolved), stages, axes,
                     sorted(tuple(sorted(m.items())) for m in mesh_list),
                     budget_mb)
    path = _cache_path(key) if cache else None
    if path is not None and os.path.exists(path):
        try:
            with open(path) as f:
                payload = json.load(f)
            plans = [Plan.from_summary(d) for d in payload["plans"]]
            counter_inc("planner.cache_hits")
            _runlog.emit("plan", devices=n_devices, candidates=len(plans),
                         cached=True, search_ms=round(
                             (time.perf_counter() - t0) * 1e3, 3),
                         chosen=plans[0].summary() if plans else None)
            return plans
        except Exception:
            pass  # unreadable cache file: fall through to a live search

    # one TrainStep build gives the state tree; everything after is abstract
    from ..jit import TrainStep

    if optimizer is None:
        from .. import optimizer as _optim

        optimizer = _optim.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    step = TrainStep(model, optimizer, loss, seed=seed)
    abstract_state = jax.eval_shape(lambda s: s, step.state)
    abstract_batch = (abstract_inputs(inputs_spec, n_devices),
                      abstract_inputs(labels_spec if labels_spec is not None
                                      else inputs_spec, n_devices))
    if hw is None:
        from ..cost_model import hardware_spec

        hw = hardware_spec()

    with _span("planner.search"):
        for plan in candidates:
            try:
                _evaluate(plan, step, abstract_state, abstract_batch,
                          devices, budget_mb, hw, options)
            except Exception as exc:  # a broken candidate must not kill search
                plan.feasible = False  # noqa: PTA104 (host-side, never traced)
                plan.pruned = f"evaluation failed: {type(exc).__name__}: {exc}"  # noqa: PTA104 (host-side, never traced)
                counter_inc("planner.pruned")

    plans = sorted(candidates,
                   key=lambda p: (not p.feasible, p.score, p.comm_bytes,
                                  p.memory_floor_bytes))
    if path is not None:
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            tmp = f"{path}.{os.getpid()}.tmp"
            with open(tmp, "w") as f:
                json.dump({"format": 1, "devices": n_devices,
                           "plans": [p.summary() for p in plans]}, f)
            os.replace(tmp, path)
            counter_inc("planner.cache_stores")
        except OSError:
            pass
    search_ms = round((time.perf_counter() - t0) * 1e3, 3)
    _runlog.emit("plan", devices=n_devices, candidates=len(candidates),
                 dropped=dropped, cached=False, search_ms=search_ms,
                 pruned=sum(1 for p in plans if not p.feasible),
                 chosen=plans[0].summary() if plans else None)
    return plans


def _budget_mb(options) -> float:
    if options is not None and getattr(options, "hbm_budget_mb", None) is not None:
        return float(options.hbm_budget_mb)
    from ..framework.flags import flag

    return float(flag("FLAGS_hbm_budget_mb"))


# ----------------------------------------------------------------- builders
def build_step(model, optimizer, loss_fn, plan: Plan, devices=None,
               seed: int = 0, **step_kwargs):
    """A sharded ``jit.TrainStep`` executing ``plan`` — the fleet
    ``distributed_step`` assembly driven by a searched plan instead of
    hand-picked strategy knobs. The dispatch jit is built by the same
    helper the planner scored with, so a plan evaluated during an elastic
    HOLD window resumes on an already-cached executable."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..jit import TrainStep, scan_steps
    from .sharding import param_placement, place_state, state_shardings

    mesh = plan.build_mesh(devices)
    step = TrainStep(model, optimizer, loss_fn, seed=seed, **step_kwargs)
    mp_specs = plan.resolved_specs()
    shardings = state_shardings(step.state, mesh, stage=plan.stage,
                                mp_specs=mp_specs)
    step._param_placement = param_placement(shardings, mp_specs)
    batch_sharding = NamedSharding(mesh, P(("dp", "sdp")))
    step.mesh = mesh
    step.state = place_state(step.state, shardings)
    step._jit = _sharded_jit(step, mesh, shardings, batch_sharding)
    step._jit_multi = scan_steps(_scoped_step_fn(step, mesh), donate_argnums=0,
                                 in_shardings=(shardings, None),
                                 out_shardings=(shardings, None))
    step.state_shardings = shardings
    step._state_shardings = shardings
    step.plan = plan
    return step


def elastic_replan(model, optimizer_factory: Callable[[], Any], loss_fn, *,
                   inputs_spec, labels_spec=None,
                   devices_for: Callable[[List[int]], int],
                   on_step: Optional[Callable[[Any], None]] = None,
                   seed: int = 0, **search_kw):
    """An ``on_rescale`` hook for :func:`~.resilience.run_resilient`:
    when membership settles on a different node set, re-plan for the new
    device count (plan-cache hit when this topology was seen before),
    build the sharded TrainStep for the winning plan — compiling it *now*,
    during the HOLD window, into the AOT executable cache — and hand the
    supervisor the new state template + shardings so the checkpoint
    restores resharded onto the new mesh.

    ``devices_for(members)`` maps the settled member list to a device
    count; ``on_step(train_step)`` receives each freshly built TrainStep
    (rebind your training closure there). The returned hook gives
    ``run_resilient`` ``(savable_target, savable_shardings)``.
    """
    from ..stability import state_to_savable

    def on_rescale(members, _state):
        n = int(devices_for(members))
        plans = search(model, n, inputs_spec=inputs_spec,
                       labels_spec=labels_spec, loss=loss_fn,
                       optimizer=optimizer_factory(), seed=seed, **search_kw)
        best = next((p for p in plans if p.feasible), None)
        if best is None:
            raise PlannerError(
                f"no feasible plan for {n} device(s): "
                + "; ".join(f"{p.label}: {p.pruned}" for p in plans))
        step = build_step(model, optimizer_factory(), loss_fn, best, seed=seed)
        if on_step is not None:
            on_step(step)
        target = state_to_savable(step.state)
        shardings = dict(step._state_shardings)
        # the savable rng is raw key data; its replicated spec still applies
        return target, shardings

    return on_rescale


# --------------------------------------------------------------------- CLI
def format_plan_table(plans: List[Plan]) -> str:
    header = ["plan", "ok", "pred ms", "comm MB/step", "peak MiB",
              "state MiB", "codes"]
    body = []
    for p in plans:
        body.append([  # noqa: PTA104 (host-side, never traced)
            p.label,
            "yes" if p.feasible else f"NO ({p.pruned[:40]})",
            "-" if p.predicted_step_ms is None else f"{p.predicted_step_ms:.3f}",
            f"{p.comm_bytes / 1e6:.3f}",
            "-" if p.peak_bytes is None else f"{p.peak_bytes / (1 << 20):.1f}",
            f"{p.memory_floor_bytes / (1 << 20):.1f}",
            ",".join(p.codes) or "-",
        ])
    widths = [max(len(r[i]) for r in [header] + body) for i in range(len(header))]
    fmt = "  ".join(f"{{:<{w}}}" for w in widths)
    lines = [fmt.format(*header), fmt.format(*["-" * w for w in widths])]
    lines += [fmt.format(*r) for r in body]
    return "\n".join(lines)


def main(argv=None) -> int:
    """``python -m paddle_tpu.distributed.planner --devices N [--json]`` —
    rank parallel plans for a GPT model (tiny by default) on N devices."""
    import argparse

    p = argparse.ArgumentParser(prog="python -m paddle_tpu.distributed.planner")
    p.add_argument("--devices", type=int, default=0,
                   help="device count to plan for (default: all visible)")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--vocab", type=int, default=512)
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--axes", default=",".join(DEFAULT_AXES),
                   help="comma list of mesh axes to factor over")
    p.add_argument("--stage", type=int, default=2,
                   help="ZeRO stage tried when sdp > 1")
    p.add_argument("--hbm-budget", type=float, default=None,
                   help="per-device MiB budget (PTA204 pruning)")
    p.add_argument("--no-cache", action="store_true",
                   help="skip the FLAGS_compile_cache_dir plan cache")
    p.add_argument("--json", action="store_true")
    args = p.parse_args(argv)

    import sys

    import jax

    import paddle_tpu as paddle
    from ..models.gpt import (
        GPTConfig,
        GPTForPretraining,
        GPTPretrainingCriterion,
    )

    n = args.devices or len(jax.devices())
    if n > len(jax.devices()):
        print(f"planner: {n} devices requested, {len(jax.devices())} visible "  # noqa: PTA105 (host-side, never traced)
              "(CPU dryrun: XLA_FLAGS=--xla_force_host_platform_device_count"
              f"={n})", file=sys.stderr)
        return 2
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=args.vocab, hidden_size=args.hidden,
                    num_layers=args.layers, num_heads=args.heads,
                    max_seq_len=max(args.seq, 2 * args.seq))
    model = GPTForPretraining(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    from ..analysis.spmd import ShardCheckOptions

    options = (ShardCheckOptions(hbm_budget_mb=args.hbm_budget)
               if args.hbm_budget is not None else None)
    spec = jax.ShapeDtypeStruct((args.batch, args.seq), np.int32)
    plans = search(model, n, inputs_spec=spec, loss=GPTPretrainingCriterion(),
                   optimizer=opt, axes=tuple(args.axes.split(",")),
                   stages=(args.stage,), options=options,
                   cache=not args.no_cache)
    if args.json:
        print(json.dumps([pl.summary() for pl in plans], indent=2))  # noqa: PTA105 (host-side, never traced)
    else:
        print(f"ranked plans for {n} device(s) "  # noqa: PTA105 (host-side, never traced)
              f"(backend: {jax.default_backend()}):")
        print(format_plan_table(plans))  # noqa: PTA105 (host-side, never traced)
    return 0 if any(pl.feasible for pl in plans) else 1


if __name__ == "__main__":
    raise SystemExit(main())
