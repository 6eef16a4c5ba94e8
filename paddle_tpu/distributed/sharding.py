"""ZeRO sharding policies (parity: the group_sharded stack —
GroupShardedOptimizerStage2 fleet/meta_parallel/sharding/
group_sharded_optimizer_stage2.py:48, GroupShardedStage3
group_sharded_stage3.py:60, public API
python/paddle/distributed/sharding/group_sharded.py).

TPU-first: a "stage" is a PartitionSpec policy over the 'sdp' mesh axis:
  stage 1 — optimizer state sharded; params/grads replicated
  stage 2 — + the f32 master sharded with its moments, in and out of the
             step: the rank that updates a shard keeps it. The step casts
             the shard, all-gathers the cast (``jit.TrainStep`` reads
             ``param_placement``) and reduce-scatters each gradient to its
             owner, both in the compute type; no collective follows the
             update
  stage 3 — the same placement and the same program (a layer-at-a-time
             re-gather, which is what would set it apart, is not built)
The reference's rank-sliced grad storage, param hooks and manual
broadcast/allgather (group_sharded_stage3.py:399-425) all become these specs.

Leaving the pattern to XLA did not give this. With only the moments' specs
sharded (before PR 35) the compiled v5e 2x2 step all-gathered the updated
parameters in f32 after the update (26 ms of a 447-ms step, plus 16 ms of
copies of the gathered stacks), re-read them for the cast, and all-reduced
every bf16 weight gradient over 'sdp' before slicing it (31 ms): 35.7 % of
the step was collectives with nothing beside them (PERF.md sections 5, 6).
"""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _extend_spec(spec: Optional[P], shape, axis_size: int, axis_name="sdp", min_size=16384, mesh=None) -> P:
    """Add ``axis_name`` (ZeRO) sharding to a param/opt spec, on the
    dimension ``_split_dim`` picks. Small params stay replicated."""
    base = list(spec) if spec is not None else [None] * len(shape)
    while len(base) < len(shape):
        base.append(None)
    if axis_size <= 1 or int(np.prod(shape)) < min_size:
        return _canonical(P(*base))
    dim = _split_dim(base, shape, axis_size, axis_name, mesh)
    if dim is not None:
        base[dim] = axis_name if base[dim] is None else _axes_of(base[dim]) + (axis_name,)
    return _canonical(P(*base))


def _axes_of(entry):
    if entry is None:
        return ()
    return tuple(entry) if isinstance(entry, (tuple, list)) else (entry,)


def _split_dim(base, shape, axis_size, axis_name, mesh):
    """The dimension ``_extend_spec`` splits: the most major one that no
    mesh axis divides yet, the layer-stacking axis (the one 'pp' names)
    after the layer's own; if none is free, the first whose shard still
    divides. A stacking axis under a real pipeline ('pp' > 1) is never cut.

    Chosen on the chip (v5e 2x2, sdp 2 x mp 2, 1.3B, one call; PERF.md
    section 6, PR 35), for what the compiler makes of each gradient:
    * a dimension of the layer's own that 'sdp' has to itself (qkv/ffn1:
      the rows; ffn2/out and the table: the columns): every weight gradient
      becomes the chip's fused reduce-scatter, 17.5 ms a step: 404 ms.
    * composed onto the mp dimension, ``('mp', 'sdp')`` (the rule before):
      XLA all-reduces the whole gradient over 'sdp' and slices, twice the
      bytes, 31.1 ms: 414 ms. (The old reason for it — a fresh dimension
      pulling activations into hidden-sharded layouts, "involuntary full
      rematerialization" — belonged to parameters that carried 'sdp' inside
      the computation; the step now computes with a gathered copy pinned to
      the mp-only spec, and no such reshard appears.)
    * the leading stacking axis, which would make the parameter gather a
      plain concatenation: a layer's gradient then has one owner, not two
      halves, and the compiler answers with all-reduces of the parent's
      bytes plus collective-permutes: 457 ms, slower than not sharding the
      master at all.
    The bf16 gather at the top of the step needs no relayout copy under any
    of the three: the 16 ms of copies before PR 35 followed the f32 gather
    of the program's outputs, on these same mp dimensions."""
    sizes = dict(mesh.shape) if mesh is not None else {}
    held = [int(np.prod([sizes.get(a, 1) for a in _axes_of(entry)])) for entry in base]
    stacking = ["pp" in _axes_of(entry) for entry in base]
    dims = sorted(range(len(shape)), key=lambda i: stacking[i])
    free = [i for i in dims if held[i] == 1 and shape[i] % axis_size == 0]
    shared = [i for i in dims if not stacking[i] and axis_name not in _axes_of(base[i])
              and shape[i] % (held[i] * axis_size) == 0]
    return next(iter(free + shared), None)


def build_state_specs(params: Dict[str, np.ndarray], mesh: Mesh, stage: int = 1, mp_specs: Optional[Dict[str, P]] = None, opt_state_keys=("m", "v", "u", "velocity", "moment", "mean_square", "mean_grad", "avg_sq_grad", "avg_sq_update")):
    """Return (param_specs, opt_specs_fn) for a TrainStep state tree."""
    sdp = mesh.shape.get("sdp", 1)
    mp_specs = mp_specs or {}
    param_specs = {}
    opt_specs = {}
    for name, arr in params.items():
        base = mp_specs.get(name)
        shape = tuple(arr.shape)
        owned = _extend_spec(base, shape, sdp, mesh=mesh)
        compute = P(*base) if base is not None else P()
        # stage >= 2: the master lives with its moments, on the rank that
        # updates it; the step gathers the compute copy (param_placement)
        param_specs[name] = owned if stage >= 2 else compute
        opt_specs[name] = owned if stage >= 1 else compute
    return param_specs, opt_specs


def param_placement(shardings, mp_specs=None):
    """``{name: (spec in the state, spec in the computation)}`` of the
    parameters a ``state_shardings`` tree holds under another spec than the
    model computes with — the ZeRO >= 2 masters, split over 'sdp'. This is
    what ``TrainStep`` reads (``_param_placement``) to cast a shard, gather
    the cast, and hand each gradient back to its owner; empty at stage 0/1."""
    mp_specs = mp_specs or {}
    compute = {name: _canonical(P(*(mp_specs.get(name) or ()))) for name in shardings["params"]}
    return {name: (sharding.spec, compute[name]) for name, sharding in shardings["params"].items()
            if _canonical(sharding.spec) != compute[name]}


def _canonical(spec: P) -> P:
    entries = list(spec)
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def state_shardings(state, mesh: Mesh, stage: int = 1, mp_specs=None, offload=False):
    """Shardings pytree matching a TrainStep state dict.

    ``offload=True`` is ZeRO-offload parity (reference
    group_sharded_optimizer_stage2.py ``offload=True`` keeps optimizer state
    in host memory): optimizer-state shardings get
    ``memory_kind='pinned_host'`` — XLA stages the m/v tensors in host RAM
    and streams them through the fused update. Falls back to device memory
    (with a warning) on backends without host memory spaces."""
    params = state["params"]
    param_specs, opt_specs = build_state_specs(params, mesh, stage, mp_specs)

    def ns(spec):
        return NamedSharding(mesh, spec)

    def ns_opt(spec):
        if offload:
            try:
                return NamedSharding(mesh, spec, memory_kind="pinned_host")
            except (ValueError, TypeError):
                import warnings

                warnings.warn("sharding offload=True: backend has no pinned_host "
                              "memory space; optimizer state stays in device memory")
                return NamedSharding(mesh, spec)
        return NamedSharding(mesh, spec)

    # opt state: dict of moment-name -> {param-name: array}
    opt_shard = {}
    for moment_name, tree in state["opt"].items():
        opt_shard[moment_name] = {k: ns_opt(opt_specs.get(k, P())) for k in tree}
    return {
        "params": {k: ns(s) for k, s in param_specs.items()},
        "buffers": {k: ns(P()) for k in state["buffers"]},
        "opt": opt_shard,
        "step": ns(P()),
        "rng": ns(P()),
    }


def place_state(state, shardings):
    """``jax.device_put(state, shardings)`` without buffer aliasing.

    A plain ``device_put`` may *reuse* the source buffer as one shard of
    the placed array (replicated leaves on the source device). A TrainStep
    then donates that buffer on its first dispatch — deleting the model's
    own parameter array out from under any later rebuild
    (``planner.build_step`` during an elastic re-plan reads
    ``model.param_arrays()`` again). Round-tripping through host bytes
    guarantees the placed state owns fresh buffers. Typed PRNG keys (no
    numpy spelling) go through a plain ``device_put`` — they are created
    fresh per TrainStep, so nothing else holds their buffer.
    """
    import jax

    def fresh(leaf, sh):
        try:
            host = np.asarray(jax.device_get(leaf))
        except TypeError:  # extended dtype: typed PRNG key
            return jax.device_put(leaf, sh)
        return jax.device_put(host, sh)

    return jax.tree_util.tree_map(fresh, state, shardings)


def group_sharded_parallel(model, optimizer, level="os_g", scaler=None, group=None, offload=False, sync_buffers=False, buffer_max_size=2**23, segment_size=2**20, sync_comm=False):
    """API parity (python/paddle/distributed/sharding/group_sharded.py).
    level: 'os' (stage1) | 'os_g' (stage2) | 'p_g_os' (stage3). Returns the
    pair unchanged plus records the stage for fleet.distributed_step."""
    stage = {"os": 1, "os_g": 2, "p_g_os": 3}[level]
    model._sharding_stage = stage
    optimizer._sharding_stage = stage
    model._sharding_offload = optimizer._sharding_offload = bool(offload)
    return model, optimizer, scaler
