"""paddle_tpu.jit — the static-graph execution path.

Parity: the reference's whole static stack — ProgramDesc + InterpreterCore
(paddle/fluid/framework/new_executor/interpretercore.cc:116),
``@paddle.jit.to_static`` (dygraph_to_static/program_translator.py:239) and
``paddle.jit.save`` — collapses to jax.jit tracing of the functional layer
call. The "program" is the jaxpr; the "executor" is XLA; data-transfer
insertion, stream analysis, GC and op scheduling are XLA's problem.
"""
from __future__ import annotations

import contextlib
import functools
import os
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis import sanitizer as _sanitizer
from ..framework import random as _random
from ..framework.autograd import no_grad
from ..framework.core import Tensor, _wrap_value, unwrap
from ..nn.functional_api import _wrap_tree, unwrap_tree


def _pure_model_call(model, arrays, args, kwargs, training, rng):
    """Run model under bound arrays; return (output, updated_buffer_arrays).

    Buffer side effects (BatchNorm running stats) are captured as explicit
    outputs — the jit-path equivalent of the reference's in-place buffer
    mutation (paddle/phi/kernels/gpu/batch_norm_kernel.cu writes mean/var out).
    """
    modes = [(l, l.training) for l in model.sublayers(include_self=True)]
    for l, _ in modes:
        l.training = training
    rng_ctx = _random.rng_scope(rng) if rng is not None else contextlib.nullcontext()
    buf_names = [n for n, _ in model.named_buffers()]
    try:
        with no_grad(), model.bind(arrays), rng_ctx:
            out = model(*_wrap_tree(list(args)), **kwargs)
            new_buffers = {}
            for n, b in model.named_buffers():
                new_buffers[n] = b._value
    finally:
        for l, was in modes:
            l.training = was
    return unwrap_tree(out), new_buffers


def scan_steps(step, *, length=None, with_consts=False, donate_argnums=0, **jit_kwargs):
    """ONE-dispatch multi-step runner: jit(lax.scan(step)) with donated carry.

    The PR-3 ``TrainStep.run_steps`` idiom as a shared helper: ``step`` is a
    scan body ``(carry, x) -> (carry, y)`` and the returned jitted function
    ``run(carry, xs=None)`` chains every iteration inside ONE compiled
    program — one host dispatch (and one host sync, when the caller reads)
    per K steps instead of per step. With ``with_consts=True`` the body is
    ``(consts, carry, x) -> (carry, y)`` and ``run(consts, carry, xs=None)``
    threads ``consts`` (e.g. model params) through untouched — keep them out
    of the carry so donation never consumes them. ``length`` pins the trip
    count when ``xs`` is None (the serving engine's fused decode);
    ``jit_kwargs`` pass through to ``jax.jit`` (shardings etc.).
    """
    if with_consts:
        def run(consts, carry, xs=None):
            return jax.lax.scan(functools.partial(step, consts), carry, xs, length=length)
    else:
        def run(carry, xs=None):
            return jax.lax.scan(step, carry, xs, length=length)
    return jax.jit(run, donate_argnums=donate_argnums, **jit_kwargs)


class TrainStep:
    """One compiled training step: forward + backward + optimizer update.

    ``loss_fn(output, *labels)`` runs on Tensors (any paddle_tpu loss).
    Donates the state buffers so param memory stays flat (reference analog:
    inplace/vars GC in interpretercore; here it's XLA buffer donation).

    ``guard=True`` (or ``FLAGS_train_guard``) fuses the training-health
    guard into the program: an all-finite reduction over loss+grads whose
    bad-step flag masks the param/opt/buffer/step update with ``jnp.where``
    — state stays bitwise at its pre-step value on a NaN/Inf gradient, with
    no extra dispatch and no host sync. Metrics gain a device-resident
    ``health`` leaf ``{bad_step, grad_norm, skipped}`` (stacked ``[K]``
    under ``run_steps``) for :class:`paddle_tpu.stability.HealthMonitor`.
    A skipped step does NOT advance ``state["step"]`` (rng fold-in and LR
    schedule stay aligned with a run that never saw the bad batch); the
    cumulative skip count lives in ``state["skipped"]``.
    """

    def __init__(self, model, optimizer, loss_fn, mesh=None, state_shardings=None, batch_shardings=None, remat=False, seed=0, amp_level=None, amp_dtype="bfloat16", accumulate_steps=1, return_outputs=False, guard=None):
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.mesh = mesh
        self.accumulate_steps = int(accumulate_steps)
        self.return_outputs = return_outputs  # include model outputs in metrics (hapi train-metric path)
        from ..framework.flags import flag as _flag

        # Training-health guard (stability subsystem): fuse an all-finite
        # reduction over loss+grads into the step program and skip the
        # param/opt/step update in-graph when it trips — state stays bitwise
        # at its pre-step value (correct under donation: the select happens
        # inside the compiled program). Metrics gain a device-resident
        # "health" leaf; no extra dispatch, no per-step host sync.
        self.guard = bool(_flag("FLAGS_train_guard")) if guard is None else bool(guard)
        # Deterministic chaos: inject non-finite gradients at a named step
        # (read HERE, at construction — the injection compiles into the
        # program, gated by an armed budget carried in the state so it fires
        # exactly once per process even across scans and rollbacks).
        from ..testing import chaos as _chaos

        self._nan_chaos = _chaos.nan_grads_due()
        # AMP (reference amp.decorate semantics, bf16-first for TPU).
        # O2: master params stay f32 in state; compute casts params+inputs to
        #     amp_dtype so matmuls hit the MXU at bf16; loss input back to f32.
        # O1: white/black-list autocast via the amp module's primitive hook.
        if amp_level not in (None, "O0", "O1", "O2"):
            raise ValueError(f"amp_level must be None/'O0'/'O1'/'O2', got {amp_level!r}")
        self.amp_level = None if amp_level == "O0" else amp_level
        self.amp_dtype = jnp.dtype(amp_dtype) if self.amp_level else None
        if self.amp_dtype == jnp.float16:
            raise ValueError(
                "float16 in the fused TrainStep has no loss-scaling hook and "
                "gradients underflow silently; use bfloat16 (TPU-native) or "
                "the eager path with amp.GradScaler")
        from ..framework.flags import flag

        if not remat and flag("FLAGS_remat_policy") != "none":
            remat = True
        params = model.param_arrays()
        buffers = model.buffer_arrays()
        self.state = {
            "params": params,
            "buffers": buffers,
            "opt": optimizer.core.init(params),
            "step": jnp.zeros((), jnp.int32),
            "rng": jax.random.key(seed),
        }
        if self.guard:
            # dispatched-but-skipped update count; step + skipped together
            # form the monotonic dispatch counter (step alone freezes on a
            # skipped update so rng fold-in stays aligned with a clean run)
            self.state["skipped"] = jnp.zeros((), jnp.int32)
        if self._nan_chaos is not None:
            self.state["chaos_nan_armed"] = jnp.asarray(self._nan_chaos[1], jnp.int32)
        self._remat = remat
        self._batch_shardings = batch_shardings
        self._state_shardings = state_shardings
        # {name: (spec in the state, spec in the computation)} for the
        # parameters a sharded build keeps with the rank that updates them
        # (distributed.sharding.param_placement: ZeRO >= 2); the step then
        # gathers their compute copy and scatters their gradients
        self._param_placement = None
        if mesh is not None and isinstance(state_shardings, dict):
            from jax.sharding import NamedSharding, PartitionSpec as P

            extras = [e for e in ("skipped", "chaos_nan_armed")
                      if e in self.state and e not in state_shardings]
            if extras:  # guard/chaos scalar leaves ride along replicated
                state_shardings = dict(state_shardings)
                for extra in extras:
                    state_shardings[extra] = NamedSharding(mesh, P())
                self._state_shardings = state_shardings
        self._build(remat)
        if mesh is not None and state_shardings is not None:
            self.state = jax.device_put(self.state, state_shardings)
        self._make_jits()
        # observability: per-batch-signature AOT executables (the retained
        # XLA Compiled handles behind explain()), their cost rows, and the
        # host-side step counter the run log indexes by
        self._compiled: Dict[tuple, Any] = {}
        self._specializations: list = []
        self._host_step = 0

    def _make_jits(self):
        if self.mesh is not None and self._state_shardings is not None:
            self._jit = jax.jit(self._step, donate_argnums=0, in_shardings=(self._state_shardings, self._batch_shardings), out_shardings=(self._state_shardings, None))
            self._jit_multi = scan_steps(self._step, donate_argnums=0, in_shardings=(self._state_shardings, None), out_shardings=(self._state_shardings, None))
        else:
            self._jit = jax.jit(self._step, donate_argnums=0)
            self._jit_multi = scan_steps(self._step, donate_argnums=0)

    def rebuild(self):
        """Re-trace and re-jit the step programs against the CURRENT
        optimizer/model hyperparameters (the compiled programs bake closed-
        over host scalars — e.g. a plain-float learning rate — so a
        divergence rollback's LR backoff only takes effect through a
        rebuild). State is preserved; compiled-specialization caches are
        dropped (next dispatch recompiles)."""
        self._build(self._remat)
        self._make_jits()
        self._compiled = {}

    def _build(self, remat):
        model, optimizer, loss_fn = self.model, self.optimizer, self.loss_fn
        amp_dt, amp_level = self.amp_dtype, self.amp_level
        o2 = amp_level == "O2"

        def _amp(a):
            return a.astype(amp_dt) if a.dtype == jnp.float32 else a

        def _to_amp(tree):
            return jax.tree_util.tree_map(_amp, tree)

        def _to_f32(x):
            return jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32) if a.dtype == amp_dt else a, x)

        def loss_of(params, buffers, inputs, labels, rng):
            def call(p):
                if o2:
                    # cast-through: grads of the cast are a cast back, so the
                    # optimizer sees f32 grads against f32 master params
                    with jax.named_scope("amp_cast"):
                        p = _to_amp(p)
                    inputs_c = _to_amp(inputs)
                else:
                    inputs_c = inputs
                if amp_level == "O1":  # white/black-list autocast (traced)
                    from .. import amp as _amp

                    ctx = _amp.auto_cast(True, level="O1", dtype=str(amp_dt))
                else:
                    ctx = contextlib.nullcontext()
                with ctx:
                    out, new_buffers = _pure_model_call(model, {**p, **buffers}, inputs_c, {}, True, rng)
                with no_grad():
                    loss_t = loss_fn(*_wrap_tree([out]), *_wrap_tree(list(labels)))
                loss_v = unwrap(loss_t)
                if amp_dt is not None and loss_v.dtype == amp_dt:
                    # loss scalar in f32 (amp black list); the loss fns do
                    # their reductions in f32 internally — logits stay bf16,
                    # which avoids materializing an f32 [..., vocab] tensor
                    loss_v = loss_v.astype(jnp.float32)
                return loss_v, (out, new_buffers)

            if remat:
                # rematerialize the forward in backward (paddle recompute /
                # fleet/utils/recompute.py:209 parity via jax.checkpoint)
                call = jax.checkpoint(call)
            return call(params)

        def _owned():
            """``{name: (owner's sharding, the computation's)}`` at trace time:
            the mesh is the fleet's as the trace finds it, like the model's
            own constraints; empty wherever the state holds every parameter
            as the model computes with it (one chip, ZeRO 0/1)."""
            if not self._param_placement:
                return {}
            from jax.sharding import NamedSharding

            from ..distributed.fleet import fleet

            mesh = fleet.mesh if fleet.mesh is not None else self.mesh
            return {name: (NamedSharding(mesh, owner), NamedSharding(mesh, compute))
                    for name, (owner, compute) in self._param_placement.items()}

        def _compute_copy(params, owned):
            """Cast the owner's shard, then gather the cast: what crosses the
            wire is ``amp_dtype``, and the f32 master never leaves its rank."""
            with jax.named_scope("amp_cast"):
                return {**params, **{
                    name: jax.lax.with_sharding_constraint(_amp(params[name]) if o2 else params[name], compute)
                    for name, (_, compute) in owned.items()}}

        def _to_owner(grads, params, owned):
            """Each gradient to the rank that updates its parameter, in the
            type it was computed in (partial sums over 'sdp' reduce-scatter);
            only the shard is widened for the update."""
            with jax.named_scope("amp_cast"):
                return {**grads, **{
                    name: jax.lax.with_sharding_constraint(grads[name], owner).astype(params[name].dtype)
                    for name, (owner, _) in owned.items()}}

        k = self.accumulate_steps
        guard = self.guard
        nan_chaos = self._nan_chaos

        def _step(state, batch):
            inputs, labels = batch
            rng = jax.random.fold_in(state["rng"], state["step"])
            owned = _owned()
            compute = _compute_copy(state["params"], owned)

            def grad_fn(buffers, inputs, labels, rng):
                res, grads = jax.value_and_grad(loss_of, has_aux=True)(compute, buffers, inputs, labels, rng)
                return res, _to_owner(grads, state["params"], owned)

            if k <= 1:
                (loss, (out, new_buffers)), grads = grad_fn(state["buffers"], inputs, labels, rng)
            else:
                # gradient merge (parity: fleet/meta_optimizers/
                # gradient_merge_optimizer.py): k microbatches through a
                # lax.scan, summed grads, one optimizer update. The strided
                # microbatch split (rows [i::k]) is shared with the pipeline.
                from ..distributed.pipeline import microbatch

                mb_in = jax.tree_util.tree_map(lambda a: microbatch(a, k), inputs)
                mb_lb = jax.tree_util.tree_map(lambda a: microbatch(a, k), labels)
                zeros = jax.tree_util.tree_map(jnp.zeros_like, state["params"])

                def acc(carry, xs):
                    gsum, lsum, buffers = carry
                    i, mi, ml = xs
                    (l, (o, nb)), g = grad_fn(buffers, mi, ml, jax.random.fold_in(rng, i))
                    gsum = jax.tree_util.tree_map(jnp.add, gsum, g)
                    # per-microbatch outputs stack up for hapi metrics (the
                    # scan ys); stacked as [k, mb, ...] and re-interleaved
                    # below so metric updates see the whole batch
                    ys = o if self.return_outputs else None
                    return (gsum, lsum + l, nb), ys

                (gsum, lsum, new_buffers), mb_out = jax.lax.scan(
                    acc, (zeros, jnp.zeros((), jnp.float32), state["buffers"]),
                    (jnp.arange(k), mb_in, mb_lb))
                grads = jax.tree_util.tree_map(lambda g: g / k, gsum)
                loss = lsum / k
                if self.return_outputs and mb_out is not None:
                    from ..distributed.pipeline import unmicrobatch as _unmb

                    out = jax.tree_util.tree_map(_unmb, mb_out)
            new_state = {"rng": state["rng"]}
            if nan_chaos is not None:
                # deterministic non-finite-gradient injection: fires while
                # the armed budget lasts, counted on the monotonic dispatch
                # counter (step+skipped), then drains — exactly once per
                # process under __call__, run_steps AND post-rollback replay
                at, _n = nan_chaos
                ctr = state["step"] + (state["skipped"] if guard else 0)
                fire = (state["chaos_nan_armed"] > 0) & (ctr >= at)
                grads = jax.tree_util.tree_map(
                    lambda g: jnp.where(fire, jnp.full_like(g, jnp.nan), g), grads)
                new_state["chaos_nan_armed"] = (
                    state["chaos_nan_armed"] - fire.astype(jnp.int32))
            if guard:
                # ONE fused reduction per grad leaf: the f32 sum-of-squares
                # feeds both the global grad norm and the finite flag (any
                # NaN/Inf grad makes the accumulator non-finite; an
                # accumulator that overflows f32 marks the step bad too —
                # such a step is garbage regardless). Cheaper than a second
                # isfinite pass over every gradient.
                sumsq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                            for g in jax.tree_util.tree_leaves(grads))
                gnorm = jnp.sqrt(sumsq)
                bad = ~(jnp.isfinite(sumsq) & jnp.isfinite(loss))
            with jax.named_scope("optimizer"):
                new_params, new_opt, lr = optimizer._traced_update(
                    grads, state["opt"], state["params"], state["step"])
            if guard:
                # bad-step skip: select the PRE-step value for every state
                # leaf inside the compiled program — bitwise no-op update,
                # correct under donate_argnums (nothing escaped the program)
                sel = lambda new, old: jnp.where(bad, old, new)  # noqa: E731
                with jax.named_scope("optimizer"):
                    new_params = jax.tree_util.tree_map(sel, new_params, state["params"])
                    new_opt = jax.tree_util.tree_map(sel, new_opt, state["opt"])
                new_buffers = jax.tree_util.tree_map(sel, new_buffers, state["buffers"])
                new_step = jnp.where(bad, state["step"], state["step"] + 1)
                new_state["skipped"] = state["skipped"] + bad.astype(jnp.int32)
            else:
                new_step = state["step"] + 1
            new_state.update(params=new_params, buffers=new_buffers,
                             opt=new_opt, step=new_step)
            metrics = {"loss": loss, "lr": lr}
            if guard:
                metrics["health"] = {"bad_step": bad, "grad_norm": gnorm,
                                     "skipped": new_state["skipped"]}
            if self.return_outputs:
                metrics["outputs"] = out
            return new_state, metrics

        # K steps in one XLA dispatch: _step is the scan body for the shared
        # scan_steps() runner built in _make_jits — the compiled program
        # chains K forward+backward+update iterations on-device, the
        # InterpreterCore's per-op scheduling amortized to one host
        # round-trip per K steps
        self._step = _step

    @staticmethod
    def _as_arrays(x):
        return tuple(unwrap(v) if isinstance(v, Tensor) else jnp.asarray(v)
                     for v in (x if isinstance(x, (list, tuple)) else [x]))

    def _dispatch(self, which, jitfn, batch):
        """Run one compiled dispatch, compiling through the AOT path on a
        new (kind, batch-shape) signature so the XLA Compiled handle — the
        only source of cost_analysis/memory_analysis — is retained for the
        run log and :meth:`explain`. Falls back to the plain jitted call
        whenever AOT is unavailable; dispatch never breaks for telemetry."""
        if _sanitizer.enabled():
            # pre-flight: a donated-and-deleted state leaf raises a
            # structured StaleStateError naming the leaf path, instead of
            # XLA's opaque deleted-buffer crash mid-dispatch; numpy batch
            # leaves become explicit device uploads so the dispatch itself
            # runs transfer-clean under the guard below
            _sanitizer.check_state("train_step", self.state, label=which)
            batch = _sanitizer.explicit_device(batch)
        sig = (which,) + tuple((tuple(l.shape), str(l.dtype))
                               for l in jax.tree_util.tree_leaves(batch))
        entry = self._compiled.get(sig)
        if entry is None:
            _sanitizer.note_compile("train_step", which, sig[1:])
            from ..observability import introspect as _introspect
            from ..observability import runlog as _runlog
            from ..observability import span as _span
            from ..profiler import counter_inc

            label = which + "/" + ",".join(
                f"{d}{list(s)}" for s, d in sig[1:5])  # first few batch leaves
            with _span("train_step.compile"):
                # FLAGS_compile_cache_dir: the compiled step round-trips
                # through the on-disk AOT store keyed on the lowered program
                # text — a warm restart (or an elastic resume onto a mesh
                # the planner already evaluated during HOLD) loads the
                # executable instead of recompiling
                compiled, info = _introspect.aot_compile(
                    jitfn, (self.state, batch), cache_scope="train_step")
            entry = compiled if compiled is not None else jitfn
            _introspect.note_program(f"train_step/{which}", compiled)
            if compiled is not None:
                from ..framework.flags import flag as _flag

                if _flag("FLAGS_shard_check"):
                    # SPMD pre-flight (PTA2xx) once per new specialization,
                    # BEFORE the executable is cached or dispatched: budget/
                    # divergence errors abort here, reshard findings warn
                    from ..analysis import spmd as _spmd

                    shardings = self._state_shardings
                    psh = shardings.get("params") if isinstance(shardings, dict) else None
                    report = _spmd.shard_check(
                        compiled, component="train_step", label=label,
                        kind=which, params=self.state.get("params"),
                        param_shardings=psh)
                    info["spmd"] = report.summary()
            self._compiled[sig] = entry
            if info.get("from_disk_cache"):
                counter_inc("train_step.aot_cache_hits")
            else:
                counter_inc("train_step.compiles")
                if info.get("aot_cache_stored"):
                    counter_inc("train_step.aot_cache_stores")
            info["label"] = label
            info["kind"] = which
            self._specializations.append(info)  # noqa: PTA305 (one entry per compiled signature — bounded by the recompile-churn sentinel under FLAGS_sanitize)
            _runlog.emit("compile", component="train_step", label=label,
                         seconds=info.get("compile_seconds"),
                         cached=bool(info.get("from_disk_cache")),
                         flops=info.get("flops"),
                         bytes_accessed=info.get("bytes_accessed"),
                         peak_bytes=info.get("peak_bytes"))
        try:
            try:
                with _sanitizer.transfer_scope(f"train_step.{which}"):
                    out = entry(self.state, batch)
            except (TypeError, ValueError):
                if entry is jitfn:
                    raise
                # AOT executables validate avals strictly; on drift fall back to
                # the jitted path permanently for this signature
                self._compiled[sig] = jitfn
                with _sanitizer.transfer_scope(f"train_step.{which}"):
                    out = jitfn(self.state, batch)
            if _sanitizer.enabled():
                import itertools

                # the dispatch donated the old state; eager model Tensors
                # still referencing those buffers get poisoned so any later
                # use raises StaleStateError instead of crashing in XLA
                _sanitizer.sweep_tensors(
                    "train_step",
                    itertools.chain(self.model.named_parameters(),
                                    self.model.named_buffers()),
                    label=which)
            return out
        except Exception as exc:
            # unhandled dispatch fault (aval drift already fell back above):
            # leave a flight-recorder dump, then let the fault propagate
            from ..observability import flightrec as _flightrec

            _flightrec.dump("dispatch_exception", exc,
                            component="train_step", which=which,
                            step=self._host_step)
            raise

    def __call__(self, inputs, labels):
        from ..observability import runlog as _runlog
        from ..observability import span as _span
        from ..profiler import counter_inc

        from ..observability import trace as _trace

        inputs = self._as_arrays(inputs)
        labels = self._as_arrays(labels)
        with _span("train_step.step") as sp:
            self.state, metrics = self._dispatch("step", self._jit, (inputs, labels))
        counter_inc("train_step.dispatches")
        counter_inc("train_step.steps")
        self._host_step += 1
        _runlog.emit("step", step=self._host_step, component="train_step",
                     k=1, seconds=sp.seconds, trace=_trace.current_trace())
        return {k: _wrap_tree(v) for k, v in metrics.items()}

    def run_steps(self, batches, k=None):
        """Run K training steps in ONE jitted dispatch (lax.scan over the
        step body, state donated).

        ``batches`` is either

        * a sequence of K per-step ``(inputs, labels)`` batches (``k`` may be
          omitted) — stacked here along a new leading axis, or
        * a pre-stacked ``(inputs, labels)`` pair whose leaves already carry
          the leading ``[k, ...]`` axis (what ``io.DataLoader(fuse_steps=k)``
          yields) — then ``k`` must be passed.

        Returns the metrics dict with every leaf stacked ``[k, ...]`` as
        device-resident arrays: nothing syncs the host until the caller
        reads a value (log boundaries), so the loop costs one Python
        dispatch per K steps instead of per step. Bitwise-identical to K
        individual ``__call__`` steps (same step fn, same per-step rng
        fold-in on the carried counter).
        """
        if k is None:
            batches = list(batches)
            k = len(batches)
            if k == 0:
                raise ValueError("run_steps needs at least one batch")
            norm = [(self._as_arrays(i), self._as_arrays(l)) for i, l in batches]
            stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *norm)
        else:
            k = int(k)
            inputs, labels = batches
            stacked = (self._as_arrays(inputs), self._as_arrays(labels))
            for leaf in jax.tree_util.tree_leaves(stacked):
                if leaf.shape[:1] != (k,):
                    raise ValueError(
                        f"pre-stacked batch leaf has leading dim {leaf.shape[:1]}, "
                        f"expected ({k},); pass per-step batches without k= to "
                        "have run_steps stack them")
        from ..observability import measured as _measured
        from ..observability import runlog as _runlog
        from ..observability import span as _span
        from ..observability import trace as _trace
        from ..profiler import counter_inc

        with _span("train_step.run_steps") as sp:
            self.state, metrics = self._dispatch("run_steps", self._jit_multi, stacked)
        counter_inc("train_step.dispatches")
        counter_inc("train_step.steps", k)
        self._host_step += k
        _runlog.emit("step", step=self._host_step, component="train_step",
                     k=k, seconds=sp.seconds, trace=_trace.current_trace())
        # measured step times, keyed by the auto-parallel plan fingerprint
        # (planner.build_step attaches .plan) — the evidence base the cost
        # model can calibrate against (persistence + schema this PR)
        fp = getattr(getattr(self, "plan", None), "fingerprint", None)
        if fp and sp.seconds is not None:
            _measured.record(fp, sp.seconds, k)
        from ..observability import slo as _slo

        # judgment layer: cadence-gated host-side evaluate — a single flag
        # check per dispatch until FLAGS_slo (or an explicit install) arms it
        _slo.on_tick()
        return {name: _wrap_tree(v) for name, v in metrics.items()}

    def explain(self, analyze: bool = False) -> list:
        """Per-specialization cost table: one row per compiled (kind,
        batch-shape) signature with the XLA ``cost_analysis``/
        ``memory_analysis`` captured at compile time (flops, bytes accessed,
        peak device memory, compile seconds). Render with
        ``paddle_tpu.observability.format_cost_table``.

        ``analyze=True`` additionally runs the SPMD sharding analyzer
        (paddle_tpu.analysis.spmd, PTA2xx) over each retained executable and
        attaches its verdict under the row's ``"spmd"`` key (collective
        counts, estimated reshard bytes, schedule fingerprint, findings) —
        works whether or not ``FLAGS_shard_check`` was on at compile time.
        """
        rows = [dict(r) for r in self._specializations]
        if analyze:
            from ..analysis import spmd as _spmd

            shardings = self._state_shardings
            psh = shardings.get("params") if isinstance(shardings, dict) else None
            # _compiled inserts exactly one entry per _specializations row,
            # in the same order (an aval-drift fallback swaps the value for
            # the plain jitfn, which has no retained HLO — skipped)
            for row, entry in zip(rows, list(self._compiled.values())):
                if "spmd" in row or not hasattr(entry, "as_text"):
                    continue
                row["spmd"] = _spmd.analyze_compiled(
                    entry, label=row.get("label", ""), kind=row.get("kind", ""),
                    params=self.state.get("params"),
                    param_shardings=psh).summary()
        return rows

    # -- interop -----------------------------------------------------------
    def sync_to_model(self):
        """Write compiled-state params/buffers back into the eager model."""
        for name, p in self.model.named_parameters():
            p._value = self.state["params"][name]
        for name, b in self.model.named_buffers():
            b._value = self.state["buffers"][name]

    def state_dict(self):
        return self.state

    def set_state(self, state):
        self.state = state

    def compile(self, sample_inputs, sample_labels):
        """AOT-compile and return the cost/compile stats (parity: first-run
        Convert+compile in interpretercore)."""
        inputs = tuple(jnp.asarray(unwrap(x)) for x in (sample_inputs if isinstance(sample_inputs, (list, tuple)) else [sample_inputs]))
        labels = tuple(jnp.asarray(unwrap(y)) for y in (sample_labels if isinstance(sample_labels, (list, tuple)) else [sample_labels]))
        lowered = self._jit.lower(self.state, (inputs, labels))
        compiled = lowered.compile()
        return compiled


class MultiStepRunner:
    """Amortized training driver over a batch stream: groups every K batches
    into one device-resident stack and runs them through
    :meth:`TrainStep.run_steps` — one Python/XLA dispatch per K steps, the
    JAX/XLA production-trainer idiom (device data + lax.scan, host sync only
    at log boundaries).

    ``batch_iter`` yields per-step ``(inputs, labels)`` batches (a plain
    ``io.DataLoader`` works); with ``prestacked=True`` it yields
    ``[k, ...]``-stacked pairs (``io.DataLoader(fuse_steps=k)``), skipping
    the host-side stacking here. Iterating the runner yields one stacked
    metrics dict per dispatch; a trailing group smaller than K still runs
    (one extra specialization compile for that size).

    ``monitor`` (a :class:`paddle_tpu.stability.HealthMonitor`) makes the
    runner health-aware: every dispatch's stacked metrics are fed to the
    monitor, which handles periodic checkpointing and divergence rollback
    (restoring ``step.state`` in place — the stream just keeps going with
    the rewound state). One observe per K steps: the guard's no-per-step-
    sync property is preserved.
    """

    def __init__(self, step: TrainStep, k: int, prestacked: bool = False,
                 monitor=None):
        if int(k) < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        self.step = step
        self.k = int(k)
        self.prestacked = prestacked
        self.monitor = monitor
        if monitor is not None and monitor.train_step is None:
            monitor.train_step = step

    def _emit(self, metrics):
        if self.monitor is not None:
            self.monitor.observe(metrics)
        return metrics

    def run(self, batch_iter):
        if self.prestacked:
            for stacked in batch_iter:
                lead = jax.tree_util.tree_leaves(stacked)[0].shape[0]
                yield self._emit(self.step.run_steps(tuple(stacked), k=lead))
            return
        group = []
        for batch in batch_iter:
            group.append(batch)
            if len(group) == self.k:
                yield self._emit(self.step.run_steps(group))
                group = []
        if group:
            yield self._emit(self.step.run_steps(group))

    __call__ = run


class EvalStep:
    """Compiled forward-only step.

    With ``mesh``, parameters are placed per their ``dist_spec`` annotations
    and inputs are batch-sharded over dp×sdp — sharded evaluation, the
    counterpart of fleet.distributed_step for inference/eval loops.
    """

    def __init__(self, model, mesh=None, batch_sharding=None):
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.model = model
        self.mesh = mesh

        def _fwd(params, buffers, inputs):
            out, _ = _pure_model_call(model, {**params, **buffers}, inputs, {}, False, None)
            return out

        if mesh is not None:
            param_shardings = {
                name: NamedSharding(mesh, p.dist_spec if getattr(p, "dist_spec", None) is not None else P())
                for name, p in model.named_parameters()
            }
            buf_shardings = {name: NamedSharding(mesh, P()) for name, _ in model.named_buffers()}
            if batch_sharding is None:
                batch_sharding = NamedSharding(mesh, P(("dp", "sdp")))
            self._param_shardings = param_shardings
            self._jit = jax.jit(_fwd, in_shardings=(param_shardings, buf_shardings, batch_sharding))
        else:
            self._param_shardings = None
            self._jit = jax.jit(_fwd)

    def __call__(self, *inputs):
        arrays = tuple(unwrap(x) if isinstance(x, Tensor) else jnp.asarray(x) for x in inputs)
        params = self.model.param_arrays()
        if self._param_shardings is not None:
            # place once per distinct param set — re-device_put per batch was a
            # host round-trip in the eval loop (VERDICT r3). The source dict is
            # held so `is`-identity over every leaf detects swapped params
            # without id-recycling hazards.
            src = getattr(self, "_placed_src", None)
            if src is None or src.keys() != params.keys() or any(
                    src[k] is not params[k] for k in params):
                self._placed = {k: jax.device_put(v, self._param_shardings[k]) for k, v in params.items()}
                self._placed_src = dict(params)
            params = self._placed
        out = self._jit(params, self.model.buffer_arrays(), arrays)
        return _wrap_tree(out)


def _preflight_lint(fn):
    """Run the dy2static pre-flight linter (paddle_tpu.analysis.ast_lint)
    over ``fn`` and surface findings as one UserWarning — BEFORE transpile or
    tracing, so unsupported constructs are reported with file:line instead of
    dying later as an opaque TracerBoolConversionError."""
    from ..analysis.ast_lint import lint_function

    try:
        diags = lint_function(fn)
    except (OSError, TypeError):  # source unavailable (C ext, REPL, …)
        return []
    if diags:
        import warnings

        from ..analysis.diagnostics import format_report

        warnings.warn("to_static(lint=True) pre-flight report for "
                      f"{getattr(fn, '__qualname__', fn)!r}:\n"
                      + format_report(diags), stacklevel=4)
    return diags


def to_static(function=None, input_spec=None, full_graph=True, lint=False, **kwargs):
    """Decorator compiling a Tensor-level function/Layer method with jax.jit.

    Parity: @paddle.jit.to_static including a minimal AST transpile
    (dygraph_to_static/program_translator.py:239): Python ``if``/``while``/
    ``for _ in range(...)`` and ``and``/``or``/``not`` are rewritten to
    runtime dispatchers that execute natively for concrete values and compile
    to lax.cond / lax.while_loop for traced ones (see jit/dy2static.py for
    the supported envelope). Unsupported shapes (returns inside branches,
    tuple-target loops, …) keep their Python semantics; a tensor-dependent
    condition there raises JAX's TracerBoolConversionError. The explicit
    bridges remain first-class: ``paddle.static.nn.cond``,
    ``paddle.static.nn.while_loop`` and ``paddle.static.nn.switch_case``
    work in eager, to_static and static programs alike; ``@jit.not_to_static``
    opts a function out of rewriting.

    ``lint=True`` runs the dy2static pre-flight linter first
    (paddle_tpu.analysis.ast_lint): unsupported constructs are reported with
    source line numbers via ``warnings`` and attached to the returned wrapper
    as ``__lint_report__`` — before any trace can fail.
    """

    def decorate(fn):
        import types

        from ..nn.layer.base import Layer
        from .dy2static import transpile

        if isinstance(fn, Layer):
            model = fn
            fwd = model.forward
            inner = getattr(fwd, "__func__", fwd)
            lint_report = _preflight_lint(inner) if lint else []
            rewritten = transpile(inner)
            if rewritten is not inner:
                model.forward = types.MethodType(rewritten, model)

            @functools.partial(jax.jit, static_argnums=(3,))
            def _fwd(params, buffers, args, training, rng):
                out, new_buffers = _pure_model_call(model, {**params, **buffers}, args, {}, training, rng)
                return out, new_buffers

            @functools.wraps(model.forward)
            def wrapper(*args):
                arrays = tuple(unwrap(a) if isinstance(a, Tensor) else jnp.asarray(a) for a in args)
                rng = _random.split_key() if model.training else None
                out, new_buffers = _fwd(model.param_arrays(), model.buffer_arrays(), arrays, model.training, rng)
                # propagate buffer side effects (BatchNorm running stats)
                for name, b in model.named_buffers():
                    b._value = new_buffers[name]
                return _wrap_tree(out)

            wrapper.__wrapped_layer__ = model
            wrapper.__lint_report__ = lint_report
            return wrapper

        lint_report = _preflight_lint(fn) if lint else []
        fn = transpile(fn)

        @functools.partial(jax.jit)
        def _pure(args):
            with no_grad():
                out = fn(*_wrap_tree(list(args)))
            return unwrap_tree(out)

        @functools.wraps(fn)
        def wrapper(*args):
            arrays = tuple(unwrap(a) if isinstance(a, Tensor) else jnp.asarray(a) for a in args)
            return _wrap_tree(_pure(arrays))

        wrapper.__lint_report__ = lint_report
        return wrapper

    if function is not None:
        return decorate(function)
    return decorate


def save(layer, path, input_spec=None, **configs):
    """paddle.jit.save parity: serialized params + executable StableHLO.

    The reference serializes a pruned ProgramDesc + params
    (python/paddle/fluid/dygraph/jit.py). Here: ``<path>.pdparams`` state
    dict always; with ``input_spec``, an executable jax.export artifact
    (``<path>.pdmodel`` + ``<path>.pdiparams`` metadata — the same format
    static.save_inference_model writes) loadable by ``jit.load`` as a
    TranslatedLayer and by ``paddle.inference.create_predictor``.
    """
    import pickle
    from pathlib import Path

    from ..framework.io import save as _save

    model = getattr(layer, "__wrapped_layer__", layer)
    _save(model.state_dict(), path + ".pdparams")
    if input_spec:
        scope = jax.export.SymbolicScope()
        specs, meta_shapes = [], []
        for i, s in enumerate(input_spec):
            shape = tuple(-1 if d is None else int(d) for d in s.shape)
            meta_shapes.append(list(shape))
            dt = jnp.dtype(s.dtype)  # handles str, np.dtype and scalar types
            if any(d < 0 for d in shape):
                spec_str = ",".join(f"d{i}_{j}" if d < 0 else str(d) for j, d in enumerate(shape))
                shape = jax.export.symbolic_shape(spec_str, scope=scope)
            specs.append(jax.ShapeDtypeStruct(shape, dt))

        params, buffers = model.param_arrays(), model.buffer_arrays()

        def _fwd(*args):
            out, _ = _pure_model_call(model, {**params, **buffers}, args, {}, False, None)
            return out

        exported = jax.export.export(jax.jit(_fwd))(*specs)
        Path(path + ".pdmodel").write_bytes(exported.serialize())
        meta = {
            "feed_names": [getattr(s, "name", None) or f"input_{i}" for i, s in enumerate(input_spec)],
            "fetch_names": [f"output_{i}" for i in range(len(exported.out_avals))],
            "feed_shapes": meta_shapes,
            "feed_dtypes": [str(s.dtype) for s in specs],
            # artifact provenance: .pdmodel is serialized StableHLO
            # (jax.export); this pickle sidecar is the legacy metadata format
            "format": "stablehlo",
            "producer": f"paddle_tpu/jax {jax.__version__}",
        }
        Path(path + ".pdiparams").write_bytes(pickle.dumps(meta))
    return path


class TranslatedLayer:
    """Loaded inference layer (reference TranslatedLayer
    python/paddle/fluid/dygraph/io.py:1137): callable like the original
    model, backed by the exported StableHLO artifact."""

    def __init__(self, prefix: str):
        from ..inference import Config, create_predictor

        self._predictor = create_predictor(Config(prefix))
        self.training = False

    def __call__(self, *args):
        arrays = [unwrap(a) if isinstance(a, Tensor) else jnp.asarray(a) for a in args]
        outs = self._predictor.run(arrays)
        wrapped = [_wrap_value(jnp.asarray(o)) for o in outs]
        return wrapped[0] if len(wrapped) == 1 else tuple(wrapped)

    forward = __call__

    def explain(self) -> list:
        """Per-specialization XLA cost rows from the backing AOT Predictor."""
        return self._predictor.explain()

    @property
    def backend(self) -> str:
        """The resolved backend the artifact actually runs on."""
        return self._predictor.get_resolved_backend()

    def eval(self):
        return self

    def train(self):
        raise RuntimeError("TranslatedLayer is inference-only (reference parity)")


def load(path, **configs):
    """jit.load parity: with a .pdmodel artifact returns a TranslatedLayer;
    otherwise the bare state dict saved by jit.save."""
    import os

    from ..framework.io import load as _load

    if os.path.exists(path + ".pdmodel"):
        return TranslatedLayer(path)
    return _load(path + ".pdparams")


from ..static import InputSpec  # noqa: E402 — one class for jit AND static
from .dy2static import not_to_static  # noqa: E402 — opt-out marker
# (reference: paddle.static.InputSpec is the single spec type both use)


class ProgramTranslator:
    """Singleton toggling @to_static rewriting (reference
    dygraph_to_static/program_translator.py:920 ProgramTranslator.enable)."""

    _instance = None
    enabled = True

    @classmethod
    def get_instance(cls):
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def enable(self, enable_to_static=True):
        type(self).enabled = bool(enable_to_static)


def enable_to_static(enable=True):
    ProgramTranslator.get_instance().enable(enable)


def set_verbosity(level=0, also_to_stdout=False):
    """dy2static logging verbosity (reference jit.set_verbosity); recorded
    only — the transpiler emits no logs."""
    ProgramTranslator.verbosity = int(level)


def set_code_level(level=100, also_to_stdout=False):
    ProgramTranslator.code_level = int(level)


class TracedLayer:
    """Legacy trace-based export (reference fluid/dygraph/jit.py TracedLayer):
    trace(layer, inputs) -> (outputs, traced) where traced serves the jitted
    forward and save_inference_model exports it."""

    def __init__(self, layer, inputs):
        self._layer = layer
        self._fn = to_static(layer)
        self._example = inputs

    @staticmethod
    def trace(layer, inputs):
        t = TracedLayer(layer, inputs)
        return t._fn(*inputs), t

    def __call__(self, *args):
        return self._fn(*args)

    def save_inference_model(self, path, feed=None, fetch=None):
        from ..static import InputSpec

        specs = [InputSpec(tuple(x.shape), str(x._value.dtype)) for x in self._example]
        return save(self._layer, path, input_spec=specs)
