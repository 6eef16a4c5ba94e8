"""chip_smoke.py — the quickest proof that paddle_tpu still starts on the chip.

One process drives the flagship GPT (h1024 / L16 / 16 heads / vocab 50304,
weights from ``paddle.seed``) through the entry points a user calls, in this
order; each phase prints one JSON line and the first failure ends the run
with a non-zero exit code:

1. *device*  — ``jax.devices()`` must be a TPU; versions and the compile-cache
   directory in force are printed;
2. *train*   — ``paddle.jit.TrainStep`` (AdamW, AMP O2) at batch 8 x 1024: a
   few steps with each loss pulled to the host, then one ``run_steps(k=8)``;
3. *serve*   — the trained model in bf16 through ``DecodeEngine`` ->
   ``ContinuousBatchingScheduler`` -> a one-replica ``ServingFleet`` ->
   ``ServingIngress`` on a loopback port, greedy tokens held to
   ``model.generate()``, the decode step through the aliased cache kernel;
4. *kernels* — forward+backward parity of every Pallas kernel a supported
   model can reach against its plain XLA reference.

``--chips 4`` runs only the multi-chip phase and what it is compared with:
``fleet.distributed_step`` on a 2x2 mesh (dp2 x mp2, then sharding2 x mp2)
against the one-device step, and four one-chip ``ServingFleet`` replicas in
this one process against a single replica.

The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``;
on failure it is not printed. There is no CPU fallback: without a TPU the
device phase fails.
"""
from __future__ import annotations

import argparse
import gc
import http.client
import json
import os
import sys
import time

import numpy as np

# -- sizes: the flagship configuration at full width (gpt2-medium's widths at
# 16 layers). tests/test_chip_smoke.py swaps these
# for a tiny set; nothing else selects a size.
MODEL = dict(vocab_size=50304, hidden_size=1024, num_layers=16, num_heads=16, max_seq_len=1024)
TRAIN = dict(batch=8, seq=1024, steps=4, fused_k=8)
SERVE = dict(slots=8, prefill_chunk=128, prompt_lens=(24, 131, 300, 70), max_new_tokens=8)
FLASH_SHAPES = ((8, 1024, 16, 64), (8, 1024, 16, 128), (1, 4096, 16, 64))  # b, s, h, d (bf16, causal)
GQA_SHAPES = ((2, 512, 8, 64, 2, False), (2, 1024, 8, 64, 8, True))  # b, s, h, d, h_kv, causal
MOE = dict(tokens=8192, d_model=1024, d_hidden=4096, experts=8, top_k=2, capacity_factor=1.25)
KERNEL_TOL = 4e-2   # max |kernel - reference| / max |reference|, bf16 operands
# two greedy decodes of one bf16 model may part ways only where the reference
# itself cannot tell the two tokens apart: logits within 8 bf16 ulps (2^-5 of
# the row's largest magnitude) of each other and of the row maximum — three
# roundings of a few ulps each: the two decodes' and the reference's own
LOGIT_TIE = 2.0 ** -5
# the mesh phase runs in float32 at "highest" matmul precision so that the
# sharded step can be held to the tolerance the CPU-mesh tests use
# (tests/test_distributed.py: 1e-5); depth is cut, widths are not
MESH = dict(num_layers=4, steps=2, rtol=1e-5)
MESH_LAYOUTS = (dict(dp=2, mp=2, sdp=1, stage=0), dict(dp=1, mp=2, sdp=2, stage=2))


class SmokeFailure(Exception):
    pass


def check(cond, message):
    if not cond:
        raise SmokeFailure(message)


_phase_clock = [time.perf_counter()]


def emit(phase, **fields):
    """One JSON line per phase, with the wall seconds since the line before."""
    now = time.perf_counter()
    print(json.dumps({"phase": phase, "phase_seconds": round(now - _phase_clock[0], 1), **fields}),
          flush=True)
    _phase_clock[0] = now


class _CacheEvents:
    """Counts JAX's persistent-compilation-cache hits and misses, so a second
    run in the same checkout can show that it compiled nothing again."""

    def __init__(self):
        from jax import monitoring

        self.hits = self.misses = 0
        monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def take(self):
        out = {"hits": self.hits, "misses": self.misses}
        self.hits = self.misses = 0
        return out


def _cache_bytes():
    """Bytes under the compile-cache directory: JAX's own entries at its top
    level, and the AOT executable store's per scope beneath."""
    from paddle_tpu.framework.flags import compile_cache_dir

    sizes = {}
    root = compile_cache_dir()
    for dirpath, _, files in os.walk(root):
        scope = os.path.relpath(dirpath, root).split(os.sep)[0]
        sizes[scope] = sizes.get(scope, 0) + sum(
            os.path.getsize(os.path.join(dirpath, f)) for f in files)
    sizes["total"] = sum(sizes.values())
    return sizes


def require_tpu(n_chips):
    """The device phase's gate: a TPU with at least ``n_chips`` devices, or
    the run ends here."""
    import jax

    devices = jax.devices()
    check(devices[0].platform == "tpu",
          f"no TPU: jax.devices()[0] is {devices[0].platform!r} ({devices[0].device_kind!r})")
    check(len(devices) >= n_chips, f"need {n_chips} chips, jax.devices() has {len(devices)}")
    return devices[:n_chips]


def phase_device(n_chips):
    from importlib import metadata

    import jax
    import jaxlib

    from paddle_tpu.framework import native
    from paddle_tpu.framework.flags import ensure_compile_cache

    devices = require_tpu(n_chips)
    cache_dir = ensure_compile_cache()
    native.load_native()  # built from csrc/ when build/ is absent; a failed build raises
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    emit("device", platform=devices[0].platform, device_kind=devices[0].device_kind,
         count=len(jax.devices()), used=n_chips, jax=jax.__version__,
         jaxlib=jaxlib.__version__, libtpu=libtpu, compile_cache_dir=cache_dir)
    return devices


def _flagship(seed, **overrides):
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining

    paddle.seed(seed)
    return GPTForPretraining(GPTConfig(**{**MODEL, **overrides}))


def _batch(seed, vocab, batch, seq):
    return np.random.default_rng(seed).integers(0, vocab, (batch, seq)).astype("int32")


# ------------------------------------------------------------------ train
def phase_train(seed, cache_events):
    import paddle_tpu as paddle
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.gpt import GPTPretrainingCriterion
    from paddle_tpu.observability import metrics

    model = _flagship(seed)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters())
    step = TrainStep(model, opt, GPTPretrainingCriterion(), amp_level="O2")
    ids = _batch(seed, MODEL["vocab_size"], TRAIN["batch"], TRAIN["seq"])
    t = paddle.to_tensor(ids)

    losses, seconds = [], []
    for _ in range(TRAIN["steps"]):
        t0 = time.perf_counter()
        out = step(t, t)
        losses.append(float(out["loss"]))  # host transfer: the step is done
        seconds.append(time.perf_counter() - t0)

    k = TRAIN["fused_k"]
    t0 = time.perf_counter()
    out = step.run_steps((np.stack([ids] * k), np.stack([ids] * k)), k=k)
    fused = [float(v) for v in np.asarray(out["loss"]._value)]
    fused_seconds = time.perf_counter() - t0

    check(all(np.isfinite(losses + fused)), f"non-finite loss: {losses} {fused}")
    check(losses[-1] < losses[0] and fused[-1] < losses[-1],
          f"loss not falling on the repeated batch: {losses} then {fused}")
    kernels = {k_: v for k_, v in metrics.counters("kernels.").items() if v}
    check(kernels.get("kernels.attention_core.picked", 0) > 0
          and not kernels.get("kernels.attention_core.fallback", 0),
          f"the model path did not pick the Pallas attention kernel: {kernels}")
    ts = metrics.counters("train_step.")
    programs = ts.get("train_step.compiles", 0) + ts.get("train_step.aot_cache_hits", 0)
    check(programs == 2, f"expected 2 programs (step, run_steps), got {ts}")
    mem = paddle.device.memory_stats()  # raises if the TPU reports none
    emit("train", config=f"h{MODEL['hidden_size']}L{MODEL['num_layers']}"
                         f"b{TRAIN['batch']}s{TRAIN['seq']}/amp=O2",
         losses=losses, fused_losses=fused, first_step_seconds=seconds[0],
         warm_step_seconds=min(seconds[1:]), run_steps_k=k,
         run_steps_first_seconds=fused_seconds, kernels=kernels,
         compiles=ts.get("train_step.compiles", 0),
         aot_cache_hits=ts.get("train_step.aot_cache_hits", 0),
         compile_cache=cache_events.take(),
         peak_bytes_in_use=mem["peak_bytes_in_use"], bytes_limit=mem.get("bytes_limit"))

    step.sync_to_model()  # the serve phase serves what was trained
    return model


# ------------------------------------------------------------------ serve
def _http(port, method, path, body=None, stream=False, timeout=900):
    """One loopback request. Returns (status, doc) or, streamed,
    (status, tokens, final doc, seconds to the first chunk)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        t0 = time.perf_counter()
        conn.request(method, path, body=None if body is None else json.dumps(body).encode(),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        if not stream:
            return resp.status, json.loads(resp.read())
        tokens, final, first = [], None, None
        while True:
            line = resp.readline()
            if not line:
                break
            doc = json.loads(line)
            if "tokens" in doc and "status" not in doc:
                if first is None:
                    first = time.perf_counter() - t0
                tokens.extend(doc["tokens"])
            else:
                final = doc
        return resp.status, tokens, final, first
    finally:
        conn.close()


def _serve_requests(fleet, prompts, max_new):
    """The prompts through a ``ServingIngress`` over ``fleet``: the first and
    the last streamed (time to first token cold and warm), the rest as plain
    JSON. Returns (tokens per prompt, cold ttft, warm ttft)."""
    from paddle_tpu.inference import ServingIngress

    ingress = ServingIngress(fleet, port=0, request_timeout=900.0)
    try:
        status, doc = _http(ingress.port, "GET", "/healthz")
        check(status == 200 and doc.get("ok"), f"/healthz answered {status} {doc}")
        got, ttft = [], {}
        for i, p in enumerate(prompts):
            body = {"prompt": [int(x) for x in p], "max_new_tokens": max_new, "seed": i}
            if i in (0, len(prompts) - 1):
                status, tokens, final, first = _http(
                    ingress.port, "POST", "/v1/generate", dict(body, stream=True), stream=True)
                check(status == 200 and final and final.get("status") == "finished",
                      f"streamed request {i}: {status} {final}")
                ttft[i] = first
            else:
                status, doc = _http(ingress.port, "POST", "/v1/generate", body)
                check(status == 200 and doc.get("status") == "finished", f"request {i}: {status} {doc}")
                tokens = doc["tokens"]
            check(len(tokens) == max_new, f"request {i}: {len(tokens)} tokens, wanted {max_new}")
            got.append([int(x) for x in tokens])
    finally:
        rc = ingress.drain(grace=60.0)
    check(rc == 0, f"ingress drain exit code {rc}")
    return got, ttft[0], ttft[len(prompts) - 1]


def _prompts(seed):
    rng = np.random.default_rng(seed + 1)
    return [rng.integers(0, MODEL["vocab_size"], (n,)).astype("int32") for n in SERVE["prompt_lens"]]


def _engine_kwargs():
    return dict(max_batch_slots=SERVE["slots"], max_seq_len=MODEL["max_seq_len"],
                prefill_chunk=SERVE["prefill_chunk"])


def _agree(model, prompts, got, want, what):
    """Hold served tokens to a reference decode of the same model. Equal
    tokens agree. Where they first differ, the full forward of the model —
    no cache, no chunks — must rate the two tokens a tie (``LOGIT_TIE``):
    in bf16 a chunked prefill and a one-shot prefill round differently, and
    an argmax over 50k near-uniform logits may then flip. After a tie the
    two decodes follow different prefixes and are not compared further.
    Returns the ties found; anything else is a failure."""
    import paddle_tpu as paddle

    ties = []
    for i, (p, g, w) in enumerate(zip(prompts, got, want)):
        j = next((k for k, (a, b) in enumerate(zip(g, w)) if a != b), None)
        if j is None:
            check(len(g) == len(w), f"{what}: request {i} served {len(g)} tokens, reference {len(w)}")
            continue
        seq = np.concatenate([p, np.asarray(g[:j], "int32")])[None]
        row = model(paddle.to_tensor(seq)).numpy()[0, -1].astype(np.float32)
        scale = float(np.abs(row).max())
        below_max = float(row.max() - min(row[g[j]], row[w[j]]))
        check(below_max <= LOGIT_TIE * scale,
              f"{what}: request {i} token {j}: served {g[j]}, reference {w[j]}, and the full "
              f"forward puts them {below_max:.4g} apart or below its maximum (logit scale "
              f"{scale:.4g}) — not a tie: {g} != {w}")
        ties.append({"request": i, "position": j, "served": g[j], "reference": w[j],
                     "logit_gap": below_max, "logit_scale": scale})
    return ties


def phase_serve(model, seed, cache_events):
    import paddle_tpu as paddle
    from paddle_tpu.inference import ServingFleet
    from paddle_tpu.observability import metrics

    model.astype("bfloat16")
    model.eval()
    gc.collect()  # the trainer's float32 state goes before the cache is allocated
    prompts, max_new = _prompts(seed), SERVE["max_new_tokens"]
    fleet = ServingFleet(model, replicas=1, **_engine_kwargs())
    got, ttft_cold, ttft_warm = _serve_requests(fleet, prompts, max_new)

    want = [[int(x) for x in model.generate(paddle.to_tensor(p[None]), max_new_tokens=max_new)
             .numpy()[0, len(p):]] for p in prompts]
    ties = _agree(model, prompts, got, want, "ingress vs model.generate()")
    infer = metrics.counters("infer.")
    programs = infer.get("infer.compiles", 0) + infer.get("infer.aot_cache_hits", 0)
    check(programs == 3, f"expected 3 programs (chunk, final chunk, decode), got {infer}")
    kernels = {k_: v for k_, v in metrics.counters("kernels.decode_attention.").items() if v}
    check(kernels.get("kernels.decode_attention.picked", 0) > 0
          and not kernels.get("kernels.decode_attention.fallback", 0),
          f"the decode step did not pick the aliased cache kernel: {kernels}")
    emit("serve", config=f"h{MODEL['hidden_size']}L{MODEL['num_layers']}/bf16/"
                         f"slots{SERVE['slots']}s{MODEL['max_seq_len']}c{SERVE['prefill_chunk']}",
         requests=len(prompts), prompt_lens=list(SERVE["prompt_lens"]), tokens=got,
         tokens_decoded=sum(map(len, got)),
         matches_generate=[g == w for g, w in zip(got, want)], bf16_ties=ties,
         ttft_cold_seconds=ttft_cold, ttft_warm_seconds=ttft_warm,
         compiles=infer.get("infer.compiles", 0),
         aot_cache_hits=infer.get("infer.aot_cache_hits", 0), kernels=kernels,
         compile_cache=cache_events.take())


# ---------------------------------------------------------------- kernels
def _rel_err(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    check(np.all(np.isfinite(got)), "non-finite kernel output")
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-6))


def _fwd_bwd(fn, args, cotangent, n_diff):
    """(output, grads w.r.t. the first ``n_diff`` args) of
    ``sum(fn(*args) * cotangent)``, jitted. Everything is an argument: an
    array closed over would be baked into the executable, and into every
    cache entry made of it."""
    import jax
    import jax.numpy as jnp

    def loss(cotangent, *a):
        out = fn(*a)
        return jnp.sum(out.astype(jnp.float32) * cotangent.astype(jnp.float32)), out

    argnums = tuple(range(1, n_diff + 1))
    (_, out), grads = jax.jit(jax.value_and_grad(loss, argnums=argnums, has_aux=True))(
        cotangent, *args)
    return out, grads


def _parity(name, kernel, reference, args, cotangent, n_diff):
    out, grads = _fwd_bwd(kernel, args, cotangent, n_diff)
    ref_out, ref_grads = _fwd_bwd(reference, args, cotangent, n_diff)
    fwd = _rel_err(out, ref_out)
    bwd = max(_rel_err(g, r) for g, r in zip(grads, ref_grads))
    check(fwd < KERNEL_TOL and bwd < KERNEL_TOL,
          f"{name}: fwd err {fwd:.3g}, bwd err {bwd:.3g} (tolerance {KERNEL_TOL})")
    return {"fwd_err": fwd, "bwd_err": bwd}


def _not_fallback(kernel, *args, **kwargs):
    from paddle_tpu.ops import registry

    impl = registry.select(kernel, *args, **kwargs)
    check(not impl.fallback, f"registry fell back to {impl.name!r} for {kernel} at the flagship shape")
    return impl.name


def phase_kernels(seed, cache_events):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.distributed.moe import dense_dispatch_combine
    from paddle_tpu.nn.functional.attention import _sdpa_reference
    from paddle_tpu.ops import flash_attention as fa
    from paddle_tpu.ops import flash_attention_flat as ff
    from paddle_tpu.ops import moe_pallas

    rng = np.random.default_rng(seed + 2)
    bf16 = jnp.bfloat16

    def normal(shape, scale=1.0, dtype=bf16):
        return jnp.asarray(rng.standard_normal(shape) * scale, dtype)

    results, selected = {}, {}
    for shape in FLASH_SHAPES:
        q, k, v, g = (normal(shape) for _ in range(4))
        ref = lambda q, k, v: fa._reference_attention(q, k, v, True)  # noqa: E731
        tag = "x".join(map(str, shape))
        results[f"flash/{tag}"] = _parity(
            f"classic flash {shape}", lambda q, k, v: fa._flash(q, k, v, True), ref, (q, k, v), g, 3)
        results[f"flash_flat/{tag}"] = _parity(
            f"flat flash {shape}", lambda q, k, v: ff.flash_flat(q, k, v, True), ref, (q, k, v), g, 3)
        results[f"flash_packed/{tag}"] = _parity(
            f"packed flat flash {shape}",
            lambda q, k, v: ff.flash_packed(jnp.stack([q, k, v], axis=2), True), ref, (q, k, v), g, 3)
        if shape == FLASH_SHAPES[0]:  # the shape the flagship model calls with
            selected["sdpa"] = _not_fallback("sdpa", q, k, v, None, True, 0.0, None, None)
            selected["attention_core"] = _not_fallback(
                "attention_core", jnp.stack([q, k, v], axis=2), 0.0, None)

    for b, s, h, d, h_kv, causal in GQA_SHAPES:
        q, g = normal((b, s, h, d)), normal((b, s, h, d))
        k, v = normal((b, s, h_kv, d)), normal((b, s, h_kv, d))
        # padding mask: the last quarter of the keys masked off
        mask = jnp.broadcast_to(jnp.where(jnp.arange(s) < 3 * s // 4, 0.0, -1e30)
                                .astype(jnp.float32), (b, 1, s, s))
        rep = h // h_kv
        results[f"flash_flat_gqa/{b}x{s}x{h}x{d}kv{h_kv}"] = _parity(
            f"flash_flat_gqa {(b, s, h, d, h_kv)}",
            lambda q, k, v, mask: ff.flash_flat_gqa(q, k, v, causal=causal, mask=mask),
            lambda q, k, v, mask: _sdpa_reference(
                q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2), mask, causal).astype(q.dtype),
            (q, k, v, mask), g, 3)

    T, D, H, E, K = (MOE[x] for x in ("tokens", "d_model", "d_hidden", "experts", "top_k"))
    capacity = int(MOE["capacity_factor"] * T * K / E)
    tokens, g = normal((T, D)), normal((T, D))
    gate_vals, gate_idx = jax.lax.top_k(jax.nn.softmax(normal((T, E), dtype=jnp.float32)), K)
    gate_vals = gate_vals.astype(bf16)
    w1, b1 = normal((E, D, H), 0.02), normal((E, 1, H), 0.02)
    w2, b2 = normal((E, H, D), 0.02), normal((E, 1, D), 0.02)

    def moe(impl):
        return lambda tok, gv, w1, b1, w2, b2, gate_idx: impl(
            tok, gv, gate_idx, None, w1, b1, w2, b2, capacity=capacity, activation=jax.nn.gelu)

    results[f"moe/T{T}D{D}H{H}E{E}K{K}"] = _parity(
        "moe_dispatch_combine", moe(moe_pallas.moe_dispatch_combine), moe(dense_dispatch_combine),
        (tokens, gate_vals, w1, b1, w2, b2, gate_idx), g, 6)
    selected["moe"] = _not_fallback("moe", tokens, gate_vals, gate_idx, None, w1, b1, w2, b2,
                                    capacity=capacity, activation=jax.nn.gelu)
    emit("kernels", tolerance=KERNEL_TOL, parity=results, selected=selected,
         compile_cache=cache_events.take())


# ------------------------------------------------------------- four chips
def _distinct_devices(array):
    return {s.device for s in array.addressable_shards}


def _mesh_losses(step, ids, steps):
    return [float(step(ids, ids)["loss"]) for _ in range(steps)]


def phase_replicas(devices, seed, cache_events):
    """Four one-chip replicas in this one process against a single replica."""
    from paddle_tpu.inference import ServingFleet

    model = _flagship(seed)
    model.astype("bfloat16")
    model.eval()
    prompts, max_new = _prompts(seed), SERVE["max_new_tokens"]
    want, _, _ = _serve_requests(ServingFleet(model, replicas=1, **_engine_kwargs()), prompts, max_new)

    n = len(devices)
    fleet = ServingFleet(model, replicas=n, **_engine_kwargs())
    placed = [rep.engine.device for rep in fleet.replicas.values()]
    check(len(set(placed)) == n, f"replica caches share devices: {placed}")
    fids = [fleet.submit(p, max_new_tokens=max_new, seed=i, replica=i % n)
            for i, p in enumerate(prompts)]
    done = fleet.run()
    got = [[int(x) for x in done[f].tokens] for f in fids]
    ties = _agree(model, prompts, got, want, f"{n} replicas vs one")
    served = [rep.completed for rep in fleet.replicas.values()]
    check(all(served), f"a replica served nothing: {served}")
    emit("replicas", replicas=n, cache_devices=[str(d) for d in placed], completed=served,
         tokens=got, matches_single_replica=[g == w for g, w in zip(got, want)],
         bf16_ties=ties, compile_cache=cache_events.take())


def phase_mesh(devices, seed, cache_events):
    """``fleet.distributed_step`` on a real 2x2 mesh against the one-device
    step: same batch, same weights, float32 at "highest" precision."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.strategy import DistributedStrategy
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.gpt import GPTPretrainingCriterion

    jax.config.update("jax_default_matmul_precision", "highest")
    ids = paddle.to_tensor(_batch(seed, MODEL["vocab_size"], TRAIN["batch"], TRAIN["seq"]))
    steps = MESH["steps"]
    gc.collect()
    # what the phases before left on each chip does not count as this step's
    base = [paddle.device.memory_stats(d)["bytes_in_use"] for d in devices]

    def build(make_step):
        model = _flagship(seed, num_layers=MESH["num_layers"])
        opt = paddle.optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters())
        return make_step(model, opt, GPTPretrainingCriterion())

    step = build(TrainStep)
    want = _mesh_losses(step, ids, steps)
    single_bytes = paddle.device.memory_stats(devices[0])["bytes_in_use"] - base[0]
    del step
    gc.collect()
    emit("mesh_reference", layers=MESH["num_layers"], losses=want, bytes_in_use=single_bytes,
         compile_cache=cache_events.take())

    for layout in MESH_LAYOUTS:
        strategy = DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": layout["dp"], "mp_degree": layout["mp"],
                                   "pp_degree": 1, "sharding_degree": layout["sdp"]}
        if layout["sdp"] > 1:
            strategy.sharding = True
            strategy.sharding_configs = {"sharding_stage": layout["stage"]}
        fleet.init(is_collective=True, strategy=strategy, devices=list(devices))
        step = build(fleet.distributed_step)
        got = _mesh_losses(step, fleet.shard_batch(ids), steps)
        worst = max(abs(g - w) / abs(w) for g, w in zip(got, want))
        check(worst <= MESH["rtol"], f"{layout}: losses {got} vs one device {want} (rel {worst:.3g})")

        # parameters and optimizer state really are spread over the chips
        qkv = step.state["params"]["gpt.layers.qkv_w"]
        moment = step.state["opt"]["m"]["gpt.layers.qkv_w"]
        for name, arr in (("qkv_w", qkv), ("adam m of qkv_w", moment)):
            check(len(_distinct_devices(arr)) == len(devices),
                  f"{layout}: {name} lives on {_distinct_devices(arr)}")
        check(qkv.addressable_shards[0].data.size < qkv.size, f"{layout}: qkv_w is not sharded")
        if layout["sdp"] > 1:
            check(moment.addressable_shards[0].data.size * len(devices) == moment.size,
                  f"{layout}: optimizer state is not sharded over sdp x mp")
        per_device = [paddle.device.memory_stats(d)["bytes_in_use"] - b0
                      for d, b0 in zip(devices, base)]
        check(max(per_device) < single_bytes,
              f"{layout}: per-device bytes {per_device} not below the one-chip {single_bytes}")
        emit("mesh", layout=layout, losses=got, max_rel_diff=worst, rtol=MESH["rtol"],
             qkv_shard_shape=list(qkv.addressable_shards[0].data.shape), qkv_shape=list(qkv.shape),
             bytes_in_use=per_device, compile_cache=cache_events.take())
        del step, qkv, moment
        gc.collect()


# ------------------------------------------------------------------- main
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: device, train, serve, kernels on one chip (default); "
                         "4: only the multi-chip phase and what it is compared with")
    ap.add_argument("--seed", type=int, default=0, help="weights, batches and prompts")
    args = ap.parse_args(argv)

    import paddle_tpu  # noqa: F401  (a bare directory fails here, before any output)

    cache_events = _CacheEvents()
    t0 = time.perf_counter()
    devices = phase_device(args.chips)
    if args.chips == 1:
        model = phase_train(args.seed, cache_events)
        phase_serve(model, args.seed, cache_events)
        del model
        gc.collect()
        phase_kernels(args.seed, cache_events)
    else:
        # serving first: once fleet.init() has run, the model's forward
        # constrains its activations to the fleet mesh
        phase_replicas(devices, args.seed, cache_events)
        phase_mesh(devices, args.seed, cache_events)
    emit("done", seconds=time.perf_counter() - t0, compile_cache_bytes=_cache_bytes())
    print(json.dumps({"ok": True, "device": {"platform": devices[0].platform,
                                             "kind": devices[0].device_kind,
                                             "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
