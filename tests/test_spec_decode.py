"""Decode raw speed round 3: draft-model speculative decoding (greedy
accepted tokens BITWISE-pinned against generate() and the non-spec engine,
dispatch amortization, sampled-mode residual resampling determinism,
kill-safe fleet requeue with draft kwargs) and the int8 KV cache (per-head
abs_max scales, >= 3x per-slot byte shrink, chunked-prefill/prefix-hit
bitwise family, documented-tolerance parity vs f32)."""
import contextlib

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.inference import ContinuousBatchingScheduler, DecodeEngine, ServingFleet
from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
from paddle_tpu.testing import chaos


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = GPTForPretraining(GPTConfig.tiny())
    m.eval()
    return m


@pytest.fixture(scope="module", autouse=True)
def aot_dir(tmp_path_factory):
    # shared executable cache: engines rebuilt with an identical spec load
    # their compiled family from disk instead of recompiling (keeps this
    # file's many-engine matrix inside the tier-1 wall-clock budget)
    prev = paddle.get_flags("FLAGS_compile_cache_dir")["FLAGS_compile_cache_dir"]
    d = tmp_path_factory.mktemp("spec_aot")
    paddle.set_flags({"FLAGS_compile_cache_dir": str(d)})
    yield str(d)
    paddle.set_flags({"FLAGS_compile_cache_dir": prev})


@contextlib.contextmanager
def _no_aot_store():
    """Every program inside is compiled by this process, none loaded from the module's store."""
    prev = paddle.get_flags("FLAGS_compile_cache_dir")["FLAGS_compile_cache_dir"]
    paddle.set_flags({"FLAGS_compile_cache_dir": ""})
    try:
        yield
    finally:
        paddle.set_flags({"FLAGS_compile_cache_dir": prev})


def _draft_cfg(**kw):
    """A genuinely smaller draft: 1 layer, hidden 32 — same vocab."""
    cfg = dict(vocab_size=512, hidden_size=32, num_layers=1, num_heads=2,
               max_seq_len=128)
    cfg.update(kw)
    return GPTConfig(**cfg)


def _prompts(n, lens=(5, 9, 3, 12, 7, 11)):
    rng = np.random.default_rng(42)
    return [rng.integers(0, 512, (lens[i % len(lens)],)).astype("int32")
            for i in range(n)]


# ------------------------------------------------------ greedy bitwise pins
def test_spec_decode_oracle_draft_bitwise_matrix(model):
    """The acceptance pin: with the TARGET as its own draft (oracle — every
    proposal accepted) greedy spec decode is BITWISE equal to generate()
    and to the plain non-spec engine at every K. Speculation must never
    change greedy output — only how many dispatches produce it."""
    ids = np.random.default_rng(11).integers(0, 512, (2, 9)).astype("int32")
    base = DecodeEngine(model, max_batch_slots=2, max_seq_len=64,
                        prefill_buckets=(16,))
    want = base.generate(ids, max_new_tokens=12)
    np.testing.assert_array_equal(
        want[:, 9:], np.asarray(model.generate(
            paddle.to_tensor(ids), max_new_tokens=12).numpy())[:, 9:])
    for k in (1, 2, 4):
        eng = DecodeEngine(model, max_batch_slots=2, max_seq_len=64,
                           prefill_buckets=(16,), draft=model, spec_k=k)
        got = eng.generate(ids, max_new_tokens=12)
        np.testing.assert_array_equal(got, want, err_msg=f"K={k}")


@pytest.mark.slow
def test_spec_decode_random_draft_bitwise(model):
    """A random (near-zero-acceptance) draft still yields BITWISE greedy
    output: rejected tails roll the slot position back and the correction
    token comes from the target verification row — correctness is
    independent of draft quality, only throughput depends on it."""
    ids = np.random.default_rng(3).integers(0, 512, (2, 7)).astype("int32")
    base = DecodeEngine(model, max_batch_slots=2, max_seq_len=64,
                        prefill_buckets=(8,))
    want = base.generate(ids, max_new_tokens=10)
    for k in (1, 4):  # K=2 rides the oracle matrix + the fleet test
        eng = DecodeEngine(model, max_batch_slots=2, max_seq_len=64,
                           prefill_buckets=(8,), draft=_draft_cfg(), spec_k=k,
                           draft_seed=7)
        got = eng.generate(ids, max_new_tokens=10)
        np.testing.assert_array_equal(got, want, err_msg=f"K={k}")


def test_spec_decode_eos_mid_window(model):
    """eos landing INSIDE a speculative window stops the row exactly where
    the sequential path stops it — tokens after eos in the accepted run are
    discarded by the in-graph emission ledger, not emitted then patched."""
    ids = np.random.default_rng(5).integers(0, 512, (1, 6)).astype("int32")
    base = DecodeEngine(model, max_batch_slots=1, max_seq_len=64,
                        prefill_buckets=(8,))
    probe = base.generate(ids, max_new_tokens=12)
    eos = int(probe[0, 6 + 4])  # token #5 of the continuation becomes eos
    want = base.generate(ids, max_new_tokens=12, eos_token_id=eos)
    eng = DecodeEngine(model, max_batch_slots=1, max_seq_len=64,
                       prefill_buckets=(8,), draft=model, spec_k=4)
    got = eng.generate(ids, max_new_tokens=12, eos_token_id=eos)
    np.testing.assert_array_equal(got, want)


def test_spec_decode_dispatch_amortization_and_compile_pin(model):
    """The raw-speed claim, CI-pinned: at acceptance > 0 one spec dispatch
    emits more than one token, so decode_dispatches_per_token drops below
    1/D of the PR-7 fused baseline's best pin (ceil(N/D) dispatches). With
    the oracle draft at K=4, N=15 tokens take <= ceil(15/5)+1 = 4 decode
    dispatches vs 8 for fuse=2 — and the compile family stays fixed at
    prefill + ONE spec program."""
    ids = np.random.default_rng(9).integers(0, 512, (1, 8)).astype("int32")
    profiler.reset_counters("infer.")
    with _no_aot_store():  # cold: pin REAL compiles
        eng = DecodeEngine(model, max_batch_slots=1, max_seq_len=64,
                           prefill_buckets=(8,), draft=model, spec_k=4)
        eng.generate(ids, max_new_tokens=15)
    counts = profiler.counters("infer.")
    n_disp = counts["infer.decode_dispatches"]
    assert n_disp <= 4, counts                       # ceil(15/5) + 1 slack
    fused_baseline = -(-15 // 2)                     # PR-7 fuse=2 pin: 8
    assert n_disp < fused_baseline, counts
    assert counts["infer.compiles"] == 2, counts     # prefill + spec_decode
    # the accounting satellites rode along
    assert counts["infer.spec_draft_tokens"] >= 4 * (n_disp - 1)
    assert counts["infer.spec_accepted_tokens"] > 0
    st = eng.spec_stats()
    assert st["spec_k"] == 4 and st["acceptance_rate"] > 0.5
    assert eng.kv_bytes_per_slot() > 0


def test_spec_decode_validation(model):
    with pytest.raises(ValueError):
        DecodeEngine(model, max_batch_slots=1, max_seq_len=64,
                     prefill_buckets=(8,), draft=model, fuse=2)
    with pytest.raises(ValueError):
        DecodeEngine(model, max_batch_slots=1, max_seq_len=64,
                     prefill_buckets=(8,), draft=model, spec_k=0)
    with pytest.raises(ValueError):
        DecodeEngine(model, max_batch_slots=1, max_seq_len=64,
                     prefill_buckets=(8,), kv_dtype="fp8")
    eng = DecodeEngine(model, max_batch_slots=1, max_seq_len=64,
                       prefill_buckets=(8,), draft=model, spec_k=2)
    ids = np.random.default_rng(0).integers(0, 512, (5,)).astype("int32")
    eng.prefill(ids, slot=0, max_new_tokens=4)
    with pytest.raises(ValueError):
        eng.decode_step(fuse=2)   # spec dispatch already emits K+1 tokens


def test_spec_decode_sampled_deterministic_per_seed(model):
    """Sampled spec decode (residual resampling through the temperature/
    top-k filter) is deterministic per seed and actually varies by seed."""
    ids = np.random.default_rng(5).integers(0, 512, (1, 5)).astype("int32")

    eng = DecodeEngine(model, max_batch_slots=1, max_seq_len=32,
                       prefill_buckets=(8,), draft=_draft_cfg(),
                       spec_k=2, do_sample=True, temperature=0.8, top_k=20)
    a = eng.generate(ids, max_new_tokens=6, seed=9)
    b = eng.generate(ids, max_new_tokens=6, seed=9)
    c = eng.generate(ids, max_new_tokens=6, seed=10)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)


@pytest.mark.slow
def test_spec_decode_scheduler_drains_variable_runs(model):
    """The scheduler's token ledger absorbs variable-length accepted runs:
    continuous-batching output == per-request generate() bitwise, and the
    finished runlog rows carry the spec accounting."""
    eng = DecodeEngine(model, max_batch_slots=2, max_seq_len=64,
                       prefill_buckets=(8, 16), draft=model, spec_k=3)
    base = DecodeEngine(model, max_batch_slots=2, max_seq_len=64,
                        prefill_buckets=(8, 16))
    prompts = _prompts(5)
    want = [base.generate(p[None], max_new_tokens=6)[0, len(p):] for p in prompts]
    sched = ContinuousBatchingScheduler(eng)
    rids = [sched.submit(p, max_new_tokens=6) for p in prompts]
    done = sched.run()
    for i, r in enumerate(rids):
        np.testing.assert_array_equal(np.asarray(done[r].tokens), want[i])


def test_spec_decode_fleet_kill_requeue_bitwise(model):
    """Mid-stream replica kill on a spec-decoding fleet: requeued requests
    finish exactly once, bitwise — a config draft rebuilds from draft_seed
    so the survivor holds identical draft weights."""
    kw = dict(max_batch_slots=2, max_seq_len=64, prefill_buckets=(8, 16),
              draft=_draft_cfg(), spec_k=2, draft_seed=5)
    prompts = _prompts(4)
    ref = DecodeEngine(model, **kw)
    want = [list(ref.generate(p[None], max_new_tokens=6)[0, len(p):])
            for p in prompts]
    with chaos.inject(FLAGS_chaos_replica_kill_at="1:2"):
        fleet = ServingFleet(model, replicas=2, **kw)
        fids = [fleet.submit(p, max_new_tokens=6) for p in prompts]
        done = fleet.run()
    assert sorted(done) == sorted(fids)
    assert fleet.stats()["dead"] == [1]
    for i, f in enumerate(fids):
        assert done[f].status == "finished"
        assert list(done[f].tokens) == want[i], f"request {i} diverged"


# ------------------------------------------------------------- int8 KV cache
def test_kv_quantize_round_trip_tolerance():
    """Per-head abs_max int8 round trip: worst-case quantization step is
    amax/127, so the round-trip error is bounded by half a step per
    element (documented tolerance of the whole int8 KV feature)."""
    from paddle_tpu.models.gpt import _kv_dequant, _kv_quantize

    u = np.random.default_rng(0).normal(size=(2, 4, 16)).astype("float32")
    q, s = _kv_quantize(u)
    assert q.dtype == np.int8 and s.shape == (2, 4)
    back = np.asarray(_kv_dequant({"q": q, "s": s}, "float32"))
    step = np.abs(u).max(-1, keepdims=True) / 127.0
    assert (np.abs(back - u) <= 0.5 * step + 1e-7).all()
    # zero rows survive (the 1e-8 scale floor, no 0/0)
    q0, s0 = _kv_quantize(np.zeros((1, 3, 8), "float32"))
    assert np.asarray(q0).sum() == 0 and np.isfinite(np.asarray(s0)).all()


def test_int8_kv_shrinks_slot_bytes_and_keeps_tokens(model):
    """kv_dtype="int8" stores int8 payload + f32 per-row scales: per-slot
    bytes shrink 4*dh/(dh+4)x (3.2x at head_dim 16, >= the 3x floor) and
    greedy tokens on the tiny model agree with the f32 engine."""
    ids = np.random.default_rng(2).integers(0, 512, (2, 9)).astype("int32")
    f32 = DecodeEngine(model, max_batch_slots=2, max_seq_len=64,
                       prefill_buckets=(16,))
    i8 = DecodeEngine(model, max_batch_slots=2, max_seq_len=64,
                      prefill_buckets=(16,), kv_dtype="int8")
    shrink = f32.kv_bytes_per_slot() / i8.kv_bytes_per_slot()
    assert shrink >= 3.0, shrink
    a = f32.generate(ids, max_new_tokens=10)
    b = i8.generate(ids, max_new_tokens=10)
    # tiny-model greedy argmax is robust to the <0.4% dequant error; the
    # per-logit tolerance itself is pinned in the round-trip test above
    assert (a == b).mean() >= 0.9, (a, b)


def test_int8_kv_chunked_and_prefix_hit_bitwise_family(model):
    """Under int8 KV the serving paths stay a CLOSED family: bucketed ==
    chunked prefill == prefix-cache warm hit, bitwise — the quantized
    representation travels end-to-end (extract/insert move int8 packs, no
    f32 round trip in HBM)."""
    prompt = np.random.default_rng(8).integers(0, 512, (19,)).astype("int32")
    kw = dict(max_batch_slots=1, max_seq_len=64, kv_dtype="int8")
    # ``chunked`` and ``warm`` share their decode program's key, and XLA:CPU cannot run a program it AOT-loads after
    # JIT-compiling an identical one in the same process ("Function wrapped_slice not found"): each engine compiles
    # its own. The family is this test's subject, the store is not (the chip loads such a program: CHANGES PR 21).
    with _no_aot_store():
        bucketed = DecodeEngine(model, prefill_buckets=(32,), **kw)
        want = bucketed.generate(prompt[None], max_new_tokens=8)
        chunked = DecodeEngine(model, prefill_chunk=8, **kw)
        np.testing.assert_array_equal(chunked.generate(prompt[None], max_new_tokens=8), want)
        warm = DecodeEngine(model, prefill_chunk=8, prefix_cache_mb=4.0, **kw)
        cold = warm.generate(prompt[None], max_new_tokens=8)   # populates cache
        np.testing.assert_array_equal(cold, want)
        assert warm.prefix_cache.stats()["entries"] > 0
        hit = warm.generate(prompt[None], max_new_tokens=8)    # warm hit
        np.testing.assert_array_equal(hit, want)
    assert warm.prefix_cache.hits >= 1
    # honest byte accounting: stored entries are the quantized segments
    per_entry = warm.prefix_cache.bytes_used() / len(warm.prefix_cache)
    assert per_entry < warm.prefix_cache.entry_bytes * 1.01


@pytest.mark.slow
def test_spec_plus_int8_bitwise_vs_nonspec_int8(model):
    """Speculation composes with the quantized cache: spec+int8 == plain
    int8 engine bitwise (speculation never changes tokens, whatever the
    cache representation underneath)."""
    ids = np.random.default_rng(6).integers(0, 512, (2, 7)).astype("int32")
    plain = DecodeEngine(model, max_batch_slots=2, max_seq_len=64,
                         prefill_buckets=(8,), kv_dtype="int8")
    want = plain.generate(ids, max_new_tokens=10)
    spec = DecodeEngine(model, max_batch_slots=2, max_seq_len=64,
                        prefill_buckets=(8,), kv_dtype="int8",
                        draft=model, spec_k=3)
    np.testing.assert_array_equal(spec.generate(ids, max_new_tokens=10), want)


def test_quantized_fixed_cache_layer_parity():
    """The dygraph serving cache mirrors the engine feature:
    gen_cache(static=True, kv_dtype="int8") decodes within the documented
    dequant tolerance of the f32 FixedCache at constant int8 shapes."""
    from paddle_tpu.models.gpt import GPTBlock
    from paddle_tpu.nn.layer.transformer import MultiHeadAttention

    cfg = GPTConfig.tiny()
    blk = GPTBlock(cfg)
    blk.eval()
    x = paddle.to_tensor(np.random.default_rng(5).normal(
        size=(2, 6, cfg.hidden_size)).astype("float32"))
    full = blk(x).numpy()
    cache = blk.gen_cache(x, static=True, max_seq=16, kv_dtype="int8")
    assert isinstance(cache, MultiHeadAttention.QuantizedFixedCache)
    outs, shapes = [], set()
    for t in range(6):
        o, cache = blk(x[:, t:t + 1], cache=cache)
        outs.append(o.numpy())
        shapes.add((tuple(cache.qk.shape), str(cache.qk.dtype).split(".")[-1]))
    dh = cfg.hidden_size // cfg.num_heads
    assert shapes == {((2, 16, cfg.num_heads, dh), "int8")}
    assert int(cache.pos.numpy()) == 6
    got = np.concatenate(outs, axis=1)
    np.testing.assert_allclose(got, full, rtol=0.02, atol=0.02)
    with pytest.raises(ValueError):
        blk.gen_cache(x, static=True, max_seq=16, kv_dtype="fp8")


def test_spec_decode_sanitize_serve_smoke(model):
    """FLAGS_sanitize=1 serve smoke with spec decode on: the runtime
    sanitizer watches the spec dispatch stream without tripping."""
    from paddle_tpu.analysis import sanitizer

    prev = paddle.get_flags("FLAGS_sanitize")["FLAGS_sanitize"]
    sanitizer.reset()
    paddle.set_flags({"FLAGS_sanitize": True})
    try:
        eng = DecodeEngine(model, max_batch_slots=2, max_seq_len=64,
                           prefill_buckets=(8, 16), draft=model, spec_k=2,
                           kv_dtype="int8")
        sched = ContinuousBatchingScheduler(eng)
        rids = [sched.submit(p, max_new_tokens=5) for p in _prompts(3)]
        done = sched.run()
        assert sorted(done) == sorted(rids)
        assert all(done[r].status == "finished" for r in rids)
    finally:
        paddle.set_flags({"FLAGS_sanitize": prev})
        sanitizer.reset()
