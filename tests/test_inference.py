"""Serving tier: AOT Predictor round trips, backend resolution, int8 path,
static-KV-cache DecodeEngine (exactly 2 compiled programs) and the
continuous-batching scheduler (slot reuse, bucketing, no cross-request
leakage)."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import inference, static
from paddle_tpu.inference import (
    Config,
    ContinuousBatchingScheduler,
    DecodeEngine,
    create_predictor,
    default_buckets,
)
from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mlp():
    return paddle.nn.Sequential(
        paddle.nn.Linear(6, 16), paddle.nn.ReLU(), paddle.nn.Linear(16, 3))


# ---------------------------------------------------------------- predictor
def test_jit_save_predictor_round_trip_bitwise(tmp_path):
    """jit.save → create_predictor outputs BITWISE equal to the live model."""
    paddle.seed(3)
    model = _mlp()
    model.eval()
    x = np.random.default_rng(0).normal(size=(4, 6)).astype("float32")
    want = np.asarray(model(paddle.to_tensor(x)).numpy())
    prefix = str(tmp_path / "mlp")
    paddle.jit.save(model, prefix, input_spec=[static.InputSpec([None, 6], "float32")])
    pred = create_predictor(Config(prefix))
    (got,) = pred.run([x])
    np.testing.assert_array_equal(np.asarray(got), want)
    # AOT path compiled + counted; cost row retained for explain()
    assert len(pred.explain()) == 1
    # staged-handle API agrees with the positional API
    h = pred.get_input_handle(pred.get_input_names()[0])
    h.copy_from_cpu(x)
    assert pred.run() is True
    out_h = pred.get_output_handle(pred.get_output_names()[0])
    np.testing.assert_array_equal(out_h.copy_to_cpu(), want)


def test_static_save_inference_model_round_trip_bitwise(tmp_path):
    """static.save_inference_model → create_predictor == Executor.run."""
    paddle.seed(7)
    model = paddle.nn.Sequential(paddle.nn.Linear(6, 3), paddle.nn.Softmax())
    prog = static.Program()
    with static.program_guard(prog):
        x = static.data("x", [2, 6])
        out = model(x)
    prefix = str(tmp_path / "m" / "model")
    exe = static.Executor()
    static.save_inference_model(prefix, [x], [out], exe, program=prog)
    xv = np.random.default_rng(1).normal(size=(2, 6)).astype("float32")
    (direct,) = exe.run(prog, feed={"x": xv}, fetch_list=[out])
    pred = create_predictor(Config(prefix))
    (got,) = pred.run([xv])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(direct))


def test_predictor_fresh_process_load(tmp_path):
    """The StableHLO artifact loads and serves in a FRESH process (no shared
    jit caches, no live model objects) with identical outputs."""
    paddle.seed(5)
    model = _mlp()
    model.eval()
    x = np.arange(24, dtype="float32").reshape(4, 6) / 24.0
    want = np.asarray(model(paddle.to_tensor(x)).numpy())
    prefix = str(tmp_path / "fresh")
    paddle.jit.save(model, prefix, input_spec=[static.InputSpec([None, 6], "float32")])
    code = (
        "import json, numpy as np\n"
        "from paddle_tpu.inference import Config, create_predictor\n"
        f"pred = create_predictor(Config({prefix!r}))\n"
        "x = np.arange(24, dtype='float32').reshape(4, 6) / 24.0\n"
        "(out,) = pred.run([x])\n"
        "print(json.dumps({'out': np.asarray(out).tolist(),\n"
        "                  'backend': pred.get_resolved_backend()}))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=240, env=env, cwd=ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    payload = json.loads([l for l in r.stdout.splitlines() if l.startswith("{")][-1])
    np.testing.assert_allclose(np.asarray(payload["out"], "float32"), want,
                               rtol=1e-6, atol=1e-6)
    assert payload["backend"] == "cpu"


def test_config_backend_resolution_is_honest():
    """enable_use_gpu no longer silently aliases: the request is recorded,
    the RESOLVED backend is what the runtime actually has (cpu in CI), and
    both are surfaced through summary()/Predictor/get_version."""
    cfg = Config("whatever")
    assert cfg.requested_device() is None
    cfg.enable_use_gpu()
    assert cfg.requested_device() == "gpu"
    assert cfg.use_gpu()
    assert cfg.resolved_backend() == "cpu"  # CI runs on the CPU platform
    s = cfg.summary()
    assert "requested device" in s and "gpu" in s
    assert "resolved backend" in s and "cpu" in s
    assert "accelerator alias" in s  # the lie is now a recorded note
    cfg.disable_gpu()
    assert cfg.resolved_backend() == "cpu" and not cfg.use_gpu()
    v = inference.get_version()
    assert "jax" in v and "default_backend=" in v


def test_predictor_reports_resolved_backend(tmp_path):
    paddle.seed(1)
    model = _mlp()
    prefix = str(tmp_path / "be")
    paddle.jit.save(model, prefix, input_spec=[static.InputSpec([2, 6], "float32")])
    cfg = Config(prefix)
    cfg.enable_use_gpu()  # accepted — and resolved honestly
    pred = create_predictor(cfg)
    assert pred.backend == "cpu"
    assert pred.get_resolved_backend() == "cpu"


def test_int8_ptq_predictor_within_tolerance(tmp_path):
    """PTQ calibrate → int8 artifact → Predictor: outputs track the f32
    model within int8 tolerance, and the served weights really are int8."""
    from paddle_tpu.quantization import PostTrainingQuantization

    paddle.seed(11)
    model = _mlp()
    model.eval()
    rng = np.random.default_rng(2)
    calib = [paddle.to_tensor(rng.normal(size=(8, 6)).astype("float32"))
             for _ in range(4)]
    x = rng.normal(size=(4, 6)).astype("float32")
    want = np.asarray(model(paddle.to_tensor(x)).numpy())
    ptq = PostTrainingQuantization(model=model, data_loader=[(c,) for c in calib],
                                  batch_nums=4)
    q = ptq.quantize()
    sd = q.state_dict()
    int8_keys = [k for k in sd if k.endswith("weight_int8")]
    assert int8_keys and all(
        np.asarray(sd[k].numpy()).dtype == np.int8 for k in int8_keys)
    prefix = str(tmp_path / "int8")
    ptq.save_quantized_model(prefix, input_spec=[static.InputSpec([None, 6], "float32")])
    pred = create_predictor(Config(prefix))
    (got,) = pred.run([x])
    # int8 weight error budget: scale = amax/127 per output channel
    np.testing.assert_allclose(np.asarray(got), want, rtol=0.1, atol=0.12)
    assert np.abs(np.asarray(got) - want).mean() < 0.05


def test_predictor_generate_serves_decoder_artifact(tmp_path):
    """export_decoder → Predictor.generate (the run()-level decoder plumbing
    with prompt_len validation)."""
    paddle.seed(13)
    cfg = GPTConfig.tiny()
    m = GPTForPretraining(cfg)
    ids = np.random.default_rng(4).integers(0, cfg.vocab_size, (2, 8)).astype("int32")
    want = np.asarray(m.generate(paddle.to_tensor(ids), max_new_tokens=4).numpy())
    prefix = str(tmp_path / "dec")
    m.export_decoder(prefix, prompt_len=8, max_new_tokens=4)
    pred = create_predictor(Config(prefix))
    np.testing.assert_array_equal(pred.generate(ids), want)
    with pytest.raises(ValueError):
        pred.generate(ids[:, :5])  # wrong prompt_len must not silently pad


# ------------------------------------------------------------------- engine
def test_engine_exactly_two_compiles_for_n_tokens():
    """THE serving-hot-path pin: decoding N tokens compiles exactly 2
    programs (one bucketed prefill + ONE decode step), asserted via the
    infer.* dispatch counters; tokens match the single-program generate()."""
    from paddle_tpu import profiler

    paddle.seed(21)
    cfg = GPTConfig.tiny()
    m = GPTForPretraining(cfg)
    m.eval()
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 7)).astype("int32")
    want = np.asarray(m.generate(paddle.to_tensor(ids), max_new_tokens=10).numpy())
    profiler.reset_counters("infer.")
    eng = DecodeEngine(m, max_batch_slots=2, max_seq_len=64, prefill_buckets=(8, 16))
    got = eng.generate(ids, max_new_tokens=10)
    np.testing.assert_array_equal(got, want)
    counts = profiler.counters("infer.")
    assert counts["infer.compiles"] == 2, counts
    assert counts["infer.decode_dispatches"] == 9  # prefill emits token #1
    # keep decoding: the SAME two programs serve new requests, no recompile
    eng.generate(ids[:, :5], max_new_tokens=6)
    assert profiler.counters("infer.")["infer.compiles"] == 2


def test_engine_donated_cache_stays_flat():
    """The cache buffers are donated into both programs: decode keeps
    updating in place and state shapes never grow (static [L,B,H,S,dh])."""
    paddle.seed(22)
    m = GPTForPretraining(GPTConfig.tiny())
    m.eval()
    eng = DecodeEngine(m, max_batch_slots=2, max_seq_len=32, prefill_buckets=(8,))
    shape0 = tuple(eng._ck.shape)
    eng.generate(np.arange(6, dtype="int32")[None], max_new_tokens=8)
    assert tuple(eng._ck.shape) == shape0 == tuple(eng._shape)
    assert eng.cache_bytes() == 2 * np.prod(shape0) * 4


def test_engine_int8_weight_path():
    """int8=True quantizes the trunk matmul stacks (per-layer×per-channel
    abs_max) and still decodes: greedy tokens within quantization drift of
    the f32 engine (tiny random model: usually identical)."""
    paddle.seed(23)
    m = GPTForPretraining(GPTConfig.tiny())
    m.eval()
    ids = np.random.default_rng(3).integers(0, 512, (1, 6)).astype("int32")
    f32 = DecodeEngine(m, max_batch_slots=1, max_seq_len=32, prefill_buckets=(8,))
    i8 = DecodeEngine(m, max_batch_slots=1, max_seq_len=32, prefill_buckets=(8,), int8=True)
    quantized = [e for e in i8._params["stack"] if isinstance(e, dict)]
    assert len(quantized) == 4  # qkv/out/ffn1/ffn2
    assert all(np.asarray(e["q"]).dtype == np.int8 for e in quantized)
    a = f32.generate(ids, max_new_tokens=8)
    b = i8.generate(ids, max_new_tokens=8)
    assert a.shape == b.shape
    assert (a[0] == b[0]).mean() > 0.5  # int8 tracks f32 decode closely


def test_engine_sampling_deterministic_per_seed():
    paddle.seed(24)
    m = GPTForPretraining(GPTConfig.tiny())
    m.eval()
    ids = np.random.default_rng(5).integers(0, 512, (1, 5)).astype("int32")
    eng = DecodeEngine(m, max_batch_slots=1, max_seq_len=32, prefill_buckets=(8,),
                       do_sample=True, temperature=0.8, top_k=20)
    a = eng.generate(ids, max_new_tokens=6, seed=9)
    b = eng.generate(ids, max_new_tokens=6, seed=9)
    c = eng.generate(ids, max_new_tokens=6, seed=10)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)  # different seed, different stream


# ---------------------------------------------------------------- scheduler
def _tiny_engine(m, slots=2):
    return DecodeEngine(m, max_batch_slots=slots, max_seq_len=64,
                        prefill_buckets=(8, 16))


def test_scheduler_slot_reuse_and_bucketing():
    """5 requests over 2 slots: every slot is reused, each prompt pads to
    its bucket, and prefill compiles once per DISTINCT bucket only."""
    from paddle_tpu import profiler

    paddle.seed(31)
    m = GPTForPretraining(GPTConfig.tiny())
    m.eval()
    profiler.reset_counters("infer.")
    sched = ContinuousBatchingScheduler(_tiny_engine(m))
    rng = np.random.default_rng(1)
    lens = (5, 7, 12, 3, 9)
    rids = [sched.submit(rng.integers(0, 512, (n,)).astype("int32"), max_new_tokens=4)
            for n in lens]
    done = sched.run()
    assert sorted(done) == sorted(rids)
    assert all(len(done[r].tokens) == 4 for r in rids)
    assert {done[r].slot for r in rids} == {0, 1}  # both slots reused
    assert [done[r].bucket for r in rids] == [8, 8, 16, 8, 16]
    counts = profiler.counters("infer.")
    # 2 distinct buckets + 1 decode step = 3 compiled programs for 5 requests
    assert counts["infer.compiles"] == 3
    assert counts["infer.prefill_dispatches"] == 5


def test_scheduler_no_cross_request_leakage_interleaved():
    """Interleaved admissions (requests join mid-decode of others) produce
    BITWISE the same tokens as each request run alone — per-slot positions
    and slot-masked sampling leak nothing across requests."""
    paddle.seed(32)
    m = GPTForPretraining(GPTConfig.tiny())
    m.eval()
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 512, (n,)).astype("int32") for n in (5, 9, 3, 12, 6)]

    # isolated references, one engine per request
    iso = []
    for p in prompts:
        eng = _tiny_engine(m, slots=1)
        out = eng.generate(p[None], max_new_tokens=5)
        iso.append(out[0, len(p):].tolist())

    # interleaved: submit mid-flight, two slots, staggered admissions
    sched = ContinuousBatchingScheduler(_tiny_engine(m))
    r0 = sched.submit(prompts[0], max_new_tokens=5)
    r1 = sched.submit(prompts[1], max_new_tokens=5)
    sched.step()  # both admitted, one token each
    r2 = sched.submit(prompts[2], max_new_tokens=5)  # queued mid-decode
    sched.step()
    r3 = sched.submit(prompts[3], max_new_tokens=5)
    r4 = sched.submit(prompts[4], max_new_tokens=5)
    done = sched.run()
    got = [done[r].tokens for r in (r0, r1, r2, r3, r4)]
    assert got == iso


def test_scheduler_request_events_and_validation(tmp_path):
    """The request lifecycle rides the run log (submitted → admitted →
    finished with timings) and the report CLI renders a serving section."""
    from paddle_tpu.observability import monitor, runlog
    from paddle_tpu.observability.__main__ import analyze

    paddle.seed(33)
    m = GPTForPretraining(GPTConfig.tiny())
    m.eval()
    monitor().clear()
    sched = ContinuousBatchingScheduler(_tiny_engine(m))
    with pytest.raises(ValueError):
        sched.submit(np.zeros(60, "int32"), max_new_tokens=10)  # > max_seq
    rng = np.random.default_rng(2)
    for n in (4, 11):
        sched.submit(rng.integers(0, 512, (n,)).astype("int32"), max_new_tokens=3)
    done = sched.run()
    evs = monitor().events("request")
    statuses = [(e["id"], e["status"]) for e in evs]
    for rid in done:
        for st in ("submitted", "admitted", "finished"):
            assert (rid, st) in statuses
    fin = [e for e in evs if e["status"] == "finished"]
    assert all(isinstance(e["total_seconds"], float) for e in fin)
    assert all(e["new_tokens"] == 3 for e in fin)
    a = analyze(monitor().events())
    sv = a["serving"]
    assert sv["finished"] == 2 and sv["submitted"] == 2
    assert sv["latency"]["p50_seconds"] > 0
    assert set(sv["phase_split_seconds"]) == {"queue", "prefill", "decode"}


def test_scheduler_eos_and_early_finish():
    """A request whose sampled token hits eos frees its slot early; a
    max_new_tokens=1 request finishes at prefill (never occupies a slot)."""
    paddle.seed(34)
    m = GPTForPretraining(GPTConfig.tiny())
    m.eval()
    eng = _tiny_engine(m)
    ids = np.random.default_rng(0).integers(0, 512, (4,)).astype("int32")
    # find the greedy first token, then use it as eos for the real run
    probe = ContinuousBatchingScheduler(eng)
    rid = probe.submit(ids, max_new_tokens=1)
    done = probe.run()
    first = done[rid].tokens[0]
    assert done[rid].slot is not None and not probe.running  # freed at prefill

    sched = ContinuousBatchingScheduler(eng)
    rid2 = sched.submit(ids, max_new_tokens=8, eos_token_id=int(first))
    done2 = sched.run()
    assert done2[rid2].tokens == [first]  # stopped at eos immediately


# ------------------------------------------------- serving hot path round 2
def test_engine_fused_decode_bitwise_and_dispatch_pin():
    """decode_step(fuse=D) runs D iterations in ONE donated scan dispatch:
    tokens BITWISE equal to the per-token path at every depth, and the
    CI-pinned dispatch counter shows <= ceil(N/D)+1 decode dispatches for N
    generated tokens (the per-step host sync + dispatch amortized by D)."""
    from paddle_tpu import profiler

    paddle.seed(41)
    m = GPTForPretraining(GPTConfig.tiny())
    m.eval()
    ids = np.random.default_rng(11).integers(0, 512, (3, 9)).astype("int32")
    base = DecodeEngine(m, max_batch_slots=3, max_seq_len=64, prefill_buckets=(16,))
    want = base.generate(ids, max_new_tokens=16)
    for depth in (2, 4, 7):
        profiler.reset_counters("infer.")
        eng = DecodeEngine(m, max_batch_slots=3, max_seq_len=64,
                           prefill_buckets=(16,), fuse=depth)
        got = eng.generate(ids, max_new_tokens=16)
        np.testing.assert_array_equal(got, want)
        counts = profiler.counters("infer.")
        assert counts["infer.decode_dispatches"] <= -(-16 // depth) + 1, (depth, counts)
        # one prefill + ONE fused decode program, regardless of depth
        assert counts["infer.compiles"] == 2, (depth, counts)


def test_engine_chunked_prefill_bitwise_and_compile_family():
    """Chunked prefill collapses the per-bucket compile family into chunk +
    final-chunk programs (plus the decode program) for ALL prompt lengths,
    with tokens bitwise equal to the bucketed path."""
    from paddle_tpu import profiler

    paddle.seed(42)
    m = GPTForPretraining(GPTConfig.tiny())
    m.eval()
    rng = np.random.default_rng(12)
    prompts = [rng.integers(0, 512, (n,)).astype("int32") for n in (5, 8, 13, 20, 31)]
    base = DecodeEngine(m, max_batch_slots=1, max_seq_len=64,
                        prefill_buckets=(8, 16, 32))
    want = [base.generate(p[None], max_new_tokens=6)[0] for p in prompts]
    profiler.reset_counters("infer.")
    eng = DecodeEngine(m, max_batch_slots=1, max_seq_len=64, prefill_chunk=8)
    got = [eng.generate(p[None], max_new_tokens=6)[0] for p in prompts]
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    counts = profiler.counters("infer.")
    # chunk + final-chunk + decode: 3 programs serve every prompt length
    # (the bucketed family above took one prefill compile PER bucket)
    assert counts["infer.compiles"] == 3, counts
    assert counts["infer.prefill_chunk_dispatches"] > len(prompts)  # multi-chunk prompts


def test_engine_prefix_cache_reuse_bitwise_and_eviction():
    """A request whose prompt prefix matches cached chunks skips their
    prefill entirely (insert dispatches only), produces BITWISE identical
    tokens, and the LRU byte budget bounds device memory."""
    from paddle_tpu import profiler
    from paddle_tpu.inference.prefix_cache import PrefixCache

    paddle.seed(43)
    m = GPTForPretraining(GPTConfig.tiny())
    m.eval()
    rng = np.random.default_rng(13)
    shared = rng.integers(0, 512, (16,)).astype("int32")
    tails = [rng.integers(0, 512, (5,)).astype("int32") for _ in range(2)]
    prompts = [np.concatenate([shared, t]) for t in tails]

    cold = DecodeEngine(m, max_batch_slots=1, max_seq_len=64, prefill_chunk=8)
    want = [cold.generate(p[None], max_new_tokens=5)[0] for p in prompts]

    profiler.reset_counters("infer.")
    profiler.reset_counters("serving.")
    eng = DecodeEngine(m, max_batch_slots=1, max_seq_len=64, prefill_chunk=8,
                       prefix_cache_mb=4.0)
    got0 = eng.generate(prompts[0][None], max_new_tokens=5)[0]
    chunks_cold = profiler.counters("infer.")["infer.prefill_chunk_dispatches"]
    got1 = eng.generate(prompts[1][None], max_new_tokens=5)[0]
    chunks_warm = (profiler.counters("infer.")["infer.prefill_chunk_dispatches"]
                   - chunks_cold)
    np.testing.assert_array_equal(got0, want[0])
    np.testing.assert_array_equal(got1, want[1])
    assert chunks_warm < chunks_cold  # shared 16-token prefix not re-prefilled
    counts = profiler.counters("serving.")
    assert counts["serving.prefix_hits"] >= 1
    assert counts["serving.prefix_tokens_reused"] >= 16
    assert profiler.counters("infer.")["infer.prefix_insert_dispatches"] >= 2
    assert eng.prefix_cache.bytes_used() <= eng.prefix_cache.budget_bytes

    # LRU eviction: a 3-entry budget holds max 3 chunks, oldest evicted
    pc = PrefixCache(chunk=4, budget_bytes=3 * 100, entry_bytes=100)
    toks = np.arange(32, dtype=np.int32)
    for i in range(5):
        pc.put(pc.key(toks, i), f"k{i}", f"v{i}")
    assert len(pc) == 3 and pc.evictions == 2
    assert not pc.has(pc.key(toks, 0))  # oldest chain dropped
    assert pc.match(toks, max_tokens=32) == []  # chain broken at chunk 0
    assert pc.stats()["bytes_used"] == 300


def test_scheduler_chunked_prefill_interleaves_with_decode():
    """A long admission in chunked mode runs one chunk per tick, and the
    already-decoding request keeps emitting tokens BETWEEN those chunk
    dispatches — prefill no longer stalls the stream. Tokens stay bitwise
    equal to isolated runs; what the chunks cost the short request is in its
    own longest token gap."""
    paddle.seed(44)
    m = GPTForPretraining(GPTConfig.tiny())
    m.eval()
    rng = np.random.default_rng(14)
    short = rng.integers(0, 512, (6,)).astype("int32")
    long = rng.integers(0, 512, (40,)).astype("int32")  # 5 chunks of 8

    def mk():
        return DecodeEngine(m, max_batch_slots=2, max_seq_len=64, prefill_chunk=8)

    iso_short = mk().generate(short[None], max_new_tokens=10)[0, 6:].tolist()
    iso_long = mk().generate(long[None], max_new_tokens=6)[0, 40:].tolist()

    sched = ContinuousBatchingScheduler(mk())
    r_short = sched.submit(short, max_new_tokens=10)
    sched.step()  # short admitted (single final chunk) + first decode
    t_long = time.perf_counter_ns()
    r_long = sched.submit(long, max_new_tokens=6)
    progress = []
    while sched.prefilling or sched.queue:
        sched.step()
        req = sched.running.get(0) or next(iter(sched.running.values()), None)
        if req is not None and req.rid == r_short:
            progress.append(len(req.tokens))
    done = sched.run()
    assert done[r_short].tokens == iso_short
    assert done[r_long].tokens == iso_long
    # the short request gained tokens across >=2 ticks of the long prefill
    assert len(progress) >= 2 and progress[-1] > progress[0]
    assert done[r_long].prefill_chunks >= 5
    # the short request's longest gap between two tokens spans the long admission's chunk: while the long prompt was
    # prefilling only the short request decoded, so the gaps that the ticks' span records keep with one chunk inside
    # are its own — each ended by a pull (the end of ``infer.decode_sync``) of a call that began after the gap did
    from paddle_tpu.observability import spans

    recs = [s for s in spans.recent(since_ns=t_long) if s.end_ns <= done[r_short].finished_ts * 1e9]
    step_of = {s.parent_id: s for s in recs if s.name == "infer.decode_step"}
    pulled = {s.parent_id: s for s in recs if s.name == "infer.decode_sync"}
    held = [(gap, s) for s in recs if s.name == "infer.sched.step" for gap, n, inside in s.attrs["gaps"] if inside == 1]
    assert len(held) >= 3 and not any(inside > 1 for s in recs if s.name == "infer.sched.step" for _, _, inside in s.attrs["gaps"])
    assert all(gap >= pulled[step_of[s.span_id].span_id].end_ns - step_of[s.span_id].start_ns > 0 for gap, s in held)
    assert done[r_short].max_gap_ns >= max(gap for gap, _ in held)
    assert done[r_short].max_gap_seconds < done[r_short].decode_seconds and done[r_long].max_gap_seconds > 0


def test_scheduler_fused_decode_drains_token_stacks():
    """The scheduler drains [D, B] fused token stacks in order: outputs
    bitwise equal to the unfused scheduler, fewer decode dispatches, and
    the report surfaces fuse depth + token gap + prefix-hit rate."""
    from paddle_tpu import profiler
    from paddle_tpu.observability import monitor
    from paddle_tpu.observability.__main__ import analyze

    paddle.seed(45)
    m = GPTForPretraining(GPTConfig.tiny())
    m.eval()
    rng = np.random.default_rng(15)
    prompts = [rng.integers(0, 512, (n,)).astype("int32") for n in (5, 9, 14)]

    def serve(**kw):
        eng = DecodeEngine(m, max_batch_slots=2, max_seq_len=64, **kw)
        sched = ContinuousBatchingScheduler(eng)
        rids = [sched.submit(p, max_new_tokens=7) for p in prompts]
        done = sched.run()
        return [done[r].tokens for r in rids]

    want = serve(prefill_buckets=(16,))
    profiler.reset_counters("infer.")
    monitor().clear()
    got = serve(prefill_chunk=8, prefix_cache_mb=2.0, fuse=3)
    assert got == want
    counts = profiler.counters("infer.")
    # 3 requests x 7 tokens at depth 3 across 2 slots: far fewer dispatches
    # than the 18 per-token steps the unfused path would take
    assert counts["infer.decode_dispatches"] <= 10, counts
    sv = analyze(monitor().events())["serving"]
    assert sv["fuse_depths"] == [3]
    # three tokens a pull: the gaps inside a stack are 0, the gap between two pulls is not
    assert 0 < sv["token_gap"]["longest_p50_seconds"] <= sv["token_gap"]["longest_p95_seconds"] <= sv["token_gap"]["longest_max_seconds"]
    assert sv["prefix_cache"]["hit_rate"] >= 0.0


def test_engine_aot_disk_cache_restart(tmp_path):
    """With FLAGS_compile_cache_dir set, serving executables serialize to
    disk and a RESTARTED engine (same specialization) loads them instead of
    compiling — 0 compiles, bitwise tokens. A different specialization
    misses and compiles normally."""
    from paddle_tpu import profiler

    paddle.seed(46)
    m = GPTForPretraining(GPTConfig.tiny())
    m.eval()
    ids = np.random.default_rng(16).integers(0, 512, (2, 9)).astype("int32")
    paddle.set_flags({"FLAGS_compile_cache_dir": str(tmp_path)})
    try:
        spec = dict(max_batch_slots=2, max_seq_len=64, prefill_chunk=8, fuse=2)
        profiler.reset_counters("infer.")
        warm = DecodeEngine(m, **spec)
        want = warm.generate(ids, max_new_tokens=8)
        c = profiler.counters("infer.")
        assert c["infer.compiles"] >= 3 and c["infer.aot_cache_stores"] >= 3
        assert any((tmp_path / "serving").glob("*.aotc"))

        profiler.reset_counters("infer.")
        restarted = DecodeEngine(m, **spec)  # fresh engine == restarted process
        got = restarted.generate(ids, max_new_tokens=8)
        np.testing.assert_array_equal(got, want)
        c = profiler.counters("infer.")
        assert c["infer.compiles"] == 0, c
        assert c["infer.aot_cache_hits"] >= 3
        assert [s["from_disk_cache"] for s in restarted.explain()]

        # a different fuse depth is a different specialization: cache miss
        profiler.reset_counters("infer.")
        other = DecodeEngine(m, max_batch_slots=2, max_seq_len=64,
                             prefill_chunk=8, fuse=4)
        other.generate(ids, max_new_tokens=8)
        assert profiler.counters("infer.")["infer.compiles"] >= 1
    finally:
        paddle.set_flags({"FLAGS_compile_cache_dir": ""})


def test_default_buckets_and_bucket_for():
    assert default_buckets(128, start=16) == (16, 32, 64, 128)
    paddle.seed(35)
    m = GPTForPretraining(GPTConfig.tiny())
    eng = DecodeEngine(m, max_batch_slots=1, max_seq_len=64, prefill_buckets=(8, 32))
    assert eng.bucket_for(3) == 8 and eng.bucket_for(8) == 8 and eng.bucket_for(9) == 32
    with pytest.raises(ValueError):
        eng.bucket_for(33)
    with pytest.raises(ValueError):
        DecodeEngine(m, max_batch_slots=1, max_seq_len=16, prefill_buckets=(32,))
