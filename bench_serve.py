"""Serving benchmark: continuous-batching GPT decode on one chip.

Prints ONE JSON line on the bench.py schema: {"metric", "value", "unit",
"vs_baseline", ...}. Measurements:

1. **decode tokens/sec** through the static-KV-cache DecodeEngine at the
   round-2 hot path (chunked prefill + fused multi-token decode, donated
   cache buffers) vs the same engine unfused and vs the legacy
   growing-concat eager cache decode — ``decode_speedup`` is the
   engine-vs-concat ratio, ``fuse_speedup`` the fused-vs-unfused ratio,
   and ``decode_dispatches_per_token`` the dispatch amortization the fused
   scan buys (≈1/D);
2. **requests/sec + latency p50/p99 + TTFT + prefill stall** from a
   continuous-batching run: R requests with mixed prompt lengths sharing a
   system-prompt prefix, admitted into B slots in flight, served twice —
   once on the PR-6 path (bucketed prefill, per-token decode) and once on
   the round-2 path (chunked prefill, prefix-cache reuse, fused decode) —
   so the ``*_prev`` fields and ratios are measured in the same process;
3. **time_to_first_token** cold (build + compile family + first prefill)
   and **restart_ttft**: the same engine spec rebuilt against a warm
   ``FLAGS_compile_cache_dir`` AOT executable cache, where the compile
   family loads from disk instead of recompiling;
4. **fleet phase** (own ``BENCH_BUDGET_FLEET`` budget, own subprocess, same
   graceful-degradation contract): a ≥2-replica ServingFleet serving the
   shared-prefix request set — aggregate ``requests_per_sec`` fault-free,
   ``p99_under_kill_ms`` with ``FLAGS_chaos_replica_kill_at`` firing
   mid-stream (every request still finishes exactly once, bitwise — the
   run asserts it), ``scaleout_ttft_ms``: time-to-first-token on a
   replica scaled out against the warm AOT cache (``compiles == 0``),
   and ``trace_overhead_pct``: the same warm fleet run timed with
   ``FLAGS_trace`` off vs the full tracing plane writing span events to
   a run-log dir (< 2% budget) — the on-arm's merged chrome trace is
   written next to the run logs and reported as ``trace_artifact``;
5. **procfleet phase** (own ``BENCH_BUDGET_PROCFLEET`` budget, own
   subprocess): the cross-process ProcServingFleet — subprocess replicas
   behind the store-RPC transport — vs the in-process fleet on the same
   request set (``requests_per_sec`` / ``requests_per_sec_inproc`` /
   ``transport_overhead_pct``), ``p99_under_sigkill_ms`` with
   ``FLAGS_chaos_replica_sigkill_at`` delivering a real ``kill -9`` to one
   replica mid-stream (bitwise exactly-once asserted), streaming
   ``stream_ttft_p50_ms`` (first token chunk across the process boundary),
   and ``child_compiles`` pinning the warm AOT boot (0 == no recompiles);
6. **spec phase** (own ``BENCH_BUDGET_SPEC`` budget, own subprocess): the
   round-3 raw-speed pair — speculative decoding
   (``spec_decode_tokens_per_sec`` at the oracle-draft acceptance ceiling
   and with a genuinely small draft, ``spec_acceptance_rate``,
   ``decode_dispatches_per_token``; both arms assert bitwise parity with
   the plain engine) and the int8 KV cache (``kv_bytes_per_slot`` int8 vs
   f32, the shrink ratio, and ``max_concurrent_slots`` under a notional
   64 MiB KV budget — the concurrency the quantization buys);
7. **alerts phase** (own ``BENCH_BUDGET_ALERTS`` budget, own subprocess):
   the observability round-3 alerting arm — a TTFT SLO with sub-second
   burn windows over a live fleet, a chaos latency spike
   (``FLAGS_chaos_replica_slow_ms``), and the judgment layer's reaction
   time: ``alert_detection_ms`` (chaos start → page alert firing, within
   the fast window), ``alert_firing_ms`` (page → cleared once the spike
   ages out of the windows under recovery traffic), and
   ``slo_eval_overhead_pct`` — the monitor's evaluation cost over the
   serving run's wall time at a 50ms cadence (< 2% budget);
8. **ingress phase** (own ``BENCH_BUDGET_INGRESS`` budget, own
   subprocess): the round-4 HTTP front door + socket fast path —
   ``ingress_requests_per_sec`` through ``ServingIngress`` vs the same
   fleet driven in-process (``requests_per_sec_inproc``),
   ``socket_vs_store_overhead_pct``: the socket-transport fleet's wall
   time vs the identical workload on the store-poll transport
   (negative == the fast path is faster), ``stream_ttft_p50_ms`` over
   HTTP chunked streaming, ``disconnect_cancel_ms`` (client socket
   dropped mid-stream → mid-decode cancel observed),
   ``drain_under_load_ms`` (SIGTERM-style drain with requests in flight:
   rc 0, every accepted request finished), and the end-to-end chaos pin:
   replica ``kill -9`` mid-decode UNDER the ingress with streams open —
   every HTTP stream completes bitwise-identical to the unkilled
   reference (``exactly_once_under_sigkill``).

Like bench.py it runs on whatever device ``JAX_PLATFORMS`` gives it and
names it in ``device``; without a TPU every phase runs a tiny smoke
configuration, says so, and reports under ``gpt_serving_throughput_cpu_smoke``
(host timings, not device metrics). Every phase is its own child process,
one after the other, sharing the one compile cache; a phase that fails or
overruns its budget is recorded and makes the exit code non-zero. A chip
belongs to one process at a time, so the procfleet and ingress phases — whose
replicas are processes — keep their own process off JAX, take the in-process
reference from a child, and never start more replica processes than the host
has chips (with one chip the kill arms, which need a survivor, are skipped
and say so).
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def _percentile(sorted_vals, q):
    if not sorted_vals:
        return None
    idx = (q / 100.0) * (len(sorted_vals) - 1)
    lo = int(idx)
    hi = min(lo + 1, len(sorted_vals) - 1)
    return sorted_vals[lo] + (sorted_vals[hi] - sorted_vals[lo]) * (idx - lo)


def _setup():
    """Prologue of a phase that computes in its own process: the shared
    compile cache, and where it runs. Returns (on_tpu, device record)."""
    import paddle_tpu as paddle
    from paddle_tpu.framework.flags import ensure_compile_cache

    ensure_compile_cache()
    device = paddle.device.describe()
    on_tpu = paddle.device.is_tpu()
    if not on_tpu:
        print(f"bench_serve: no TPU (platform={device['platform']}): tiny smoke configuration, "
              "host timings — not device metrics", file=sys.stderr)
    return on_tpu, device


def _setup_off_jax(what):
    """Prologue of a phase whose replicas are child processes: this process
    must leave the chip to them, so it asks the PCI bus, not JAX. Returns
    (on_tpu, how many replica processes the host can place: 2, or 1 on a
    one-chip host)."""
    import paddle_tpu as paddle
    from paddle_tpu.framework.flags import ensure_compile_cache

    ensure_compile_cache()
    on_tpu = paddle.device.place_on_chips(1, what)
    return on_tpu, (min(2, paddle.device.local_tpu_chips()) if on_tpu else 2)


def _measure():
    import paddle_tpu as paddle
    from paddle_tpu import profiler
    from paddle_tpu.inference import ContinuousBatchingScheduler, DecodeEngine
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining

    t_start = time.perf_counter()
    on_tpu, device = _setup()
    if on_tpu:
        cfg = GPTConfig(vocab_size=50304, hidden_size=1024, num_layers=16,
                        num_heads=16, max_seq_len=1024)
        slots, max_seq, max_new, n_requests, decode_tokens = 8, 1024, 64, 32, 128
        buckets = (64, 128, 256, 512)
        fuse, chunk, prefix_mb = 8, 128, 512.0
    else:
        cfg = GPTConfig.tiny()
        slots, max_seq, max_new, n_requests, decode_tokens = 4, 128, 12, 12, 48
        buckets = (8, 16, 32, 64)
        fuse, chunk, prefix_mb = 4, 16, 16.0

    paddle.seed(0)
    model = GPTForPretraining(cfg)
    model.eval()
    rng = np.random.default_rng(0)

    # --- engine decode throughput (round-2: chunked prefill + fused scan) --
    profiler.reset_counters("infer.")
    engine = DecodeEngine(model, max_batch_slots=slots, max_seq_len=max_seq,
                          prefill_chunk=chunk, fuse=fuse)
    prompt = rng.integers(0, cfg.vocab_size, (slots, chunk // 2)).astype("int32")
    engine.generate(prompt, max_new_tokens=2)  # compiles prefill-final + fused decode
    ttft_cold = time.perf_counter() - t_start
    compiles = int(profiler.counters("infer.").get("infer.compiles", 0))
    engine.generate(prompt, max_new_tokens=2)            # warm the fused path
    engine.generate(prompt, max_new_tokens=2, fuse=1)    # warm the unfused program
    profiler.reset_counters("infer.")
    t0 = time.perf_counter()
    out = engine.generate(prompt, max_new_tokens=decode_tokens)
    dt_engine = time.perf_counter() - t0
    engine_tps = slots * decode_tokens / dt_engine
    c = profiler.counters("infer.")
    decode_dispatches = int(c.get("infer.decode_dispatches", 0))
    dispatches_per_token = decode_dispatches / max(1, slots * decode_tokens)
    assert out.shape == (slots, prompt.shape[1] + decode_tokens)
    t0 = time.perf_counter()
    engine.generate(prompt, max_new_tokens=decode_tokens, fuse=1)
    dt_unfused = time.perf_counter() - t0
    unfused_tps = slots * decode_tokens / dt_unfused

    # --- growing-concat baseline (the legacy eager cache= decode path) ---
    from paddle_tpu.models.gpt import GPTBlock

    concat_tokens = max(8, decode_tokens // 4)  # eager is slow; scale count
    blocks = [GPTBlock(cfg) for _ in range(cfg.num_layers)]
    for b in blocks:
        b.eval()
    emb = model.gpt.embeddings
    x = paddle.to_tensor(prompt[:, :1])

    def concat_decode(n_tokens):
        caches = [b.gen_cache(emb(x)) for b in blocks]
        h = emb(x)
        for _ in range(n_tokens):
            for i, b in enumerate(blocks):
                h, caches[i] = b(h, cache=caches[i])
            h = h[:, -1:].detach()
        return h

    concat_decode(2)  # warm eager dispatch paths
    t0 = time.perf_counter()
    concat_decode(concat_tokens)
    dt_concat = time.perf_counter() - t0
    concat_tps = slots * concat_tokens / dt_concat
    speedup = engine_tps / concat_tps if concat_tps > 0 else None

    # --- continuous batching: PR-6 path vs round-2 path ------------------
    # same request set both rounds: mixed prompt lengths behind one shared
    # system-prompt prefix (2 chunks — what the prefix cache feeds on) with
    # duplicated queries, the serving-traffic shape prefix reuse exists for
    lens = rng.integers(max(1, chunk // 4), chunk, max(1, n_requests // 2))
    shared = rng.integers(0, cfg.vocab_size, (2 * chunk,)).astype("int32")
    tails = [rng.integers(0, cfg.vocab_size, (int(n),)).astype("int32") for n in lens]
    prompts = [np.concatenate([shared, tails[i % len(tails)]])
               for i in range(n_requests)]

    def serve_round(**engine_kwargs):
        eng = DecodeEngine(model, max_batch_slots=slots, max_seq_len=max_seq,
                           **engine_kwargs)
        # warm every program BEFORE any request's latency clock starts —
        # the serving numbers measure dispatch, not compile (compile cost
        # is reported separately as TTFT cold / restart)
        if engine_kwargs.get("prefill_chunk"):
            warm_lens = (engine_kwargs["prefill_chunk"] + 1,)
        else:
            warm_lens = engine_kwargs["prefill_buckets"]
        for blen in warm_lens:
            eng.generate(rng.integers(0, cfg.vocab_size, (1, blen)).astype("int32"),
                         max_new_tokens=2)
        best = None
        for _trial in range(3):  # best-of-3: host scheduling noise dominates
            sched = ContinuousBatchingScheduler(eng)
            for p in prompts:
                sched.submit(p, max_new_tokens=max_new)
            t0 = time.perf_counter()
            done = sched.run()
            dt = time.perf_counter() - t0
            lat = sorted(r.total_seconds for r in done.values())
            ttft = sorted(r.ttft_seconds for r in done.values())
            stalls = sorted(r.stall_seconds for r in done.values())
            trial = {
                "engine": eng,
                "requests": len(done),
                "requests_per_sec": len(done) / dt if dt > 0 else None,
                "latency_p50_ms": _percentile(lat, 50) * 1e3,
                "latency_p99_ms": _percentile(lat, 99) * 1e3,
                "ttft_p50_ms": _percentile(ttft, 50) * 1e3,
                "prefill_stall_ms_p99": _percentile(stalls, 99) * 1e3,
                "tokens_generated": int(sum(len(r.tokens) for r in done.values())),
            }
            if best is None or trial["requests_per_sec"] > best["requests_per_sec"]:
                best = trial
        return best

    prev = serve_round(prefill_buckets=buckets)          # the PR-6 serving path
    cur = serve_round(prefill_chunk=chunk, prefix_cache_mb=prefix_mb, fuse=fuse)
    pstats = cur["engine"].prefix_cache.stats()
    hit_rate = pstats["hits"] / max(1, pstats["hits"] + pstats["misses"])

    # --- restart TTFT: AOT executable store under the compile cache ------
    restart_ttft = None
    aot_hits = 0
    try:
        spec = dict(max_batch_slots=slots, max_seq_len=max_seq,
                    prefill_chunk=chunk, fuse=fuse)
        warm = DecodeEngine(model, **spec)
        warm.generate(prompt[:1], max_new_tokens=2)  # compile + serialize family
        profiler.reset_counters("infer.")
        t0 = time.perf_counter()
        cold = DecodeEngine(model, **spec)           # "restarted" engine
        job = cold.begin_prefill(prompt[0], slot=0, max_new_tokens=2)
        while not cold.prefill_step(job):
            pass
        restart_ttft = time.perf_counter() - t0      # first token, no compiles
        aot_hits = int(profiler.counters("infer.").get("infer.aot_cache_hits", 0))
    except Exception:
        pass

    config_key = (f"{device['kind']}/h{cfg.hidden_size}"
                  f"L{cfg.num_layers}b{slots}s{max_seq}")
    out = {
        "value": round(cur["requests_per_sec"], 3),
        "config": config_key,
        "on_tpu": on_tpu,
        "device": device,
        "requests_per_sec": round(cur["requests_per_sec"], 3),
        "latency_p50_ms": round(cur["latency_p50_ms"], 2),
        "latency_p99_ms": round(cur["latency_p99_ms"], 2),
        "ttft_p50_ms": round(cur["ttft_p50_ms"], 2),
        "ttft_p50_ms_prev": round(prev["ttft_p50_ms"], 2),
        "prefill_stall_ms_p99": round(cur["prefill_stall_ms_p99"], 3),
        "requests": cur["requests"],
        "tokens_generated": cur["tokens_generated"],
        "requests_per_sec_prev": round(prev["requests_per_sec"], 3),
        "latency_p50_ms_prev": round(prev["latency_p50_ms"], 2),
        "decode_tokens_per_sec": round(engine_tps, 1),
        "decode_tokens_per_sec_unfused": round(unfused_tps, 1),
        "decode_tokens_per_sec_concat": round(concat_tps, 1),
        "decode_speedup": round(speedup, 2) if speedup else None,
        "fuse_speedup": round(engine_tps / unfused_tps, 2) if unfused_tps else None,
        "fuse": fuse,
        "prefill_chunk": chunk,
        "decode_dispatches_per_token": round(dispatches_per_token, 4),
        "prefix_cache_hit_rate": round(hit_rate, 3),
        "prefix_tokens_reused": int(profiler.counters("serving.").get(
            "serving.prefix_tokens_reused", 0)),
        "decode_compiles": compiles,
        "time_to_first_token_cold": round(ttft_cold, 3),
        "restart_ttft": round(restart_ttft, 3) if restart_ttft is not None else None,
        "restart_aot_cache_hits": aot_hits,
    }
    return out


def _measure_fleet():
    """The serving-fleet phase: throughput, p99 under a mid-stream replica
    kill, and scale-out TTFT against the warm AOT cache. Asserts the kill
    run's completions are exactly-once and bitwise-equal to the fault-free
    run — the bench doubles as the fleet's integration check."""
    import paddle_tpu as paddle
    from paddle_tpu import profiler
    from paddle_tpu.inference import ServingFleet
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    from paddle_tpu.testing import chaos

    on_tpu, _ = _setup()
    if on_tpu:
        cfg = GPTConfig(vocab_size=50304, hidden_size=1024, num_layers=16,
                        num_heads=16, max_seq_len=1024)
        slots, max_seq, max_new, n_requests = 8, 1024, 32, 24
        chunk, fuse, prefix_mb, n_replicas = 128, 8, 256.0, 2
    else:
        cfg = GPTConfig.tiny()
        slots, max_seq, max_new, n_requests = 2, 128, 8, 10
        chunk, fuse, prefix_mb, n_replicas = 16, 2, 16.0, 2

    paddle.seed(0)
    model = GPTForPretraining(cfg)
    model.eval()
    rng = np.random.default_rng(0)
    kw = dict(max_batch_slots=slots, max_seq_len=max_seq, prefill_chunk=chunk,
              fuse=fuse, prefix_cache_mb=prefix_mb)
    shared = rng.integers(0, cfg.vocab_size, (2 * chunk,)).astype("int32")
    prompts = [np.concatenate([shared, rng.integers(
        0, cfg.vocab_size, (int(n),)).astype("int32")])
        for n in rng.integers(max(1, chunk // 4), chunk, n_requests)]

    import tempfile

    # --- fault-free throughput (programs warm via the AOT store) ------
    fleet = ServingFleet(model, replicas=n_replicas, **kw)
    fids = [fleet.submit(p, max_new_tokens=max_new, seed=i)
            for i, p in enumerate(prompts)]
    fleet.run()  # warm run: compiles + serializes the family
    want = {i: list(fleet.requests[f].tokens) for i, f in enumerate(fids)}
    fleet = ServingFleet(model, replicas=n_replicas, **kw)
    fids = [fleet.submit(p, max_new_tokens=max_new, seed=i)
            for i, p in enumerate(prompts)]
    t0 = time.perf_counter()
    done = fleet.run()
    dt = time.perf_counter() - t0
    rps = len(done) / dt if dt > 0 else None

    # --- p99 latency with a replica killed mid-stream -----------------
    with chaos.inject(FLAGS_chaos_replica_kill_at=f"{n_replicas - 1}:2"):
        fleet_k = ServingFleet(model, replicas=n_replicas, **kw)
        fids_k = [fleet_k.submit(p, max_new_tokens=max_new, seed=i)
                  for i, p in enumerate(prompts)]
        done_k = fleet_k.run()
    assert len(done_k) == len(prompts), "kill run lost completions"
    for i, f in enumerate(fids_k):
        assert list(done_k[f].tokens) == want[i], \
            f"kill run diverged on request {i}"
    lat = sorted(r.total_seconds for r in done_k.values())
    p99_kill = _percentile(lat, 99)
    stats_k = fleet_k.stats()

    # --- scale-out TTFT at compiles == 0 ------------------------------
    profiler.reset_counters("infer.")
    t0 = time.perf_counter()
    new = fleet.scale_out(1)
    fid = fleet.submit(prompts[0], max_new_tokens=2, seed=0,
                       replica=new[0])
    while fleet.requests[fid].status != "finished":
        fleet.step()
    scaleout_ttft = fleet.requests[fid].first_token_ts - t0
    scaleout_compiles = int(profiler.counters("infer.").get("infer.compiles", 0))

    # --- tracing overhead, measured in-band ---------------------------
    # same warm fleet spec with the run-log disk mirror held constant
    # in BOTH arms (that's pre-existing monitor cost, not tracing
    # cost): FLAGS_trace off (no ids, no span events) vs the full
    # tracing plane.  Arms interleave and each takes min-of-3 so host
    # scheduling noise cancels.  The on-arm's merged chrome trace is
    # kept as the bench artifact.  PR-14 budget: < 2% throughput cost.
    off_dir = tempfile.mkdtemp(prefix="bench_fleet_notrace_")
    trace_dir = tempfile.mkdtemp(prefix="bench_fleet_trace_")
    prev_flags = paddle.get_flags(["FLAGS_trace", "FLAGS_run_log_dir"])

    def _timed_run(trace_on):
        paddle.set_flags({"FLAGS_trace": trace_on,
                          "FLAGS_run_log_dir":
                              trace_dir if trace_on else off_dir})
        fl = ServingFleet(model, replicas=n_replicas, **kw)
        for i, p in enumerate(prompts):
            fl.submit(p, max_new_tokens=max_new, seed=i)
        t0 = time.perf_counter()
        fl.run()
        return time.perf_counter() - t0

    trace_overhead_pct = None
    trace_artifact = None
    trace_events = 0
    try:
        _timed_run(True)  # warm both log files + the trace-id streams
        _timed_run(False)
        t_off, t_on = [], []
        for _ in range(5):  # interleaved min-of-5: host noise on the
            t_off.append(_timed_run(False))  # tiny CPU config is far
            t_on.append(_timed_run(True))    # larger than the signal
        t_off, t_on = min(t_off), min(t_on)
        trace_overhead_pct = (t_on - t_off) / t_off * 100.0 if t_off else None

        from paddle_tpu.observability.__main__ import chrome_trace_doc

        doc = chrome_trace_doc(trace_dir)
        trace_events = len(doc.get("traceEvents", []))
        trace_artifact = os.path.join(trace_dir, "trace.json")
        with open(trace_artifact, "w") as f:
            json.dump(doc, f)
    except Exception:
        trace_artifact = None
    finally:
        paddle.set_flags(prev_flags)

    return {
        "replicas": n_replicas,
        "requests": len(done),
        "requests_per_sec": round(rps, 3) if rps else None,
        "p99_under_kill_ms": round(p99_kill * 1e3, 2),
        "requeues_under_kill": stats_k["requeues"],
        "replica_deaths": len(stats_k["dead"]),
        "scaleout_ttft_ms": round(scaleout_ttft * 1e3, 2),
        "scaleout_compiles": scaleout_compiles,
        "trace_overhead_pct": (round(trace_overhead_pct, 2)
                               if trace_overhead_pct is not None else None),
        "trace_artifact": trace_artifact,
        "trace_events": trace_events,
    }


def _procfleet_workload(on_tpu):
    """The procfleet phase's sizes and prompts — shared by the phase (which
    stays off JAX) and its in-process reference child."""
    from paddle_tpu.models.gpt import GPTConfig

    if on_tpu:
        cfg = GPTConfig(vocab_size=50304, hidden_size=1024, num_layers=16,
                        num_heads=16, max_seq_len=1024)
        slots, max_seq, max_new, n_requests = 8, 1024, 32, 24
        chunk, fuse = 128, 8
    else:
        cfg = GPTConfig.tiny()
        slots, max_seq, max_new, n_requests = 2, 128, 8, 10
        chunk, fuse = 16, 2
    rng = np.random.default_rng(0)
    kw = dict(max_batch_slots=slots, max_seq_len=max_seq, prefill_chunk=chunk,
              fuse=fuse)
    prompts = [rng.integers(0, cfg.vocab_size, (int(n),)).astype("int32")
               for n in rng.integers(max(1, chunk // 4), chunk, n_requests)]
    return cfg, kw, prompts, max_new


def _measure_procfleet_ref():
    """The in-process arm of the procfleet phase, in a child of its own:
    warm the AOT store, pin the reference tokens, then a timed fault-free
    run — the transport-overhead baseline. ``BENCH_REPLICAS`` is the
    replica count the phase settled on."""
    import paddle_tpu as paddle
    from paddle_tpu.inference import ServingFleet
    from paddle_tpu.models.gpt import GPTForPretraining

    on_tpu, device = _setup()
    n_replicas = int(os.environ["BENCH_REPLICAS"])
    cfg, kw, prompts, max_new = _procfleet_workload(on_tpu)
    paddle.seed(0)
    model = GPTForPretraining(cfg)
    model.eval()
    warm = ServingFleet(model, replicas=n_replicas, **kw)
    fids = [warm.submit(p, max_new_tokens=max_new, seed=i)
            for i, p in enumerate(prompts)]
    warm.run()  # compiles + serializes the program family
    want = [[int(t) for t in warm.requests[f].tokens] for f in fids]
    fl = ServingFleet(model, replicas=n_replicas, **kw)
    fids = [fl.submit(p, max_new_tokens=max_new, seed=i)
            for i, p in enumerate(prompts)]
    t0 = time.perf_counter()
    done = fl.run()
    dt_in = time.perf_counter() - t0
    return {"want": want, "device": device,
            "rps": len(done) / dt_in if dt_in > 0 else None,
            "ttft": sorted(r.ttft_seconds for r in done.values()),
            "lat": sorted(r.total_seconds for r in done.values())}


def _measure_procfleet():
    """The cross-process fleet phase: subprocess replicas behind the
    store-RPC transport vs the in-process fleet on the same request set
    (``*_inproc`` fields → transport overhead), p99 latency with one
    replica killed by a real SIGKILL mid-stream, and streaming TTFT (time
    to the first token CHUNK delivered across the process boundary). Both
    procfleet arms assert exactly-once bitwise completions — the bench
    doubles as the kill -9 integration check. This process never touches
    JAX: the replicas need the chip."""
    from paddle_tpu.inference import ProcServingFleet
    from paddle_tpu.testing import chaos

    on_tpu, n_replicas = _setup_off_jax("bench_serve procfleet")
    cfg, kw, prompts, max_new = _procfleet_workload(on_tpu)
    ref = _child("procfleet_ref", BENCH_REPLICAS=str(n_replicas))
    want, rps_in, ttft_in, lat_in = ref["want"], ref["rps"], ref["ttft"], ref["lat"]

    # --- cross-process arm, fault-free: boot cost, throughput, and
    # streaming TTFT (first chunk across the process boundary) --------
    t0 = time.perf_counter()
    pf = ProcServingFleet(cfg, replicas=n_replicas,
                          heartbeat_timeout=120.0, **kw)
    boot_s = time.perf_counter() - t0
    try:
        stream = pf.submit(prompts[0], max_new_tokens=max_new, seed=0,
                           stream=True)
        fids = [stream.fid] + [pf.submit(p, max_new_tokens=max_new, seed=i)
                               for i, p in enumerate(prompts) if i > 0]
        t0 = time.perf_counter()
        chunks = list(stream)
        done_p = pf.run(timeout_s=600)
        dt_p = time.perf_counter() - t0
        assert len(done_p) == len(prompts), "procfleet lost completions"
        got = [list(pf.requests[f].tokens) for f in fids]
        assert got == want, "procfleet diverged from the in-process run"
        assert [t for c in chunks for t in c] == want[0], "stream diverged"
        rps_p = len(done_p) / dt_p if dt_p > 0 else None
        ttft_p = sorted(r.ttft_seconds for r in done_p.values())
        counters = pf.child_counters()
        child_compiles = sum(c.get("compiles", 0) for c in counters.values())
    finally:
        pf.shutdown()

    # --- p99 with one subprocess killed by a real SIGKILL mid-stream: needs
    # a survivor, so a second replica process, so a second chip -----------
    lat_k, stats_k = None, {"requeues": None, "dead": []}
    if n_replicas >= 2:
        with chaos.inject(
                FLAGS_chaos_replica_sigkill_at=f"{n_replicas - 1}:2"):
            pf_k = ProcServingFleet(cfg, replicas=n_replicas,
                                    heartbeat_timeout=120.0, **kw)
            try:
                fids_k = [pf_k.submit(p, max_new_tokens=max_new, seed=i)
                          for i, p in enumerate(prompts)]
                done_k = pf_k.run(timeout_s=600)
                assert len(done_k) == len(prompts), "sigkill run lost completions"
                for i, f in enumerate(fids_k):
                    assert list(done_k[f].tokens) == want[i], \
                        f"sigkill run diverged on request {i}"
                lat_k = sorted(r.total_seconds for r in done_k.values())
                stats_k = pf_k.stats()
            finally:
                pf_k.shutdown()

    overhead = ((rps_in / rps_p - 1.0) * 100.0
                if rps_in and rps_p else None)
    return {
        "replicas": n_replicas,
        "device": ref["device"],
        "requests": len(done_p),
        "requests_per_sec": round(rps_p, 3) if rps_p else None,
        "requests_per_sec_inproc": round(rps_in, 3) if rps_in else None,
        "transport_overhead_pct": round(overhead, 2) if overhead is not None else None,
        "p99_under_sigkill_ms": (round(_percentile(lat_k, 99) * 1e3, 2) if lat_k
                                 else "skipped: the kill arm needs a second chip"),
        "latency_p99_ms_inproc": round(_percentile(lat_in, 99) * 1e3, 2),
        "stream_ttft_p50_ms": round(_percentile(ttft_p, 50) * 1e3, 2),
        "ttft_p50_ms_inproc": round(_percentile(ttft_in, 50) * 1e3, 2),
        "requeues_under_sigkill": stats_k["requeues"],
        "replica_deaths": len(stats_k["dead"]),
        "boot_seconds": round(boot_s, 3),
        "child_compiles": child_compiles,  # 0 == the warm-boot pin held
        "stream_chunks": len(chunks),
    }


def _measure_spec():
    """The round-3 raw-speed phase: speculative decoding (oracle self-draft
    — the acceptance-rate ceiling — plus a genuinely small draft) and the
    int8 KV cache. Reports ``spec_decode_tokens_per_sec`` vs the plain
    per-token engine, the measured ``spec_acceptance_rate`` and
    ``decode_dispatches_per_token`` amortization, and the KV-cache byte
    story: ``kv_bytes_per_slot`` int8 vs f32, the shrink ratio, and
    ``max_concurrent_slots`` — how many slots a notional 64 MiB KV budget
    admits under each representation (the capacity the quantization buys).
    The spec arms assert bitwise parity with the plain engine in-band."""
    import paddle_tpu as paddle
    from paddle_tpu import profiler
    from paddle_tpu.inference import DecodeEngine
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining

    on_tpu, _ = _setup()
    if on_tpu:
        cfg = GPTConfig(vocab_size=50304, hidden_size=1024, num_layers=16,
                        num_heads=16, max_seq_len=1024)
        dcfg = GPTConfig(vocab_size=50304, hidden_size=256, num_layers=2,
                         num_heads=4, max_seq_len=1024)
        slots, max_seq, decode_tokens, spec_k = 8, 1024, 128, 4
        buckets = (64,)
    else:
        cfg = GPTConfig.tiny()
        dcfg = GPTConfig(vocab_size=512, hidden_size=32, num_layers=1,
                         num_heads=2, max_seq_len=128)
        slots, max_seq, decode_tokens, spec_k = 4, 128, 48, 4
        buckets = (16,)

    paddle.seed(0)
    model = GPTForPretraining(cfg)
    model.eval()
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, (slots, buckets[0] - 2)).astype("int32")
    kw = dict(max_batch_slots=slots, max_seq_len=max_seq, prefill_buckets=buckets)

    def timed_tps(eng):
        eng.generate(prompt, max_new_tokens=2)   # compile + warm
        t0 = time.perf_counter()
        out = eng.generate(prompt, max_new_tokens=decode_tokens)
        dt = time.perf_counter() - t0
        return slots * decode_tokens / dt, out

    plain = DecodeEngine(model, **kw)
    plain_tps, want = timed_tps(plain)

    # oracle self-draft: acceptance ~1.0 — the amortization ceiling (a real
    # deployment's distilled draft lands between this and the small-draft arm)
    profiler.reset_counters("infer.")
    oracle = DecodeEngine(model, draft=model, spec_k=spec_k, **kw)
    oracle_tps, got = timed_tps(oracle)
    assert np.array_equal(got, want), "oracle spec arm diverged from plain engine"
    c = profiler.counters("infer.")
    disp_per_tok = (int(c.get("infer.decode_dispatches", 0)) - 1) / max(
        1, int(c.get("infer.tokens", 0)) - slots)  # minus the warm-up generate
    oracle_acc = oracle.spec_stats()["acceptance_rate"]

    # small independent draft: real draft-forward cost at its (random-init,
    # near-zero) acceptance — the throughput floor of the mechanism
    small = DecodeEngine(model, draft=dcfg, spec_k=spec_k, draft_seed=1, **kw)
    small_tps, got = timed_tps(small)
    assert np.array_equal(got, want), "small-draft spec arm diverged from plain engine"
    small_acc = small.spec_stats()["acceptance_rate"]

    # --- int8 KV cache: per-slot bytes and the capacity they buy ----------
    i8 = DecodeEngine(model, kv_dtype="int8", **kw)
    i8.generate(prompt, max_new_tokens=2)
    f32_slot, i8_slot = plain.kv_bytes_per_slot(), i8.kv_bytes_per_slot()
    kv_budget = 64 * 1024 * 1024  # notional per-chip KV budget for capacity math
    return {
        "spec_k": spec_k,
        "decode_tokens_per_sec_plain": round(plain_tps, 1),
        "spec_decode_tokens_per_sec": round(oracle_tps, 1),
        "spec_decode_tokens_per_sec_small_draft": round(small_tps, 1),
        "spec_speedup_oracle": round(oracle_tps / plain_tps, 2) if plain_tps else None,
        "spec_acceptance_rate": round(oracle_acc, 4),
        "spec_acceptance_rate_small_draft": round(small_acc, 4),
        "decode_dispatches_per_token": round(disp_per_tok, 4),
        "kv_bytes_per_slot": i8_slot,
        "kv_bytes_per_slot_f32": f32_slot,
        "kv_shrink": round(f32_slot / i8_slot, 2) if i8_slot else None,
        "max_concurrent_slots": int(kv_budget // i8_slot) if i8_slot else None,
        "max_concurrent_slots_f32": int(kv_budget // f32_slot) if f32_slot else None,
    }


def _measure_alerts():
    """The round-3 alerting arm: the SLO engine watching a live fleet.

    Installs one TTFT SLO with sub-second burn windows (the production
    ~5min/1h windows shrunk so the bench finishes), injects a chaos
    latency spike (``FLAGS_chaos_replica_slow_ms``), and measures the
    judgment layer's reaction time: ``alert_detection_ms`` — wall time
    from the start of the degraded run to the page-severity alert firing
    — and ``alert_firing_ms`` — page until the alert cleared as the
    spike aged out of both windows. The spike size and
    objective threshold are machine-relative (multiples of the measured
    healthy TTFT) so the arm pages on the chaos and never on the host's
    own speed. ``slo_eval_overhead_pct`` is the monitor's cost while the
    healthy run was serving: Σ ``slo.eval_seconds`` over the run's wall
    time, evaluated every 50ms — budget < 2%."""
    import paddle_tpu as paddle
    from paddle_tpu.inference import ServingFleet
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    from paddle_tpu.observability import metrics, slo
    from paddle_tpu.testing import chaos

    on_tpu, _ = _setup()
    if on_tpu:
        cfg = GPTConfig(vocab_size=50304, hidden_size=1024, num_layers=16,
                        num_heads=16, max_seq_len=1024)
        slots, max_seq, max_new, n_requests = 8, 1024, 16, 12
        chunk, fuse, n_replicas = 128, 8, 2
    else:
        cfg = GPTConfig.tiny()
        slots, max_seq, max_new, n_requests = 2, 128, 6, 6
        chunk, fuse, n_replicas = 16, 2, 2

    paddle.seed(0)
    model = GPTForPretraining(cfg)
    model.eval()
    rng = np.random.default_rng(0)
    kw = dict(max_batch_slots=slots, max_seq_len=max_seq, prefill_chunk=chunk,
              fuse=fuse)
    prompts = [rng.integers(0, cfg.vocab_size, (int(n),)).astype("int32")
               for n in rng.integers(max(1, chunk // 4), chunk, n_requests)]

    import tempfile

    log_dir = tempfile.mkdtemp(prefix="bench_alerts_log_")
    prev_flags = paddle.get_flags(["FLAGS_run_log_dir"])
    paddle.set_flags({"FLAGS_run_log_dir": log_dir})

    def serve(tag):
        fl = ServingFleet(model, replicas=n_replicas, **kw)
        for i, p in enumerate(prompts):
            fl.submit(p, max_new_tokens=max_new, seed=i)
        t0 = time.perf_counter()
        fl.run()
        return time.perf_counter() - t0

    try:
        serve("warm")   # compile + serialize the program family
        serve("healthy")  # healthy TTFT sample, monitor not yet installed
        ttft_hist = metrics.histogram("serving.ttft_seconds")
        healthy_ms = (ttft_hist.percentile(50) or 0.01) * 1e3
        # objective + spike sized off the measured healthy TTFT so the arm
        # alerts on the chaos, not on the host's own speed
        threshold_ms = max(50.0, 2.0 * healthy_ms)
        slow_ms = int(min(2500.0, max(150.0, 4.0 * healthy_ms)))
        # the fast window must hold several chaos-slowed ticks: the first
        # degraded TTFT only exists a few ticks into the incident, so a
        # window shorter than that could never contain its own detection
        fast_w = max(2.0, 6.0 * slow_ms / 1e3)
        slow_w = 4.0 * fast_w
        spec = slo.SLO("serving.ttft_p50_ms", "percentile",
                       threshold=threshold_ms, histogram="serving.ttft_seconds",
                       q=50, scale=1e3, page_burn=1.2, warn_burn=1.0,
                       description="bench alerting arm: machine-relative TTFT")
        mon = slo.install([spec], with_regress=False, eval_every_s=0.05,
                          fast_window_s=fast_w, slow_window_s=slow_w)
        mon.evaluate()  # baseline snapshot before the overhead-metered run

        # --- monitor overhead while serving healthy traffic ---------------
        eval_sum0 = metrics.histogram("slo.eval_seconds").sum
        evals0 = metrics.counters("slo.")["slo.evaluations"]
        dt_healthy = serve("metered")
        eval_cost = metrics.histogram("slo.eval_seconds").sum - eval_sum0
        overhead_pct = eval_cost / dt_healthy * 100.0 if dt_healthy else None
        evaluations = int(metrics.counters("slo.")["slo.evaluations"] - evals0)
        paged_on_healthy = mon.states()[0]["severity"] is not None

        # --- chaos latency spike: page within the fast window -------------
        # quiesce one fast window first: otherwise the healthy run's TTFT
        # samples share the window with the first chaos samples and hold
        # the percentile down, inflating detection by ~the window length
        time.sleep(fast_w)
        mon.evaluate()
        t_chaos = time.time()
        with chaos.inject(FLAGS_chaos_replica_slow_ms=str(slow_ms)):
            serve("chaos")  # tick loops drive the monitor's 50ms cadence
        mon.evaluate()

        # --- recovery: healthy traffic, spike ages out of both windows ----
        serve("recovery")
        deadline = time.time() + 4 * slow_w
        while time.time() < deadline:
            mon.evaluate()
            if mon.states()[0]["severity"] is None:
                break
            time.sleep(0.1)

        # detection/clear come from the run-log alert events: with chaos
        # ticks longer than the fast window the alert can fire AND clear
        # inside the chaos run itself, so post-run monitor state alone
        # would under-report what the judgment layer actually did
        events = []
        for name in sorted(os.listdir(log_dir)):
            if not (name.startswith("run-") and name.endswith(".jsonl")):
                continue
            with open(os.path.join(log_dir, name)) as f:
                for line in f:
                    try:
                        ev = json.loads(line)
                    except ValueError:
                        continue
                    if (ev.get("event") == "alert"
                            and ev.get("slo") == spec.name
                            and ev.get("ts", 0) >= t_chaos):
                        events.append(ev)
        events.sort(key=lambda e: e.get("ts", 0))
        pages = [e for e in events if e.get("state") == "firing"
                 and e.get("severity") == "page"]
        severity = "page" if pages else (
            events[-1].get("severity") if events else None)
        detection_ms = ((pages[0]["since"] - t_chaos) * 1e3
                        if pages and pages[0].get("since") else None)
        cleared = [e for e in events if e.get("state") == "cleared"
                   and pages and e["ts"] >= pages[0]["ts"]]
        clear_ms = ((cleared[-1]["ts"] - pages[0]["since"]) * 1e3
                    if cleared and pages[0].get("since") else None)
        final_quiet = mon.states()[0]["severity"] is None
        return {
            "replicas": n_replicas,
            "healthy_ttft_p50_ms": round(healthy_ms, 2),
            "ttft_threshold_ms": round(threshold_ms, 2),
            "chaos_slow_ms": slow_ms,
            "fast_window_s": fast_w,
            "slow_window_s": slow_w,
            "alert_severity": severity,
            "alert_detection_ms": (round(detection_ms, 1)
                                   if detection_ms is not None else None),
            "detected_within_fast_window": (
                detection_ms is not None and detection_ms <= fast_w * 1e3),
            "alert_cleared": bool(cleared) and final_quiet,
            "alert_firing_ms": (round(clear_ms, 1)
                                if clear_ms is not None else None),
            "slo_evaluations": evaluations,
            "slo_eval_overhead_pct": (round(overhead_pct, 4)
                                      if overhead_pct is not None else None),
            "paged_on_healthy_traffic": paged_on_healthy,
            "page_alerts_fired": int(metrics.counters("alerts.")["alerts.page"]),
        }
    finally:
        slo.uninstall()
        try:
            paddle.set_flags(prev_flags)
        except Exception:
            pass


def _measure_ingress():
    """The round-4 front-door phase: HTTP ingress over the cross-process
    fleet on the socket fast path. Measures the HTTP hop against the same
    fleet driven in-process, the socket transport against the store-poll
    transport on an identical workload, streaming TTFT over chunked
    transfer, the disconnect→cancel reaction, a drain under load, and the
    headline chaos pin: ``kill -9`` of a replica mid-decode with HTTP
    streams open — every stream must complete bitwise-identical to the
    unkilled reference, exactly once, through the real socket path."""
    import http.client
    import threading

    from paddle_tpu.inference import ProcServingFleet, ServingIngress
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.testing import chaos

    # this process never touches JAX: the replica processes need the chip
    on_tpu, n_replicas = _setup_off_jax("bench_serve ingress")
    if on_tpu:
        cfg = GPTConfig(vocab_size=50304, hidden_size=1024, num_layers=16,
                        num_heads=16, max_seq_len=1024)
        slots, max_seq, max_new, n_requests = 8, 1024, 16, 16
        chunk, fuse = 128, 8
    else:
        cfg = GPTConfig.tiny()
        slots, max_seq, max_new, n_requests = 2, 128, 8, 8
        chunk, fuse = 16, 2

    rng = np.random.default_rng(0)
    kw = dict(max_batch_slots=slots, max_seq_len=max_seq, prefill_chunk=chunk,
              fuse=fuse, heartbeat_timeout=120.0)
    prompts = [rng.integers(0, cfg.vocab_size, (int(n),)).astype("int32")
               for n in rng.integers(max(1, chunk // 4), chunk, n_requests)]
    bodies = [{"prompt": [int(t) for t in p], "max_new_tokens": max_new,
               "seed": i} for i, p in enumerate(prompts)]

    def _post(port, body, stream=False, key=None, timeout=600):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
        headers = {"Content-Type": "application/json"}
        if key:
            headers["Idempotency-Key"] = key
        conn.request("POST", "/v1/generate",
                     body=json.dumps(body).encode(), headers=headers)
        r = conn.getresponse()
        if not stream:
            doc = json.loads(r.read())
            conn.close()
            return r.status, doc, None
        toks, t_first, final = [], None, None
        while True:
            line = r.readline()
            if not line:
                break
            doc = json.loads(line)
            if t_first is None:
                t_first = time.perf_counter()
            if "tokens" in doc:
                toks.extend(doc["tokens"])
            else:
                final = doc
        conn.close()
        return r.status, {"tokens": toks, "final": final}, t_first

    # --- socket-transport fleet: in-process reference, then HTTP -----
    pf = ProcServingFleet(cfg, replicas=n_replicas, **kw)
    try:
        # untimed warm-up: the children compile their program family on
        # first prefill — both transport arms are timed warm
        for i, p in enumerate(prompts):
            pf.submit(p, max_new_tokens=max_new, seed=500 + i)
        pf.run(timeout_s=600)
        fids = [pf.submit(p, max_new_tokens=max_new, seed=i)
                for i, p in enumerate(prompts)]
        t0 = time.perf_counter()
        pf.run(timeout_s=600)
        dt_direct = time.perf_counter() - t0
        assert all(pf.requests[f].status == "finished" for f in fids), \
            "direct run lost completions"
        want = [list(pf.requests[f].tokens) for f in fids]
        rps_direct = len(fids) / dt_direct if dt_direct > 0 else None

        ing = ServingIngress(pf, port=0)
        results = [None] * n_requests
        nthreads = min(4, n_requests)

        def http_worker(idxs):
            for i in idxs:
                st, doc, _ = _post(ing.port, bodies[i])
                results[i] = (st, doc)

        t0 = time.perf_counter()
        ts = [threading.Thread(target=http_worker,
                               args=(range(k, n_requests, nthreads),))
              for k in range(nthreads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        dt_http = time.perf_counter() - t0
        for i, (st, doc) in enumerate(results):
            assert st == 200 and doc["status"] == "finished", (st, doc)
            assert doc["tokens"] == want[i], "http run diverged"
        rps_http = n_requests / dt_http if dt_http > 0 else None

        # streaming TTFT over HTTP (sequential — isolates the hop)
        ttfts = []
        for i in range(min(4, n_requests)):
            t0 = time.perf_counter()
            st, doc, t_first = _post(ing.port, dict(bodies[i], stream=True),
                                     stream=True)
            assert st == 200 and doc["tokens"] == want[i], "stream diverged"
            ttfts.append(t_first - t0)
        ttfts.sort()

        # client disconnect mid-stream -> mid-decode cancel
        long_body = dict(bodies[0], max_new_tokens=max_new * 8,
                         stream=True)
        conn = http.client.HTTPConnection("127.0.0.1", ing.port,
                                          timeout=600)
        conn.request("POST", "/v1/generate",
                     body=json.dumps(long_body).encode(),
                     headers={"Idempotency-Key": "bench-disconnect"})
        r = conn.getresponse()
        r.readline()  # one chunk is flowing; the request is mid-decode
        fid = ing._idem["bench-disconnect"].fid
        t0 = time.perf_counter()
        conn.sock.close()
        conn.close()
        while (pf.requests[fid].status not in
               ("finished", "cancelled", "deadline_exceeded")
               and time.perf_counter() - t0 < 30):
            time.sleep(0.002)
        disconnect_ms = (time.perf_counter() - t0) * 1e3
        disconnect_status = pf.requests[fid].status

        # drain under load: requests in flight when the drain begins
        drain_docs = []

        def drain_worker(i):
            _, doc, _ = _post(ing.port, dict(bodies[i], seed=100 + i))
            drain_docs.append(doc)

        dts = [threading.Thread(target=drain_worker, args=(i,))
               for i in range(3)]
        for t in dts:
            t.start()
        t0 = time.perf_counter()
        while len(ing._active) < 3 and time.perf_counter() - t0 < 30:
            time.sleep(0.002)
        t0 = time.perf_counter()
        ing.begin_drain()
        drain_rc = ing.drain(grace=300)
        drain_ms = (time.perf_counter() - t0) * 1e3
        for t in dts:
            t.join()
        drain_finished = sum(1 for d in drain_docs
                             if d.get("status") == "finished")
    finally:
        pf.shutdown()

    # --- store-poll transport: identical workload, sockets off -------
    pf_s = ProcServingFleet(cfg, replicas=n_replicas, use_sockets=False,
                            **kw)
    try:
        for i, p in enumerate(prompts):  # warm, like the socket arm
            pf_s.submit(p, max_new_tokens=max_new, seed=500 + i)
        pf_s.run(timeout_s=600)
        fids = [pf_s.submit(p, max_new_tokens=max_new, seed=i)
                for i, p in enumerate(prompts)]
        t0 = time.perf_counter()
        pf_s.run(timeout_s=600)
        dt_store = time.perf_counter() - t0
        assert all(pf_s.requests[f].status == "finished" for f in fids), \
            "store run lost completions"
        got = [list(pf_s.requests[f].tokens) for f in fids]
        assert got == want, "store transport diverged"
    finally:
        pf_s.shutdown()

    # --- kill -9 through the ingress: bitwise exactly-once. Needs a
    # survivor, so a second replica process, so a second chip -------------
    stats_k = None
    if n_replicas >= 2:
        with chaos.inject(
                FLAGS_chaos_replica_sigkill_at=f"{n_replicas - 1}:2"):
            pf_k = ProcServingFleet(cfg, replicas=n_replicas, **kw)
            ing_k = ServingIngress(pf_k, port=0)
            try:
                kill_docs = [None] * 4

                def kill_worker(i):
                    st, doc, _ = _post(ing_k.port,
                                       dict(bodies[i], stream=True),
                                       stream=True)
                    kill_docs[i] = (st, doc)

                kts = [threading.Thread(target=kill_worker, args=(i,))
                       for i in range(4)]
                for t in kts:
                    t.start()
                for t in kts:
                    t.join()
                for i, (st, doc) in enumerate(kill_docs):
                    assert st == 200, f"kill arm http {st}"
                    assert doc["final"]["status"] == "finished", doc["final"]
                    assert doc["tokens"] == want[i], \
                        f"kill arm diverged on stream {i}"
                stats_k = pf_k.stats()
            finally:
                ing_k.stop()
                pf_k.shutdown()
    socket_vs_store = ((dt_direct / dt_store - 1.0) * 100.0
                       if dt_store > 0 else None)
    return {
        "replicas": n_replicas,
        "requests": n_requests,
        "ingress_requests_per_sec": round(rps_http, 3) if rps_http else None,
        "requests_per_sec_inproc": round(rps_direct, 3) if rps_direct else None,
        "http_overhead_pct": (round((dt_http / dt_direct - 1.0) * 100.0, 2)
                              if dt_direct > 0 else None),
        "socket_vs_store_overhead_pct": (round(socket_vs_store, 2)
                                         if socket_vs_store is not None
                                         else None),
        "stream_ttft_p50_ms": round(_percentile(ttfts, 50) * 1e3, 2),
        "disconnect_cancel_ms": round(disconnect_ms, 2),
        "disconnect_status": disconnect_status,
        "drain_under_load_ms": round(drain_ms, 2),
        "drain_rc": drain_rc,
        "drain_finished": drain_finished,
        "drain_inflight": len(drain_docs),
        # asserted above, bitwise — where the host could place two replicas
        "exactly_once_under_sigkill": (True if stats_k else
                                       "skipped: the kill arm needs a second chip"),
        "requeues_under_sigkill": stats_k["requeues"] if stats_k else None,
        "replica_deaths": len(stats_k["dead"]) if stats_k else None,
    }


def _child(which, timeout=None, **env):
    """Run one phase of this script in a child process; its last JSON line.
    A child that dies prints its stderr tail and raises."""
    import subprocess

    r = subprocess.run([sys.executable, os.path.abspath(__file__)],
                       env=dict(os.environ, BENCH_ONE=which, **env),
                       capture_output=True, text=True, timeout=timeout)
    if r.returncode != 0:
        print(r.stderr[-2000:], file=sys.stderr)
        raise RuntimeError(f"phase {which!r} exited {r.returncode}")
    return json.loads([l for l in r.stdout.splitlines() if l.startswith("{")][-1])


_PHASES = {"serve": _measure, "spec": _measure_spec, "fleet": _measure_fleet,
           "procfleet": _measure_procfleet, "procfleet_ref": _measure_procfleet_ref,
           "alerts": _measure_alerts, "ingress": _measure_ingress}
# per-phase wall budgets (seconds), env-overridable: a phase past its budget
# is killed and recorded as failed
_BUDGETS = {"serve": ("BENCH_BUDGET_SERVE", 420), "spec": ("BENCH_BUDGET_SPEC", 300),
            "fleet": ("BENCH_BUDGET_FLEET", 300), "procfleet": ("BENCH_BUDGET_PROCFLEET", 300),
            "alerts": ("BENCH_BUDGET_ALERTS", 240), "ingress": ("BENCH_BUDGET_INGRESS", 420)}


def main():
    if os.environ.get("BENCH_ONE"):
        print(json.dumps(_PHASES[os.environ["BENCH_ONE"]]()))
        return

    # this parent never touches JAX: every phase is a child, one at a time
    import subprocess

    results, failed = {}, []
    for name, (var, default) in _BUDGETS.items():
        budget = float(os.environ.get(var, default))
        try:
            results[name] = _child(name, timeout=budget)
        except subprocess.TimeoutExpired:
            results[name] = {"status": "timeout", "budget_seconds": budget}
            failed.append(name)
        except Exception as exc:
            results[name] = {"status": "error", "error": f"{type(exc).__name__}: {exc}"}
            failed.append(name)

    extras = results.pop("serve")
    on_tpu = bool(extras.get("on_tpu"))
    out = {
        # a run without a TPU is a smoke run: its numbers are host timings
        # and never carry the device metric's name
        "metric": "gpt_serving_throughput" if on_tpu else "gpt_serving_throughput_cpu_smoke",
        "value": extras.get("value"),
        "unit": "requests/sec" if on_tpu else "requests/sec (host timing, not a device metric)",
        "vs_baseline": None,
    }
    base_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "bench_serve_baseline.json")
    with open(base_path) as f:
        prior = json.load(f)
    # the committed baseline is a CPU smoke configuration: only like-for-like
    if extras.get("value") and prior.get("config") == extras.get("config") and prior.get("value"):
        out["vs_baseline"] = round(extras["value"] / prior["value"], 4)
        # round-2 acceptance ratios vs the committed baseline:
        # throughput-style fields improve UP, latency-style DOWN
        if prior.get("decode_tokens_per_sec"):
            extras["decode_tokens_per_sec_vs_baseline"] = round(
                extras["decode_tokens_per_sec"] / prior["decode_tokens_per_sec"], 4)
        if prior.get("ttft_p50_ms"):
            extras["ttft_p50_ms_vs_baseline"] = round(
                prior["ttft_p50_ms"] / extras["ttft_p50_ms"], 4)
    out.update({k: v for k, v in extras.items() if k != "value"})
    out.update(results)
    if failed:
        out["failed_phases"] = failed
    print(json.dumps(out))
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
