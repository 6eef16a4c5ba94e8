"""GPT serving end to end: export → Predictor → generate → continuous
batching.

1. export a decoder artifact (StableHLO: prefill + KV-cache token loop) and
   serve it through paddle.inference.create_predictor;
2. serve the LIVE model through the static-KV-cache DecodeEngine — exactly
   two compiled programs (bucketed prefill + decode step, donated cache
   buffers) for the whole request stream;
3. run a continuous-batching burst: requests with mixed prompt lengths
   admitted into free batch slots mid-flight, with request-level telemetry;
4. the round-2 hot path: ``fuse=D`` (D decode tokens per dispatch inside
   one donated scan — use whenever per-dispatch host overhead is visible),
   ``prefill_chunk=C`` (prompts prefill in C-token dispatches interleaved
   with decode — use when long prompts would stall the stream, and to
   collapse the prefill compile family to 2 programs), and
   ``prefix_cache_mb=M`` (KV reuse across requests sharing a prompt prefix
   — use when traffic shares system prompts / few-shot headers). All three
   keep tokens bitwise equal to the plain path;
5. (``--fleet``) the fault-tolerant fleet tier: 2 engine replicas behind
   the prefix-affinity router, a chaos-injected replica kill mid-stream,
   and every request finishing exactly once with tokens bitwise-equal to
   the unkilled run — plus a load-shed and a deadline expiry;
6. (``--http``) the network boundary: ``ServingIngress`` in front of the
   fleet — a real HTTP POST, an idempotent retry replaying the same
   answer, a chunked per-token stream, and a graceful drain to exit 0.

Run:  python examples/serve_gpt.py [--fleet] [--http]
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.inference import Config, ContinuousBatchingScheduler, DecodeEngine, create_predictor
from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_out")


def main():
    print(f"device: {paddle.device.describe()}")
    paddle.seed(0)
    cfg = GPTConfig.tiny()
    model = GPTForPretraining(cfg)
    model.eval()
    rng = np.random.default_rng(0)

    # 1) export the whole decode loop as a deployable StableHLO artifact
    prefix = os.path.join(OUT, "gpt_decoder")
    model.export_decoder(prefix, prompt_len=8, max_new_tokens=8)
    pred = create_predictor(Config(prefix))
    ids = rng.integers(0, cfg.vocab_size, (2, 8)).astype("int32")
    tokens = pred.generate(ids)
    print(f"predictor[{pred.get_resolved_backend()}] served {tokens.shape[1] - 8} "
          f"tokens/row from the exported artifact")

    # 2) the serving engine: static KV cache, 2 compiled programs total
    profiler.reset_counters("infer.")
    engine = DecodeEngine(model, max_batch_slots=4, max_seq_len=64,
                          prefill_buckets=(8, 16, 32))
    out = engine.generate(ids, max_new_tokens=12)
    c = profiler.counters("infer.")
    print(f"engine decoded {out.shape[1] - ids.shape[1]} tokens/row with "
          f"{int(c['infer.compiles'])} compiled programs "
          f"(prefill + step), cache {engine.cache_bytes() // 1024} KiB")

    # 3) continuous batching: admit-into-free-slots over mixed prompts
    sched = ContinuousBatchingScheduler(engine)
    for n in (5, 9, 3, 14, 7, 11):
        sched.submit(rng.integers(0, cfg.vocab_size, (n,)).astype("int32"),
                     max_new_tokens=6)
    done = sched.run()
    for rid in sorted(done):
        r = done[rid]
        print(f"  request {rid}: prompt {len(r.prompt):>2} tok (bucket {r.bucket:>2}) "
              f"slot {r.slot} -> {len(r.tokens)} tokens in {r.total_seconds * 1e3:6.1f} ms "
              f"(ttft {r.ttft_seconds * 1e3:5.1f} ms)")
    lat = sorted(r.total_seconds for r in done.values())
    print(f"served {len(done)} requests, p50 latency {lat[len(lat) // 2] * 1e3:.1f} ms")

    # 4) round-2 knobs: fused decode + chunked prefill + prefix reuse.
    #    The burst shares a 16-token system prompt, so after the first
    #    admission every request's shared prefix comes from the KV cache.
    profiler.reset_counters("infer.")
    engine2 = DecodeEngine(model, max_batch_slots=4, max_seq_len=64,
                           fuse=4, prefill_chunk=8, prefix_cache_mb=16.0)
    sched2 = ContinuousBatchingScheduler(engine2)
    system = rng.integers(0, cfg.vocab_size, (16,)).astype("int32")
    for n in (5, 9, 3, 14, 7, 11):
        prompt = np.concatenate([system, rng.integers(0, cfg.vocab_size, (n,)).astype("int32")])
        sched2.submit(prompt, max_new_tokens=8)
    done2 = sched2.run()
    c = profiler.counters("infer.")
    ps = engine2.prefix_cache.stats()
    toks = sum(len(r.tokens) for r in done2.values())
    print(f"round-2 engine served {len(done2)} requests / {toks} tokens with "
          f"{int(c['infer.decode_dispatches'])} decode dispatches (fuse=4), "
          f"{int(c['infer.compiles'])} compiles "
          f"(chunk + final + fused step + prefix insert/extract)")
    print(f"  prefix cache: {ps['hits']} hits / {ps['misses']} misses, "
          f"{ps['entries']} chunks ({ps['bytes_used'] // 1024} KiB), "
          f"longest token gap {max(r.max_gap_seconds or 0.0 for r in done2.values()) * 1e3:.2f} ms")

    # 5) (--fleet) the fault-tolerant fleet: replica kill mid-stream,
    #    requeue onto the survivor, exactly-once bitwise completions
    if "--fleet" in sys.argv:
        fleet_stage(model, rng, cfg)

    # 6) (--http) the network boundary: HTTP front door over the fleet
    if "--http" in sys.argv:
        http_stage(model, rng, cfg)


def fleet_stage(model, rng, cfg):
    from paddle_tpu.inference import FleetOverloadError, ServingFleet
    from paddle_tpu.testing import chaos

    kw = dict(max_batch_slots=2, max_seq_len=64, prefill_chunk=8, fuse=2)
    prompts = [rng.integers(0, cfg.vocab_size, (int(n),)).astype("int32")
               for n in (5, 9, 3, 12, 7, 11)]

    # unkilled single-replica reference: the tokens every request must get
    ref = ServingFleet(model, replicas=1, **kw)
    want = [ref.submit(p, max_new_tokens=6, seed=i) for i, p in enumerate(prompts)]
    ref_done = ref.run()
    want = [list(ref_done[f].tokens) for f in want]

    with chaos.inject(FLAGS_chaos_replica_kill_at="1:2"):
        fleet = ServingFleet(model, replicas=2, **kw)
        fids = [fleet.submit(p, max_new_tokens=6, seed=i)
                for i, p in enumerate(prompts)]
        done = fleet.run()
    st = fleet.stats()
    ok = all(list(done[f].tokens) == want[i] for i, f in enumerate(fids))
    print(f"fleet served {len(done)}/{len(prompts)} requests through a "
          f"mid-stream replica kill (dead: {st['dead']}, requeues: "
          f"{st['requeues']}), tokens bitwise-equal to the unkilled run: {ok}")

    # graceful degradation: deadline expiry + queue-depth shed
    small = ServingFleet(model, replicas=1, max_queue_depth=2, **kw)
    fid = small.submit(prompts[3], max_new_tokens=40, deadline_s=0.001)
    small.run()
    print(f"  deadline: request {fid} ended "
          f"{small.requests[fid].status} (slot reclaimed, not drained)")
    small.submit(prompts[0], max_new_tokens=4)
    small.submit(prompts[1], max_new_tokens=4)
    try:
        small.submit(prompts[2], max_new_tokens=4)
    except FleetOverloadError as e:
        print(f"  overload shed: {e}")
    small.run()


def http_stage(model, rng, cfg):
    import http.client
    import json

    from paddle_tpu.inference import ServingFleet, ServingIngress

    kw = dict(max_batch_slots=2, max_seq_len=64, prefill_chunk=8, fuse=2)
    fleet = ServingFleet(model, replicas=2, **kw)
    ing = ServingIngress(fleet, port=0)
    prompt = rng.integers(0, cfg.vocab_size, (6,)).astype("int32").tolist()

    def post(body, key=None, stream=False):
        conn = http.client.HTTPConnection("127.0.0.1", ing.port, timeout=60)
        hdrs = {"Content-Type": "application/json"}
        if key:
            hdrs["Idempotency-Key"] = key
        conn.request("POST", "/v1/generate", json.dumps(body), hdrs)
        resp = conn.getresponse()
        if stream:
            lines = [json.loads(ln) for ln in resp.read().splitlines() if ln]
            conn.close()
            return lines
        doc = json.loads(resp.read())
        conn.close()
        return doc

    # a real request over the wire, then an idempotent retry of the same
    # key: the ingress replays the ledger answer, never re-generates
    body = {"prompt": prompt, "max_new_tokens": 8, "seed": 7}
    first = post(body, key="example-1")
    again = post(body, key="example-1")
    replay = first["tokens"] == again["tokens"] and first["fid"] == again["fid"]
    print(f"http: POST /v1/generate -> {first['status']}, "
          f"{len(first['tokens'])} tokens; idempotent retry replayed "
          f"fid {again['fid']}: {replay}")

    # per-token chunked streaming rides the same exactly-once ledger
    lines = post(dict(body, seed=8, stream=True), stream=True)
    toks = [t for ln in lines if "tokens" in ln for t in ln["tokens"]]
    print(f"http: streamed {len(toks)} tokens in {len(lines) - 1} chunks, "
          f"final status {lines[-1].get('status')}")

    # graceful drain: healthz flips NotReady, in-flight finishes, exit 0
    ing.begin_drain()
    rc = ing.drain(grace=30.0)
    print(f"http: drained with exit code {rc}")


if __name__ == "__main__":
    main()
