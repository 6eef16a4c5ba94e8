"""Recommender-workload benchmark: DLRM over mesh-sharded embedding tables.

Prints ONE JSON line on the bench.py schema: {"metric", "value", "unit",
"vs_baseline", ...}. Measurements:

1. **recsys_examples_per_sec** — DLRM (dense bottom MLP + fused
   ``ShardedEmbedding`` bags + pairwise interaction + top MLP) trained
   through the one-dispatch ``TrainStep.run_steps`` scan on a row-sharded
   dp mesh with the ``RowSparseAdam`` touched-rows-only optimizer path;
2. **embedding_a2a_bytes_per_step** — the static per-step ``all_to_all``
   exchange payload (ids + embeddings, fwd + grad push) the sharded
   lookup declares from shapes alone;
3. **touched_row_fraction** — mean unique-ids / vocab over the measured
   batches: the fraction of the table a step actually updates, the number
   that justifies the row-sparse optimizer contract.

Like bench.py / bench_serve.py it runs on whatever device ``JAX_PLATFORMS``
gives it and names it in ``device``; without a TPU it runs a tiny smoke
configuration on a 4-way virtual CPU mesh, says so, and reports under
``dlrm_examples_per_sec_cpu_smoke`` (host timings, not device metrics). The
measured run is a child process under ``BENCH_BUDGET_RECSYS``; a timeout or
crash makes the exit code non-zero.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def _measure():
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import profiler
    from paddle_tpu.distributed.planner import Plan, build_step
    from paddle_tpu.framework.flags import ensure_compile_cache
    from paddle_tpu.models.dlrm import DLRM, DLRMConfig, DLRMCriterion
    from paddle_tpu.observability.metrics import counter_inc
    from paddle_tpu.optimizer import RowSparseAdam

    ensure_compile_cache()
    device = paddle.device.describe()
    on_tpu = paddle.device.is_tpu()
    if not on_tpu:
        print(f"bench_recsys: no TPU (platform={device['platform']}): tiny smoke configuration, "
              "host timings — not device metrics", file=sys.stderr)
    ndev = device["count"]
    if on_tpu:
        cfg = DLRMConfig(num_dense=13, vocab_sizes=(100_000,) * 8,
                         embedding_dim=64, bottom_mlp=(256, 128),
                         top_mlp=(256, 128))
        batch, k, rounds = 4096, 8, 4
        shards = ndev
    else:
        cfg = DLRMConfig(num_dense=8, vocab_sizes=(512, 256, 1024, 512),
                         embedding_dim=16, bottom_mlp=(32,), top_mlp=(32,))
        batch, k, rounds = 64, 8, 4
        shards = min(4, ndev)

    paddle.seed(0)
    model = DLRM(cfg)
    opt = RowSparseAdam(learning_rate=1e-3, parameters=model.parameters(),
                        sparse_params=model.sparse_param_names())
    plan = Plan(mesh={"dp": shards} if shards > 1 else {}, template="row",
                n_devices=shards, param_specs={"embedding.weight": ["dp"]})
    step = build_step(model, opt, DLRMCriterion(), plan,
                      devices=jax.devices()[:shards], seed=0)

    rng = np.random.default_rng(0)

    def make_batch():
        dense = rng.normal(size=(batch, cfg.num_dense)).astype(np.float32)
        # power-law id skew: the recsys-traffic shape (hot head, long tail)
        ids = np.stack(
            [np.minimum((rng.pareto(1.05, batch) * (v // 50)).astype(np.int64),
                        v - 1) for v in cfg.vocab_sizes], axis=1).astype(np.int32)
        labels = rng.integers(0, 2, (batch, 1)).astype(np.float32)
        return (dense, ids), (labels,)

    stacks = [[make_batch() for _ in range(k)] for _ in range(rounds)]
    offsets = np.cumsum((0,) + cfg.vocab_sizes[:-1])[None, :]
    touched = np.mean([  # per-STEP touched fraction of the fused table
        np.unique(b[0][1] + offsets).size / cfg.total_vocab
        for stack in stacks for b in stack])

    t_build0 = time.perf_counter()
    step.run_steps(stacks[0])  # compile (run_steps scan) + first dispatch
    ttfs = time.perf_counter() - t_build0

    profiler.reset_counters("train_step.")
    t0 = time.perf_counter()
    last = None
    for stack in stacks:
        last = step.run_steps(stack)
    float(last["loss"].numpy()[-1])  # host sync: everything above finished
    dt = time.perf_counter() - t0
    steps = rounds * k
    counter_inc("recsys.steps", steps)
    counter_inc("recsys.examples", steps * batch)
    c = profiler.counters("train_step.")
    exch = model.embedding.exchange_stats(batch * cfg.num_sparse,
                                          shards=shards)

    config_key = (f"{device['kind']}/dlrm-v{cfg.total_vocab}"
                  f"d{cfg.embedding_dim}b{batch}x{shards}")
    return {
        "value": round(steps * batch / dt, 1),
        "config": config_key,
        "on_tpu": on_tpu,
        "device": device,
        "recsys_examples_per_sec": round(steps * batch / dt, 1),
        "steps_per_sec": round(steps / dt, 2),
        "embedding_a2a_bytes_per_step": exch["bytes_total"],
        "touched_row_fraction": round(float(touched), 5),
        "exchange_capacity": exch["capacity"],
        "shards": shards,
        "batch": batch,
        "total_vocab": cfg.total_vocab,
        "embedding_dim": cfg.embedding_dim,
        "loss_final": round(float(last["loss"].numpy()[-1]), 5),
        "dispatches_per_run_steps": c.get("train_step.dispatches", 0) / rounds,
        "time_to_first_step": round(ttfs, 3),
    }


def main():
    if os.environ.get("BENCH_ONE"):
        print(json.dumps(_measure()))
        return

    # virtual CPU mesh for the sharded exchange; must land before any jax
    # backend init in this process or a child (harmless on real TPUs)
    os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
        " --xla_force_host_platform_device_count=4"

    import subprocess

    budget = float(os.environ.get("BENCH_BUDGET_RECSYS", 300))
    r = subprocess.run([sys.executable, os.path.abspath(__file__)],
                       env=dict(os.environ, BENCH_ONE="recsys"),
                       capture_output=True, text=True, timeout=budget)
    if r.returncode != 0:
        print(r.stderr[-2000:], file=sys.stderr)
        sys.exit(r.returncode)
    extras = json.loads([l for l in r.stdout.splitlines() if l.startswith("{")][-1])

    base_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "bench_recsys_baseline.json")
    with open(base_path) as f:
        prior = json.load(f)
    vs = None  # the committed baseline is a CPU smoke configuration: only like-for-like
    if prior.get("config") == extras.get("config") and prior.get("value"):
        vs = round(extras["value"] / prior["value"], 4)

    on_tpu = extras["on_tpu"]
    out = {"metric": "dlrm_examples_per_sec" if on_tpu else "dlrm_examples_per_sec_cpu_smoke",
           "value": extras["value"],
           "unit": "examples/sec" if on_tpu else "examples/sec (host timing, not a device metric)",
           "vs_baseline": vs}
    out.update({key: v for key, v in extras.items() if key != "value"})
    print(json.dumps(out))


if __name__ == "__main__":
    main()
