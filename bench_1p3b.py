"""Flagship-config proof runs (BASELINE.md rows 4 and 5).

Modes:
  python bench_1p3b.py cpu-mesh   — full GPT-3 1.3B hybrid (dp2 x mp2 x pp2,
      ZeRO stage-2 over sdp where factored) ONE step on an 8-device mesh at
      full layer/hidden dims, tiny batch: proves the sharded compile + memory
      plan without TPU hardware. Run it with ``JAX_PLATFORMS=cpu
      XLA_FLAGS=--xla_force_host_platform_device_count=8``; the platform is
      never changed in code.
  python bench_1p3b.py tpu        — single real chip: 1.3B with selective
      remat + grad accumulation + bf16 AMP O2, measured tokens/sec/chip.
  python bench_1p3b.py tpu-ernie  — ERNIE-3.0-style hybrid config #5 proxy on
      one chip (same trunk machinery; mp/pp degrees are mesh-bound, so the
      single-chip number is the per-chip throughput of the dp slice).

Each mode prints one JSON line that names the device it ran on; the ``tpu``
modes fail without one.
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np


def _cpu_mesh_step():
    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.strategy import DistributedStrategy
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining, GPTPretrainingCriterion

    strat = DistributedStrategy()
    strat.hybrid_configs = {"dp_degree": 1, "sharding_degree": 2, "mp_degree": 2, "pp_degree": 2}
    strat.sharding = True
    strat.sharding_configs = {"sharding_stage": 2}
    strat.pipeline_configs = {"accumulate_steps": 2, "schedule": "1f1b"}
    fleet.init(is_collective=True, strategy=strat)

    paddle.seed(0)
    cfg = GPTConfig.gpt3_1p3b(max_seq_len=256)  # full width/depth, short seq
    model = GPTForPretraining(cfg)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    opt = paddle.optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters())
    step = fleet.distributed_step(model, opt, GPTPretrainingCriterion())
    ids = fleet.shard_batch(paddle.to_tensor(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (4, 256)).astype("int32")))
    t0 = time.time()
    loss = float(step(ids, ids)["loss"])
    print(json.dumps({
        "metric": "gpt3_1p3b_hybrid_cpu_mesh_step", "params": n_params,
        "mesh": "sdp2xmp2xpp2+zero2", "loss": round(loss, 4),
        "step_wall_s": round(time.time() - t0, 1), "ok": bool(np.isfinite(loss)),
        "device": paddle.device.describe(),
    }))


def _tpu_run(ernie=False):
    import paddle_tpu as paddle
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining, GPTPretrainingCriterion

    if not paddle.device.is_tpu():
        raise SystemExit(f"bench_1p3b: the tpu modes measure a chip; this is {paddle.device.describe()}")
    paddle.seed(0)
    rng = np.random.default_rng(0)
    if ernie:
        # the REAL ERNIE family (models/ernie.py): 3.0-xbase shape, MLM+SOP
        from paddle_tpu.models.ernie import (
            ErnieConfig,
            ErnieForPretraining,
            ErniePretrainingCriterion,
        )

        cfg = ErnieConfig.ernie3_xbase(vocab_size=40000)
        model = ErnieForPretraining(cfg)

        class Crit(paddle.nn.Layer):
            def __init__(self):
                super().__init__()
                self.c = ErniePretrainingCriterion()

            def forward(self, outs, mlm_labels, sop_labels):
                return self.c(outs[0], outs[1], mlm_labels, sop_labels)

        crit = Crit()
        batch, seq, accum, iters = 16, 512, 1, 8
        name, config = "ernie3_xbase_throughput", f"b16xs512 bf16-O2 MLM+SOP"
        ids = rng.integers(0, cfg.vocab_size, (batch, seq)).astype("int32")
        mlm = ids.copy()
        mlm[:, ::2] = -100  # odd positions are the masked targets
        labels = (paddle.to_tensor(mlm.astype("int64")),
                  paddle.to_tensor(rng.integers(0, 2, (batch,)).astype("int64")))
    else:
        cfg = GPTConfig.gpt3_1p3b(recompute=True, recompute_granularity="selective")
        model = GPTForPretraining(cfg)
        crit = GPTPretrainingCriterion()
        batch, seq, accum, iters = 4, 2048, 2, 6
        name = "gpt3_1p3b_throughput"
        config = f"b{batch}xs{seq} accum{accum} bf16-O2 remat=selective"
        ids = rng.integers(0, cfg.vocab_size, (batch, seq)).astype("int32")
        labels = paddle.to_tensor(ids)

    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    opt = paddle.optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters())
    step = TrainStep(model, opt, crit, amp_level="O2", accumulate_steps=accum)
    t = paddle.to_tensor(ids)
    for _ in range(2):
        out = step(t, labels)
    float(out["loss"])
    t0 = time.perf_counter()
    for _ in range(iters):
        out = step(t, labels)
    float(out["loss"])
    dt = time.perf_counter() - t0
    print(json.dumps({
        "metric": name, "params": n_params,
        "value": round(batch * seq * iters / dt, 1), "unit": "tokens/sec/chip",
        "config": config, "device": paddle.device.describe(),
    }))


if __name__ == "__main__":
    mode = sys.argv[1] if len(sys.argv) > 1 else "cpu-mesh"
    if mode == "cpu-mesh":
        _cpu_mesh_step()
    elif mode == "tpu":
        _tpu_run(False)
    elif mode == "tpu-ernie":
        _tpu_run(True)
    else:
        raise SystemExit(f"unknown mode {mode}")
