"""Training steps: one ``TrainStep`` call per batch, the loss pulled to the
host each step (a host transfer, so the step is done when it is timed).

Traffic parameters: ``batch`` (sequences, global), ``seq``, ``amp_level``,
``learning_rate``, ``distinct_batches``, ``warmup_steps`` and ``mesh``: null
for one chip through ``paddle.jit.TrainStep``, or the hybrid degrees and ZeRO
stage for ``fleet.distributed_step`` over the cell's chips. Batches are made
on the device from ``--seed`` and stay there.

The window lies on step boundaries: it opens at the end of the last warm-up
step and closes at the last step end at or before open + ``--seconds``.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmark.harness import stats
from benchmark.harness import trace as _trace

clock = time.perf_counter


def _builds() -> int:
    from paddle_tpu.observability import metrics

    c = metrics.counters("train_step.")
    return int(c.get("train_step.compiles", 0) + c.get("train_step.aot_cache_hits", 0))


def build_step(records, devices):
    """(step, place) — the compiled trainer for the cell's layout, and the
    function that puts a host batch where that layout wants it."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTPretrainingCriterion

    cell = records.cell
    traffic, mesh_spec = cell.traffic, cell.traffic.get("mesh")
    if traffic["optimizer"] != "AdamW":
        raise ValueError(f"optimizer {traffic['optimizer']!r}")
    mesh = None
    if mesh_spec:
        from paddle_tpu.distributed import fleet
        from paddle_tpu.distributed.strategy import DistributedStrategy

        strategy = DistributedStrategy()
        strategy.hybrid_configs = {k: int(mesh_spec[k]) for k in
                                   ("dp_degree", "mp_degree", "pp_degree", "sharding_degree")}
        if int(mesh_spec["sharding_degree"]) > 1:
            strategy.sharding = True
            strategy.sharding_configs = {"sharding_stage": int(mesh_spec["sharding_stage"])}
        if traffic["amp_level"]:
            strategy.amp = True
            strategy.amp_configs = {"level": traffic["amp_level"], "dtype": "bfloat16"}
        fleet.init(is_collective=True, strategy=strategy, devices=list(devices))
        mesh = fleet.mesh
    model = cell.family.build_model(cell.config, records.seed, cell.config["training"]["param_dtype"], mesh=mesh)
    opt = paddle.optimizer.AdamW(learning_rate=float(traffic["learning_rate"]), parameters=model.parameters())
    if mesh is None:
        from paddle_tpu.jit import TrainStep

        step = TrainStep(model, opt, GPTPretrainingCriterion(), amp_level=traffic["amp_level"])
        return step, lambda a: jax.device_put(a, devices[0])
    step = fleet.distributed_step(model, opt, GPTPretrainingCriterion())
    # the eager model's own copy of the weights would sit beside the sharded
    # state for the whole run; the step never reads it again
    cell.family.drop_eager_weights(model)
    return step, fleet.shard_batch


def run(records, devices, *, process_start, trace_on, trace_dir):
    cell = records.cell
    traffic, vocab = cell.traffic, int(cell.config["vocab_size"])
    batch, seq = int(traffic["batch"]), int(traffic["seq"])
    records.tokens_per_step = batch * seq
    step, place = build_step(records, devices)
    ids = np.random.default_rng([records.seed, 3]).integers(
        0, vocab, (int(traffic["distinct_batches"]), batch, seq + 1)).astype(np.int32)
    batches = [(place(b[:, :-1]), place(b[:, 1:])) for b in ids]   # next-token: labels are inputs shifted

    def one(k):
        x, y = batches[k % len(batches)]
        t0 = clock()
        with _trace.annotate("bench.step", trace_on):
            loss = float(step(x, y)["loss"])     # host transfer: the step is done
        t1 = clock()
        records.step_end.append(t1)
        records.step_seconds.append(t1 - t0)
        records.step_loss.append(loss)
        return t1

    warmup = int(traffic["warmup_steps"])
    for k in range(warmup):
        if k == warmup - 1:   # before the last warm-up step, whose end opens the window
            gc.collect()
            gc.freeze()
        one(k)
    k = warmup
    builds = _builds()
    t_open = records.step_end[-1]
    records.window_open = t_open
    records.setup_s = t_open - process_start
    traced = _trace.TracedPart(trace_on, trace_dir, records, records.seconds)
    inside = 0
    while True:
        end = one(k)
        k += 1
        if end > t_open + records.seconds:
            break
        inside += 1
        traced.after_unit(len(records.step_end) - 1, end)
    traced.finish(len(records.step_end) - 2)
    _, i_close = stats.tick_window(records.step_end, t_open, records.seconds)
    records.window_close = records.step_end[i_close]
    records.compiles_in_window = _builds() - builds
    records.attempted, records.failed = inside, 0
    records.notes.update({"window_steps": inside, "losses": records.step_loss,
                          "longest_step_s": max(records.step_seconds[warmup:])})
    del step, batches
    gc.collect()
    t0 = clock()
    records.check = cell.family.check_training(cell.config, records.seed, ids[0][:, :-1], ids[0][:, 1:],
                                               records.step_loss[0], records.step_loss)
    records.check["seconds"] = clock() - t0
