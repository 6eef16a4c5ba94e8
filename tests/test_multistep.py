"""Single-dispatch multi-step training: TrainStep.run_steps / MultiStepRunner
+ the K-stack DataLoader feed path (the lax.scan production-trainer idiom).

Correctness contract: K scanned steps are BITWISE identical to K individual
TrainStep calls on CPU — same step fn, same per-step rng fold-in on the
carried counter — for params, optimizer state, rng, and metrics. Dispatch
contract: one run_steps(k) call is exactly ONE jit dispatch (the
amortization invariant, pinned against regressions via the profiler
counters).
"""
import numpy as np
import pytest

import jax

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import profiler
from paddle_tpu.jit import MultiStepRunner, TrainStep


def _make_step(seed=0):
    paddle.seed(seed)
    net = nn.Sequential(nn.Linear(8, 16), nn.GELU(), nn.Linear(16, 4))
    return TrainStep(net, paddle.optimizer.Adam(learning_rate=1e-2),
                     nn.CrossEntropyLoss())


def _batches(n, rng_seed=0):
    rng = np.random.default_rng(rng_seed)
    return [(rng.normal(size=(4, 8)).astype("float32"),
             rng.integers(0, 4, 4).astype("int64")) for _ in range(n)]


def _state_leaves(state):
    out = []
    for leaf in jax.tree_util.tree_leaves(state):
        if hasattr(leaf, "dtype") and jax.dtypes.issubdtype(leaf.dtype, jax.dtypes.prng_key):
            leaf = jax.random.key_data(leaf)
        out.append(np.asarray(leaf))
    return out


def test_run_steps_bitwise_matches_per_step():
    """K scanned steps == K individual steps, bit for bit (params, opt
    state, step counter, rng, losses)."""
    batches = _batches(4)
    a = _make_step()
    per_step_losses = [float(a(x, y)["loss"]) for x, y in batches]

    b = _make_step()
    metrics = b.run_steps(batches)
    fused_losses = [float(v) for v in np.asarray(metrics["loss"]._value)]

    assert per_step_losses == fused_losses  # bitwise, not allclose
    for la, lb in zip(_state_leaves(a.state), _state_leaves(b.state)):
        np.testing.assert_array_equal(la, lb)


def test_run_steps_prestacked_matches():
    """The pre-stacked [k, ...] input form (DataLoader fuse_steps output)
    produces the same state as the per-batch list form."""
    batches = _batches(4)
    a = _make_step()
    a.run_steps(batches)
    b = _make_step()
    stacked = (np.stack([x for x, _ in batches]), np.stack([y for _, y in batches]))
    b.run_steps(stacked, k=4)
    for la, lb in zip(_state_leaves(a.state), _state_leaves(b.state)):
        np.testing.assert_array_equal(la, lb)


def test_run_steps_prestacked_wrong_lead_dim_raises():
    step = _make_step()
    stacked = (np.zeros((3, 4, 8), "float32"), np.zeros((3, 4), "int64"))
    with pytest.raises(ValueError, match="leading dim"):
        step.run_steps(stacked, k=4)


def test_run_steps_single_dispatch_counter():
    """The amortization invariant: one run_steps(k=4) call = exactly 1 jit
    dispatch and 4 steps on the profiler counters."""
    step = _make_step()
    batches = _batches(4)
    profiler.reset_counters("train_step.")
    step.run_steps(batches)
    counts = profiler.counters("train_step.")
    assert counts["train_step.dispatches"] == 1
    assert counts["train_step.steps"] == 4

    profiler.reset_counters("train_step.")
    for x, y in batches:
        step(x, y)
    counts = profiler.counters("train_step.")
    assert counts["train_step.dispatches"] == 4
    assert counts["train_step.steps"] == 4


def test_multi_step_runner_groups_and_matches():
    batches = _batches(6)
    a = _make_step()
    for x, y in batches:
        a(x, y)
    b = _make_step()
    outs = list(MultiStepRunner(b, 3).run(iter(batches)))
    assert len(outs) == 2
    assert np.asarray(outs[0]["loss"]._value).shape == (3,)
    for la, lb in zip(_state_leaves(a.state), _state_leaves(b.state)):
        np.testing.assert_array_equal(la, lb)


def test_multi_step_runner_trailing_partial_group():
    step = _make_step()
    outs = list(MultiStepRunner(step, 4).run(iter(_batches(6))))
    assert [np.asarray(o["loss"]._value).shape[0] for o in outs] == [4, 2]


def test_dataloader_fuse_steps_stacks():
    from paddle_tpu.io import DataLoader, TensorDataset

    xs = np.arange(64, dtype="float32").reshape(16, 4)
    ys = np.arange(16, dtype="int64")
    ds = TensorDataset([paddle.to_tensor(xs), paddle.to_tensor(ys)])
    stacks = list(DataLoader(ds, batch_size=2, fuse_steps=4))
    assert len(stacks) == 2
    assert np.asarray(stacks[0][0]).shape == (4, 2, 4)
    assert np.asarray(stacks[0][1]).shape == (4, 2)
    # stacking preserves order: flattening the stacks recovers the dataset
    flat = np.concatenate([np.asarray(s[0]).reshape(-1, 4) for s in stacks])
    np.testing.assert_array_equal(flat, xs)


def test_dataloader_fuse_steps_ragged_remainder():
    """A drop_last=False remainder batch cannot join a stack: it is flushed
    as its own (smaller) group instead of crashing np.stack."""
    from paddle_tpu.io import DataLoader, TensorDataset

    xs = np.arange(64, dtype="float32").reshape(16, 4)
    ds = TensorDataset([paddle.to_tensor(xs)])
    lead = [np.asarray(s[0]).shape[:2] for s in DataLoader(ds, batch_size=3, fuse_steps=2)]
    # 5 full batches of 3 + remainder of 1: [2x3, 2x3, 1x3(flush), 1x1]
    assert lead == [(2, 3), (2, 3), (1, 3), (1, 1)]


def test_dataloader_fuse_steps_feeds_run_steps():
    from paddle_tpu.io import DataLoader, TensorDataset

    rng = np.random.default_rng(3)
    xs = rng.normal(size=(16, 8)).astype("float32")
    ys = rng.integers(0, 4, 16).astype("int64")
    ds = TensorDataset([paddle.to_tensor(xs), paddle.to_tensor(ys)])

    a = _make_step()
    for xb, yb in DataLoader(ds, batch_size=4):
        a(np.asarray(xb), np.asarray(yb))
    b = _make_step()
    for stack in DataLoader(ds, batch_size=4, fuse_steps=2):
        b.run_steps((stack[0], stack[1]), k=np.asarray(stack[0]).shape[0])
    for la, lb in zip(_state_leaves(a.state), _state_leaves(b.state)):
        np.testing.assert_array_equal(la, lb)


def test_stack_batches_standalone():
    from paddle_tpu.io import stack_batches

    it = iter([(np.full((2, 4), i, "float32"), np.full((2,), i, "int64"))
               for i in range(5)])
    stacks = list(stack_batches(it, 2, to_device=False))
    assert [s[0].shape for s in stacks] == [(2, 2, 4), (2, 2, 4), (1, 2, 4)]
    np.testing.assert_array_equal(stacks[1][1], [[2, 2], [3, 3]])


def test_run_steps_amortization_speedup():
    """On the tiny-GPT config the counters show exactly 1 dispatch per 8 steps
    of ``run_steps(k=8)``. What that buys in steps per second is the chip's to
    say: a ratio of two CPU timings is not a speed."""
    from paddle_tpu.models.gpt import (GPTConfig, GPTForPretraining,
                                       GPTPretrainingCriterion)

    cfg = GPTConfig.tiny(vocab_size=128, hidden_size=32, num_layers=1,
                         num_heads=2, max_seq_len=32)
    paddle.seed(0)
    model = GPTForPretraining(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters())
    step = TrainStep(model, opt, GPTPretrainingCriterion())
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 8)).astype("int32")
    K, N = 8, 96
    stacked = (np.stack([ids] * K), np.stack([ids] * K))

    profiler.reset_counters("train_step.")
    for _ in range(N // K):
        step.run_steps(stacked, k=K)
    jax.block_until_ready(step.state["params"])

    counts = profiler.counters("train_step.")
    assert counts["train_step.steps"] == N
    assert counts["train_step.dispatches"] * K == counts["train_step.steps"]
