"""Tensor grad hooks + eager DataParallel grad sync.

Parity: varbase_patch_methods.py:202 register_hook,
imperative/reducer.cc:127 (grad all-reduce during backward).
"""
import os
import subprocess
import sys
import tempfile
import textwrap

import numpy as np
import pytest

pytestmark = pytest.mark.slow

import paddle_tpu as paddle

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_hook_fires_and_can_modify_grad():
    x = paddle.to_tensor(np.array([1.0, 2.0], "float32"), stop_gradient=False)
    seen = []

    def hook(g):
        seen.append(g.numpy().copy())
        return g * 2.0

    x.register_hook(hook)
    y = (x * 3.0).sum()
    y.backward()
    assert len(seen) == 1
    np.testing.assert_allclose(seen[0], [3.0, 3.0])
    np.testing.assert_allclose(x.grad.numpy(), [6.0, 6.0])  # doubled by hook


def test_hook_on_intermediate_tensor_and_order():
    x = paddle.to_tensor(np.array([2.0], "float32"), stop_gradient=False)
    order = []
    h = x * 2.0          # intermediate
    h.register_hook(lambda g: order.append("intermediate"))
    x.register_hook(lambda g: order.append("leaf"))
    ((h * h).sum()).backward()
    # cotangent reaches the intermediate before propagating to the leaf
    assert order == ["intermediate", "leaf"]
    np.testing.assert_allclose(x.grad.numpy(), [16.0])  # d/dx (2x)^2 = 8x


def test_hook_remove_handle():
    x = paddle.to_tensor(np.array([1.0], "float32"), stop_gradient=False)
    calls = []
    handle = x.register_hook(lambda g: calls.append(1))
    handle.remove()
    (x * 2.0).sum().backward()
    assert calls == []


def test_hook_fires_once_on_accumulated_grad():
    # a tensor consumed twice: the hook sees the final accumulated grad once
    # (GradNodeAccumulation semantics)
    x = paddle.to_tensor(np.array([1.0], "float32"), stop_gradient=False)
    calls = []
    x.register_hook(lambda g: calls.append(g.numpy().copy()))
    ((x * 1.0) + (x * 2.0)).sum().backward()
    assert len(calls) == 1
    np.testing.assert_allclose(calls[0], [3.0])
    np.testing.assert_allclose(x.grad.numpy(), [3.0])


def test_hook_on_stop_gradient_raises():
    x = paddle.to_tensor(np.array([1.0], "float32"))
    with pytest.raises(RuntimeError):
        x.register_hook(lambda g: None)


def test_data_parallel_single_process_passthrough():
    from paddle_tpu.distributed.parallel import DataParallel

    m = paddle.nn.Linear(4, 2)
    dp = DataParallel(m)
    assert not dp._grad_sync  # single controller: no hooks registered
    x = paddle.to_tensor(np.ones((2, 4), "float32"))
    loss = dp(x).sum()
    loss.backward()
    assert m.weight.grad is not None


DDP_SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ.pop("PYTHONPATH", None)
    sys.path.insert(0, "__REPO__")
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.distributed.env import init_parallel_env, get_rank
    from paddle_tpu.distributed.parallel import DataParallel

    init_parallel_env()
    rank = get_rank()
    paddle.seed(0)  # same init on both ranks
    m = paddle.nn.Linear(4, 1)
    dp = DataParallel(m)
    assert dp._grad_sync
    # each rank trains on different data; hooks must average the grads
    x = paddle.to_tensor(np.full((2, 4), rank + 1.0, "float32"))
    loss = dp(x).sum()
    loss.backward()
    g = m.weight.grad.numpy()
    # rank0 grad pre-sync: 2*1=2 per element; rank1: 2*2=4; mean = 3
    np.testing.assert_allclose(g, np.full((4, 1), 3.0), rtol=1e-6)
    open(f"ddp_ok.{rank}", "w").write("ok")
""").replace("__REPO__", REPO)


def test_data_parallel_two_process_grad_sync():
    with tempfile.TemporaryDirectory() as d:
        script = os.path.join(d, "train.py")
        open(script, "w").write(DDP_SCRIPT)
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = ""
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch", "--nnodes", "1", "--nproc_per_node", "2", "--master", "127.0.0.1:49561", script],
            env=env, cwd=d, capture_output=True, text=True, timeout=180)
        assert r.returncode == 0, r.stdout + r.stderr
        assert os.path.exists(os.path.join(d, "ddp_ok.0"))
        assert os.path.exists(os.path.join(d, "ddp_ok.1"))


def test_bucket_reducer_plan_and_unused_param_error():
    """Bucket plan: fixed at init, grouped by dtype, byte-budgeted; missing
    grads error unless find_unused_parameters=True (reducer.cc semantics)."""
    from paddle_tpu.distributed.parallel import _BucketReducer

    paddle.seed(0)
    big = paddle.nn.Linear(256, 256)   # 256KB fp32 weight
    params = [p for p in big.parameters() if not p.stop_gradient]
    r = _BucketReducer(params, comm_buffer_mb=0.1)  # 100KB budget → splits
    assert len(r.buckets) >= 2
    assert all(dt == "float32" for dt, _ in r.buckets)
    planned = [p for _, ps in r.buckets for p in ps]
    assert len(planned) == len(params)

    # one param has a grad, another doesn't → strict mode raises
    x = paddle.to_tensor(np.ones((2, 256), "float32"))
    big(x).sum().backward()
    big.bias.grad = None
    with pytest.raises(RuntimeError, match="no gradient"):
        r.reduce(find_unused_parameters=False)
    # permissive mode runs (world=1 mesh: pmean over a single process)
    r.reduce(find_unused_parameters=True)


SPAWN_HELPER = """
import os, sys
sys.path.insert(0, {repo!r})
"""


def _spawn_target(out_dir):
    # runs in a spawned subprocess: record rank/world from the env
    import os

    rank = os.environ["PADDLE_TRAINER_ID"]
    world = os.environ["PADDLE_TRAINERS_NUM"]
    open(os.path.join(out_dir, f"rank{rank}"), "w").write(world)


def test_spawn_multiprocess():
    import tempfile

    from paddle_tpu.distributed.parallel import spawn

    with tempfile.TemporaryDirectory() as d:
        spawn(_spawn_target, args=(d,), nprocs=2, join=True)
        assert open(os.path.join(d, "rank0")).read() == "2"
        assert open(os.path.join(d, "rank1")).read() == "2"

    # nprocs=-1 is a direct call (single-controller canonical path)
    hit = []
    spawn(lambda: hit.append(1), nprocs=-1)
    assert hit == [1]
