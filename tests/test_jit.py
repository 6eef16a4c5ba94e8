"""jit path tests: TrainStep full-step compile, to_static, EvalStep, save."""
import os
import tempfile

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.jit import EvalStep, InputSpec, TrainStep, to_static


def _rand(*shape):
    return np.random.randn(*shape).astype("float32")


def test_train_step_converges():
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 32), nn.GELU(), nn.Linear(32, 4))
    step = TrainStep(net, paddle.optimizer.Adam(learning_rate=1e-2), nn.CrossEntropyLoss())
    x = _rand(16, 8)
    y = np.random.randint(0, 4, 16)
    losses = [float(step(x, y)["loss"]) for _ in range(30)]
    assert losses[-1] < losses[0] * 0.5


def test_train_step_matches_eager():
    """One jit step == one eager step (same SGD math)."""
    paddle.seed(7)
    net = nn.Linear(4, 2)
    x, y = _rand(8, 4), _rand(8, 2)

    # eager
    import copy

    w0, b0 = net.weight.numpy().copy(), net.bias.numpy().copy()
    opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=net.parameters())
    loss = nn.MSELoss()(net(paddle.to_tensor(x)), paddle.to_tensor(y))
    loss.backward()
    opt.step()
    eager_w = net.weight.numpy().copy()

    # jit from same init
    net.weight.set_value(w0)
    net.bias.set_value(b0)
    step = TrainStep(net, paddle.optimizer.SGD(learning_rate=0.1), nn.MSELoss())
    step(x, y)
    step.sync_to_model()
    np.testing.assert_allclose(net.weight.numpy(), eager_w, atol=1e-5)


def test_train_step_updates_batchnorm_buffers():
    net = nn.Sequential(nn.Linear(4, 8), nn.BatchNorm1D(8), nn.Linear(8, 2))
    step = TrainStep(net, paddle.optimizer.SGD(learning_rate=0.01), nn.MSELoss())
    x, y = _rand(16, 4) + 3.0, _rand(16, 2)
    step(x, y)
    mean_after = step.state["buffers"]["1._mean"]
    assert not np.allclose(np.asarray(mean_after), 0.0)


def test_train_step_lr_schedule_traced():
    from paddle_tpu.optimizer import lr as lr_mod

    net = nn.Linear(2, 2)
    sch = lr_mod.StepDecay(learning_rate=0.1, step_size=2, gamma=0.5)
    step = TrainStep(net, paddle.optimizer.SGD(learning_rate=sch), nn.MSELoss())
    x, y = _rand(4, 2), _rand(4, 2)
    lrs = [float(step(x, y)["lr"]) for _ in range(4)]
    np.testing.assert_allclose(lrs, [0.1, 0.1, 0.05, 0.05], rtol=1e-6)


def test_train_step_remat():
    net = nn.Sequential(nn.Linear(8, 16), nn.GELU(), nn.Linear(16, 2))
    step = TrainStep(net, paddle.optimizer.Adam(learning_rate=1e-2), nn.MSELoss(), remat=True)
    x, y = _rand(4, 8), _rand(4, 2)
    l0 = float(step(x, y)["loss"])
    for _ in range(10):
        l1 = float(step(x, y)["loss"])
    assert l1 < l0


def test_eval_step():
    net = nn.Sequential(nn.Linear(4, 3), nn.Softmax())
    net.eval()
    es = EvalStep(net)
    x = _rand(5, 4)
    out = es(x)
    np.testing.assert_allclose(out.numpy().sum(-1), 1.0, rtol=1e-5)


def test_to_static_function():
    @to_static
    def f(a, b):
        return paddle.tanh(a) * b + 1.0

    a, b = _rand(3, 3), _rand(3, 3)
    got = f(paddle.to_tensor(a), paddle.to_tensor(b))
    np.testing.assert_allclose(got.numpy(), np.tanh(a) * b + 1.0, atol=1e-6)


def test_to_static_layer():
    net = nn.Sequential(nn.Linear(4, 2))
    net.eval()
    fast = to_static(net)
    x = _rand(3, 4)
    np.testing.assert_allclose(fast(paddle.to_tensor(x)).numpy(), net(paddle.to_tensor(x)).numpy(), atol=1e-6)


def test_jit_save_exports_stablehlo():
    import paddle_tpu.jit as jit

    net = nn.Linear(4, 2)
    d = tempfile.mkdtemp()
    path = os.path.join(d, "model")
    jit.save(net, path, input_spec=[InputSpec([1, 4])])
    assert os.path.exists(path + ".pdparams")
    assert os.path.exists(path + ".pdmodel")  # executable jax.export artifact
    loaded = jit.load(path)  # TranslatedLayer (reference io.py:1137 parity)
    x = paddle.ones([1, 4])
    np.testing.assert_allclose(loaded(x).numpy(), net(x).numpy(), rtol=1e-5, atol=1e-6)
    # without a .pdmodel, load falls back to the bare state dict
    os.remove(path + ".pdmodel")
    state = jit.load(path)
    assert "weight" in state


def test_train_step_checkpoint_roundtrip():
    from paddle_tpu.distributed import checkpoint as ckpt

    net = nn.Linear(4, 2)
    step = TrainStep(net, paddle.optimizer.Adam(learning_rate=1e-2), nn.MSELoss())
    x, y = _rand(4, 4), _rand(4, 2)
    step(x, y)
    d = os.path.join(tempfile.mkdtemp(), "ck")
    ckpt.save_train_step(step, d)

    net2 = nn.Linear(4, 2)
    step2 = TrainStep(net2, paddle.optimizer.Adam(learning_rate=1e-2), nn.MSELoss())
    ckpt.load_train_step(step2, d)
    np.testing.assert_allclose(np.asarray(step2.state["params"]["weight"]), np.asarray(step.state["params"]["weight"]))
    assert int(step2.state["step"]) == 1
    # resumes cleanly
    step2(x, y)


def test_jit_save_preserves_int_input_dtype():
    """Regression: InputSpec dtype (int32 ids) must survive export."""
    import paddle_tpu.jit as jit

    emb = nn.Embedding(10, 4)
    emb.eval()
    path = os.path.join(tempfile.mkdtemp(), "emb")
    jit.save(emb, path, input_spec=[InputSpec([None, 8], "int32", name="ids")])
    loaded = jit.load(path)
    ids = np.random.randint(0, 10, (3, 8)).astype("int32")
    np.testing.assert_allclose(
        loaded(paddle.to_tensor(ids)).numpy(),
        emb(paddle.to_tensor(ids)).numpy(), rtol=1e-6)


def test_train_step_amp_o2_converges():
    """bf16-compute/f32-master AMP step trains (the training cells' path)."""
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 32), nn.GELU(), nn.Linear(32, 4))
    step = TrainStep(net, paddle.optimizer.Adam(learning_rate=1e-2),
                     nn.CrossEntropyLoss(), amp_level="O2")
    x = _rand(16, 8)
    y = np.random.randint(0, 4, 16)
    losses = [float(step(x, y)["loss"]) for _ in range(30)]
    assert losses[-1] < losses[0] * 0.5
    # master params stayed f32
    assert all(str(a.dtype) == "float32" for a in step.state["params"].values())


def test_dygraph_static_parity_resnet():
    """The reference's canonical d2s test (dygraph_to_static/test_resnet.py):
    the SAME ResNet runs eager, @to_static and through a recorded static
    Program; all three outputs match."""
    from paddle_tpu.vision.models import resnet18

    paddle.seed(7)
    m = resnet18(num_classes=10)
    m.eval()
    x_np = np.random.default_rng(0).standard_normal((2, 3, 32, 32)).astype("float32")
    x = paddle.to_tensor(x_np)

    eager = np.asarray(m(x).numpy())

    jitted = paddle.jit.to_static(m)
    np.testing.assert_allclose(np.asarray(jitted(x).numpy()), eager, rtol=2e-4, atol=2e-4)

    # static Program capture + Executor run
    from paddle_tpu import static

    main = static.Program()
    with static.program_guard(main):
        inp = static.data("x", [2, 3, 32, 32], "float32")
        out = m(inp)
    exe = static.Executor()
    (got,) = exe.run(main, feed={"x": x_np}, fetch_list=[out])
    np.testing.assert_allclose(np.asarray(got), eager, rtol=2e-4, atol=2e-4)
