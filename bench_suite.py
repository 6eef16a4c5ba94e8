"""Secondary benchmark suite for the BASELINE.md north-star configs.

``bench.py`` stays the driver's single-line flagship metric; this suite
measures the other configs on demand:

    python bench_suite.py mnist            # LeNet eager + jit steps/sec
    python bench_suite.py resnet50 [batch] # jit train step images/sec (AMP O2)
    python bench_suite.py bert             # BERT-base MLM tokens/sec (AMP O2)
    python bench_suite.py decode [batch]   # GPT-medium generate() tokens/sec

Each subcommand prints one JSON line that names the device it ran on
(whatever ``JAX_PLATFORMS`` gives it; sizes never change with the device).
Reference analog: the external benchmark suite cloned by
tools/ci_model_benchmark.sh:50.
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np


def _time_steps(fn, warmup=3, iters=20, sync=None):
    for _ in range(warmup):
        out = fn()
    if sync:
        sync(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    if sync:
        sync(out)
    return iters / (time.perf_counter() - t0)


def bench_mnist():
    import paddle_tpu as paddle
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.lenet import LeNet

    paddle.seed(0)
    m = LeNet()
    opt = paddle.optimizer.Momentum(learning_rate=0.01, parameters=m.parameters())
    loss_fn = paddle.nn.CrossEntropyLoss()
    x = paddle.to_tensor(np.random.default_rng(0).normal(size=(64, 1, 28, 28)).astype("float32"))
    y = paddle.to_tensor(np.random.default_rng(1).integers(0, 10, (64,)).astype("int64"))

    def eager_step():
        loss = loss_fn(m(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    eager_sps = _time_steps(eager_step, warmup=3, iters=20, sync=float)
    step = TrainStep(m, opt, loss_fn)
    jit_sps = _time_steps(lambda: step(x, y), warmup=3, iters=200, sync=lambda o: float(o["loss"]))
    return {"metric": "mnist_lenet_steps_per_sec", "eager": round(eager_sps, 2), "jit": round(jit_sps, 1), "batch": 64}


def bench_resnet50(batch=128):
    import paddle_tpu as paddle
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.vision.models import resnet50

    paddle.seed(0)
    m = resnet50(num_classes=1000)
    opt = paddle.optimizer.Momentum(learning_rate=0.1, parameters=m.parameters())
    loss_fn = paddle.nn.CrossEntropyLoss()
    x = paddle.to_tensor(np.random.default_rng(0).normal(size=(batch, 3, 224, 224)).astype("float32"))
    y = paddle.to_tensor(np.random.default_rng(1).integers(0, 1000, (batch,)).astype("int64"))
    step = TrainStep(m, opt, loss_fn, amp_level="O2")
    sps = _time_steps(lambda: step(x, y), warmup=3, iters=20, sync=lambda o: float(o["loss"]))
    return {"metric": "resnet50_images_per_sec", "value": round(batch * sps, 1), "batch": batch, "amp": "O2"}


def bench_bert():
    import paddle_tpu as paddle
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.bert import BertConfig, BertForPretraining, BertPretrainingCriterion

    paddle.seed(0)
    cfg = BertConfig()
    m = BertForPretraining(cfg)
    crit = BertPretrainingCriterion()
    opt = paddle.optimizer.AdamW(learning_rate=1e-4, parameters=m.parameters())
    b, s = 16, 512

    def loss_fn(outs, mlm_labels, nsp_labels):
        mlm, nsp = outs
        return crit(mlm, nsp, mlm_labels, nsp_labels)

    ids = paddle.to_tensor(np.random.default_rng(0).integers(0, cfg.vocab_size, (b, s)).astype("int32"))
    labels = np.full((b, s), -100, "int32")
    labels[:, :64] = np.random.default_rng(1).integers(0, cfg.vocab_size, (b, 64))
    mlm_y = paddle.to_tensor(labels)
    nsp_y = paddle.to_tensor(np.random.default_rng(2).integers(0, 2, (b,)).astype("int64"))
    step = TrainStep(m, opt, loss_fn, amp_level="O2")
    sps = _time_steps(lambda: step(ids, (mlm_y, nsp_y)), warmup=3, iters=15, sync=lambda o: float(o["loss"]))
    return {"metric": "bert_base_mlm_tokens_per_sec", "value": round(b * s * sps), "batch": b, "seq": s, "amp": "O2"}


def bench_decode(batch=8):
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=50304, hidden_size=1024, num_layers=16, num_heads=16, max_seq_len=1024)
    m = GPTForPretraining(cfg)
    m.eval()
    for _, p in m.named_parameters():
        p._value = p._value.astype(jnp.bfloat16)
    prompt = paddle.to_tensor(np.random.default_rng(0).integers(0, cfg.vocab_size, (batch, 128)).astype("int32"))
    new = 384
    _ = m.generate(prompt, max_new_tokens=new).numpy()
    t0 = time.perf_counter()
    for _ in range(3):
        out = m.generate(prompt, max_new_tokens=new)
    _ = out.numpy()
    dt = (time.perf_counter() - t0) / 3
    return {"metric": "gpt_decode_tokens_per_sec", "value": round(batch * new / dt), "batch": batch, "prompt": 128, "new_tokens": new, "dtype": "bf16"}


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "mnist"
    arg = int(sys.argv[2]) if len(sys.argv) > 2 else None
    if which == "mnist":
        out = bench_mnist()
    elif which == "resnet50":
        out = bench_resnet50(arg or 128)
    elif which == "bert":
        out = bench_bert()
    elif which == "decode":
        out = bench_decode(arg or 8)
    else:
        raise SystemExit(f"unknown benchmark {which!r}")
    import paddle_tpu as paddle

    out["device"] = paddle.device.describe()
    print(json.dumps(out))


if __name__ == "__main__":
    main()
