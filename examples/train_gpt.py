"""Fleet hybrid GPT pretraining: the reference's
fleetrun + DistributedStrategy flow, TPU-native.

Run:  python examples/train_gpt.py    (needs 8 devices: 8 chips, or on the CPU
      the 8 virtual devices the XLA_FLAGS line below asks for — that flag
      does nothing on a TPU)
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# must land before the first jax backend init: 8 virtual devices on CPU
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
    " --xla_force_host_platform_device_count=8"

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.distributed import fleet
from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining, GPTPretrainingCriterion


def main():
    strategy = paddle.distributed.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 2, "pp_degree": 2,
                               "sharding_degree": 2}
    print(f"device: {paddle.device.describe()}")
    strategy.amp = paddle.device.is_tpu()  # bf16 allreduce promotion trips XLA's CPU backend
    strategy.amp_configs = {"level": "O2"}
    strategy.pipeline_configs = {"accumulate_steps": 4, "schedule": "1f1b"}
    fleet.init(is_collective=True, strategy=strategy)

    cfg = GPTConfig.tiny()
    model = GPTForPretraining(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=3e-4, parameters=model.parameters())
    step = fleet.distributed_step(model, opt, GPTPretrainingCriterion())

    rng = np.random.default_rng(0)
    for it in range(5):
        ids = rng.integers(0, cfg.vocab_size, (8, 64)).astype("int32")
        metrics = step(paddle.to_tensor(ids), paddle.to_tensor(ids))
        print(f"iter {it} loss {float(metrics['loss']):.4f} lr {float(metrics['lr']):.2e}")

    # outputs land under the gitignored examples/_out (override with
    # PADDLE_TPU_EXAMPLE_OUT) so test/bench runs leave `git status` clean
    out_root = os.environ.get(
        "PADDLE_TPU_EXAMPLE_OUT",
        os.path.join(os.path.dirname(os.path.abspath(__file__)), "_out"))
    ckpt_dir = os.path.join(out_root, "gpt_ckpt")
    paddle.distributed.checkpoint.save_train_step(step, ckpt_dir)
    print("checkpoint saved to", ckpt_dir)


if __name__ == "__main__":
    sys.exit(main())
