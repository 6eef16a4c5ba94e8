"""hapi Model.fit -> QAT -> int8 Predictor: the train-to-deploy loop.

Run:  python examples/finetune_classifier.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.vision.transforms as T
from paddle_tpu.inference import Config, create_predictor
from paddle_tpu.quantization import ImperativeQuantAware


def make_data(n=256):
    rng = np.random.default_rng(0)
    xs = rng.standard_normal((n, 1, 12, 12)).astype("float32")
    ys = (xs.mean((1, 2, 3)) > 0).astype("int64")
    return xs, ys


def main():
    print(f"device: {paddle.device.describe()}")
    pipeline = T.Compose([T.Normalize(mean=[0.0], std=[1.0])])
    xs, ys = make_data()
    xs = np.stack([pipeline(x) for x in xs])

    net = paddle.nn.Sequential(
        paddle.nn.Conv2D(1, 8, 3, padding=1), paddle.nn.ReLU(),
        paddle.nn.Flatten(), paddle.nn.Linear(8 * 12 * 12, 2))
    qat = ImperativeQuantAware()
    qat.quantize(net)

    model = paddle.Model(net)
    model.prepare(paddle.optimizer.Adam(learning_rate=1e-3, parameters=net.parameters()),
                  paddle.nn.CrossEntropyLoss(), paddle.metric.Accuracy())
    class ArrayDataset(paddle.io.Dataset):
        def __len__(self):
            return len(xs)

        def __getitem__(self, i):
            return xs[i], ys[i]

    model.fit(ArrayDataset(), epochs=2, batch_size=32, verbose=1,
              callbacks=[paddle.callbacks.EarlyStopping(monitor="loss", patience=3)])

    # gitignored output dir (override with PADDLE_TPU_EXAMPLE_OUT) so
    # test/bench runs leave `git status` clean
    out_root = os.environ.get(
        "PADDLE_TPU_EXAMPLE_OUT",
        os.path.join(os.path.dirname(os.path.abspath(__file__)), "_out"))
    os.makedirs(out_root, exist_ok=True)
    prefix = os.path.join(out_root, "clf_int8")
    net.eval()
    qat.save_quantized_model(net, prefix,
                             input_spec=[paddle.static.InputSpec([None, 1, 12, 12], "float32")])
    pred = create_predictor(Config(prefix))
    (probs,) = pred.run([xs[:4]])
    print("served int8 logits:", np.asarray(probs).round(3))


if __name__ == "__main__":
    main()
