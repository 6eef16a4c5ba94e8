"""Raw text -> tokenizer -> model -> label, streamed through the
FleetExecutor interceptor pipeline (the reference's serving DAG).

Run:  python examples/serve_text.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.distributed import FleetExecutor, TaskNode
from paddle_tpu.framework import FasterTokenizer, StringTensor

VOCAB = {t: i for i, t in enumerate(
    ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "great", "terrible", "movie", "plot", "acting"])}


def main():
    print(f"device: {paddle.device.describe()}")
    tok = FasterTokenizer(VOCAB)
    emb = paddle.nn.Embedding(len(VOCAB), 16)
    head = paddle.nn.Linear(16, 2)

    def classify(ids):
        import paddle_tpu.nn.functional as F

        h = emb(paddle.to_tensor(ids))
        pooled = F.sequence_pool(h, paddle.to_tensor((ids != 0).sum(-1)), "average")
        return ["negative", "positive"][int(np.argmax(np.asarray(head(pooled).numpy())))]

    fe = FleetExecutor().init([
        TaskNode(lambda s: tok([s], max_seq_len=16)[0], name="tokenize"),
        TaskNode(classify, name="classify"),
    ])
    reqs = StringTensor(["great movie great plot", "terrible acting", "movie plot"])
    for text, label in zip(reqs, fe.run(reqs)):
        print(f"{str(text)!r:<28} -> {label}")


if __name__ == "__main__":
    main()
