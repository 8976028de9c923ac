"""MNIST LeNet via the high-level Model API (BASELINE config 1).

Runs on whatever accelerator JAX sees (TPU or CPU). The dataset falls back
to a deterministic synthetic corpus when no local IDX files are given —
this environment has no network egress.

    python examples/train_mnist.py
"""

import os, sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.core.chip import enable_compile_cache
from paddle_tpu.vision.datasets import MNIST
from paddle_tpu.vision.models import LeNet


def main():
    enable_compile_cache()
    paddle.seed(0)
    model = paddle.Model(LeNet(10))
    model.prepare(optimizer.Adam(1e-3, parameters=model.parameters()),
                  nn.CrossEntropyLoss(),
                  metrics=[paddle.metric.Accuracy()])
    model.fit(MNIST(mode="train", synthetic_size=2048), epochs=2,
              batch_size=64)
    print(model.evaluate(MNIST(mode="test", synthetic_size=512),
                         batch_size=64))


if __name__ == "__main__":
    main()
