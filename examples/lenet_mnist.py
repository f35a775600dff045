"""BASELINE config 1 — LeNet-5 on MNIST (single-device smoke).

Exercises the eager core end to end through the high-level `paddle.Model`
API: autograd, optimizer, DataLoader, metric, checkpoint save/load.
Real MNIST IDX files are picked up from <checkout>/.paddle_tpu_cache/mnist when
present; otherwise the dataset synthesizes MNIST-shaped data so the example
runs hermetically.

Run:  python examples/lenet_mnist.py [--epochs 2] [--batch-size 64]
"""
import argparse
import sys
import os

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _common  # noqa: E402

_common.setup()

import paddle_tpu as paddle
from paddle_tpu.io import DataLoader
from paddle_tpu.metric import Accuracy
from paddle_tpu.vision.datasets import MNIST
from paddle_tpu.vision.models import LeNet
from paddle_tpu.vision.transforms import Normalize


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    args = ap.parse_args()

    transform = Normalize(mean=[127.5], std=[127.5], data_format="CHW")
    train_ds = MNIST(mode="train", transform=transform)
    test_ds = MNIST(mode="test", transform=transform)
    train = DataLoader(train_ds, batch_size=args.batch_size, shuffle=True)
    test = DataLoader(test_ds, batch_size=256)

    model = paddle.Model(LeNet(num_classes=10))
    opt = paddle.optimizer.Adam(learning_rate=args.lr,
                                parameters=model.parameters())
    model.prepare(opt, paddle.nn.CrossEntropyLoss(), Accuracy())
    model.fit(train, epochs=args.epochs, verbose=1)
    print(model.evaluate(test, verbose=0))
    model.save("output/lenet")


if __name__ == "__main__":
    main()
