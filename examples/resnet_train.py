"""BASELINE config 2 — ResNet image classification.

Exercises the conv/BN kernel path under `paddle.jit.to_static` capture
(one compiled program per train step, BN running stats threaded through
capture) with bf16 autocast. Uses Cifar10 when its files are cached
(<checkout>/.paddle_tpu_cache), otherwise synthetic image data — hermetic
either way.

Run:  python examples/resnet_train.py [--arch resnet18] [--steps 50]
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _common  # noqa: E402

_common.setup()

import paddle_tpu as paddle
from paddle_tpu.io import DataLoader
from paddle_tpu.vision import models as vision_models
from paddle_tpu.vision.datasets import Cifar10
from paddle_tpu.vision.transforms import Normalize


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="resnet18",
                    help="any paddle_tpu.vision.models constructor name")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--amp", action="store_true", help="bf16 autocast")
    args = ap.parse_args()

    net = getattr(vision_models, args.arch)(num_classes=10)
    net = paddle.jit.to_static(net)  # guard-keyed jit capture
    opt = paddle.optimizer.Momentum(learning_rate=args.lr, momentum=0.9,
                                    parameters=net.parameters(),
                                    weight_decay=1e-4)
    loss_fn = paddle.nn.CrossEntropyLoss()

    transform = Normalize(mean=[125.3, 123.0, 113.9],
                          std=[63.0, 62.1, 66.7], data_format="CHW")
    loader = DataLoader(Cifar10(mode="train", transform=transform),
                        batch_size=args.batch_size, shuffle=True)

    net.train()
    step = 0
    t0 = time.perf_counter()
    while step < args.steps:
        for x, y in loader:
            if step >= args.steps:
                break
            with paddle.amp.auto_cast(enable=args.amp, level="O1"):
                logits = net(x)
                loss = loss_fn(logits, y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            step += 1
            if step % 10 == 0:
                dt = time.perf_counter() - t0
                print(f"step {step}  loss {float(loss):.4f}  "
                      f"{step * args.batch_size / dt:.1f} img/s")
    paddle.save(net.state_dict(), "output/resnet.pdparams")


if __name__ == "__main__":
    main()
