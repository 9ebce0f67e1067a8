"""ResNet-50 training driver (PaddleClas analog) — ladder config #2.

Run: python examples/train_resnet.py --cpu --arch resnet18 --steps 10
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
import os
import time


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="resnet50",
                   choices=["resnet18", "resnet34", "resnet50"])
    p.add_argument("--batch_size", type=int, default=8)
    p.add_argument("--image_size", type=int, default=64)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--cpu", action="store_true")
    args = p.parse_args()

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.jit import to_static

    paddle.seed(0)
    net = getattr(paddle.vision.models, args.arch)(num_classes=10)
    opt = paddle.optimizer.Momentum(learning_rate=args.lr, momentum=0.9,
                                    parameters=net.parameters(),
                                    weight_decay=1e-4)
    loss_fn = nn.CrossEntropyLoss()

    @to_static
    def step(x, y):
        loss = loss_fn(net(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    rng = np.random.default_rng(0)
    t0 = time.time()
    for i in range(args.steps):
        y = rng.integers(0, 10, args.batch_size)
        x = rng.standard_normal(
            (args.batch_size, 3, args.image_size, args.image_size)) * 0.1
        for b, lab in enumerate(y):  # label-correlated stripe
            x[b, 0, (lab * args.image_size // 10) % args.image_size] += 1.0
        loss = step(paddle.to_tensor(x.astype("float32")),
                    paddle.to_tensor(y))
        img_s = args.batch_size * (i + 1) / max(time.time() - t0, 1e-9)
        print(f"step {i:3d} loss {float(loss):.4f} images/s {img_s:,.1f}")


if __name__ == "__main__":
    main()
