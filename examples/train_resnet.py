#!/usr/bin/env python
"""ResNet image classification on synthetic data (BASELINE config 2).

    python examples/train_resnet.py --small --steps 10   # resnet18/CPU
    python examples/train_resnet.py                      # resnet50/TPU
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--batch", type=int, default=None)
    args = ap.parse_args()

    if args.small:
        import jax
        jax.config.update("jax_platforms", "cpu")

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.core.flags import apply_compile_cache
    from paddle_tpu.vision.models import resnet18, resnet50
    from paddle_tpu.static import TrainStep

    apply_compile_cache()
    paddle.seed(0)
    if args.small:
        model, batch, size = resnet18(num_classes=10), args.batch or 4, 32
    else:
        model, batch, size = resnet50(num_classes=1000), \
            args.batch or 64, 224
    opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                    parameters=model.parameters(),
                                    weight_decay=1e-4)
    step = TrainStep(model, lambda out, y: F.cross_entropy(out, y), opt,
                     amp_level="O1", amp_dtype="bfloat16")
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(
        rng.randn(batch, 3, size, size).astype(np.float32))
    y = paddle.to_tensor(rng.randint(0, 10, (batch,)).astype(np.int32))
    print("compiling...", flush=True)
    loss0 = float(step(x, y).item())
    t0 = time.perf_counter()
    for _ in range(args.steps):
        loss = step(x, y)
    last = float(loss.item())
    dt = time.perf_counter() - t0
    print(f"loss {loss0:.4f} -> {last:.4f} | "
          f"{batch * args.steps / dt:,.1f} images/s")


if __name__ == "__main__":
    main()
