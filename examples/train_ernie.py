#!/usr/bin/env python
"""ERNIE/BERT pretraining on synthetic data (BASELINE config 3).

One compiled train step (fwd + loss + bwd + AdamW + AMP O1) per batch;
on a TPU chip this is the bench.py flagship path. It runs where jax puts
it; --cpu (or JAX_PLATFORMS=cpu) is the explicit way to the CPU:

    python examples/train_ernie.py --cpu --tiny --steps 30
    python examples/train_ernie.py                  # base config (TPU)
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="tiny config + small shapes")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seqlen", type=int, default=None)
    ap.add_argument("--cpu", action="store_true",
                    help="force the XLA CPU backend")
    args = ap.parse_args()

    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")

    import paddle_tpu as paddle
    from paddle_tpu.core.flags import apply_compile_cache
    from paddle_tpu.models import ErnieConfig, ErnieForPretraining
    from paddle_tpu.static import TrainStep

    if args.tiny:
        cfg = ErnieConfig.tiny()
        batch, seqlen = args.batch or 8, args.seqlen or 64
    else:
        cfg = ErnieConfig(vocab_size=30528, max_position_embeddings=512)
        batch, seqlen = args.batch or 48, args.seqlen or 512

    apply_compile_cache()
    paddle.seed(0)
    model = ErnieForPretraining(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 weight_decay=0.01)
    step = TrainStep(
        model,
        lambda out, labels: ErnieForPretraining.pretraining_loss(out,
                                                                 labels),
        opt, amp_level="O1", amp_dtype="bfloat16")

    rng = np.random.RandomState(0)
    x = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seqlen)).astype(np.int32))
    y = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seqlen)).astype(np.int32))

    print("compiling...", flush=True)
    loss0 = float(step(x, y).item())
    t0 = time.perf_counter()
    for i in range(args.steps):
        loss = step(x, y)
    last = float(loss.item())
    dt = time.perf_counter() - t0
    toks = batch * seqlen * args.steps / dt
    print(f"loss {loss0:.4f} -> {last:.4f} | "
          f"{dt / args.steps * 1e3:.1f} ms/step | {toks:,.0f} tokens/s")

    # ragged corpora: right-padded batch + seq_lens rides the varlen
    # flash path (blockwise key masking, no materialized s*s mask);
    # padded label positions are ignore_index
    lens = rng.randint(max(1, seqlen // 4), seqlen + 1,
                       batch).astype(np.int32)
    ids = np.zeros((batch, seqlen), np.int32)
    lbl = np.full((batch, seqlen), -100, np.int32)
    for i, L in enumerate(lens):
        ids[i, :L] = rng.randint(0, cfg.vocab_size, L)
        lbl[i, :L] = rng.randint(0, cfg.vocab_size, L)
    print("compiling varlen form...", flush=True)  # new input
    # structure -> one more XLA trace/compile of the step
    vloss = step((paddle.to_tensor(ids), None, None, None,
                  paddle.to_tensor(lens)), (paddle.to_tensor(lbl),))
    print(f"varlen batch (mean len {lens.mean():.0f}/{seqlen}) "
          f"loss {float(vloss.item()):.4f}")


if __name__ == "__main__":
    main()
