#!/usr/bin/env python
"""Autoregressive decoding with the compiled KV-cache loop.

Greedy, top-k sampling, and beam search all run as ONE XLA program
(models/generation.py). With an untrained model the output is noise —
the point is the machinery:

    python examples/generate_gpt.py --beams 4 --tokens 16
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=12)
    ap.add_argument("--beams", type=int, default=1)
    ap.add_argument("--top-k", type=int, default=None)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-p", type=float, default=None,
                    help="nucleus sampling mass (0,1]")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()

    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")

    import paddle_tpu as paddle
    from paddle_tpu.core.flags import apply_compile_cache
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    apply_compile_cache()
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig.tiny(dropout=0.0))
    model.eval()
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, 512, (2, 8)).astype(np.int32)
    out = model.generate(paddle.to_tensor(prompt),
                         max_new_tokens=args.tokens,
                         temperature=args.temperature,
                         top_k=args.top_k, top_p=args.top_p,
                         num_beams=args.beams)
    arr = np.asarray(out.numpy())
    for r, row in enumerate(arr):
        print(f"[{r}] prompt={[int(t) for t in row[:8]]} -> {[int(t) for t in row[8:]]}")

    if args.beams == 1:
        # serving-shaped call: ragged (right-padded) prompts of three
        # different lengths, bf16 weights/cache, one compiled program
        P = 8
        lens = np.asarray([P, 5, 2], np.int32)
        ragged = np.zeros((3, P), np.int32)
        for i, L in enumerate(lens):
            ragged[i, :L] = rng.randint(0, 512, L)
        out = model.generate(paddle.to_tensor(ragged),
                             max_new_tokens=args.tokens,
                             temperature=args.temperature,
                             top_k=args.top_k, dtype="bfloat16",
                             prompt_lens=paddle.to_tensor(lens))
        arr = np.asarray(out.numpy())
        print("ragged + bf16 serving:")
        for r, row in enumerate(arr):
            L = int(lens[r])
            print(f"[{r}] len={L} prompt={[int(t) for t in row[:L]]}"
                  f" -> {[int(t) for t in row[P:]]}")


if __name__ == "__main__":
    main()
