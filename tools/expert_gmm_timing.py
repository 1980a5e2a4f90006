#!/usr/bin/env python3
"""The expert layer's grouped matmul alone, on a TPU: JAX's megablox
kernel (`ops/pallas_kernels.expert_gmm`, the serving path's) against
`jax.lax.ragged_dot`, at the expert shapes of Mellum2-12B-A2.5B (64
experts, hidden 2,304, expert width 896: gate|up [64, 2304, 1792],
down [64, 896, 2304], bf16) and the row counts the serving cell feeds
them: a prefill of 2 prompts of 5,120 tokens (81,920 rows) and a decode
token-step of 32 lanes (256 rows), 8 rows a token.

    python tools/expert_gmm_timing.py [--reps 20]

Rows are routed by a random router over hidden states drawn from the
seed (softmax, top 8), sorted by expert: group sizes as the program
makes them. Each case's time is the median over `reps` calls of a
jitted loop of 10 calls, each fed the last's output so that none is
skipped, by the host's clock after `block_until_ready`; FLOP/s and
bytes/s are of the work the rows need (2 x rows x K x N; the weights of
the experts hit and the rows in and out). The two outputs are compared.
One JSON line per case and implementation. Fails where the device is
not a TPU.
"""
import argparse
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

E, H, F, K = 64, 2304, 896, 8
LOOP = 10


def _sizes(jax, jnp, rows, key):
    """Group sizes of `rows` (token, choice) rows routed by a random
    router: softmax over E, top K, counted by expert."""
    tokens = rows // K
    x = jax.random.normal(key, (tokens, H), jnp.float32)
    router = 0.02 * jax.random.normal(jax.random.fold_in(key, 1), (H, E))
    _, e = jax.lax.top_k(jax.nn.softmax(x @ router, axis=-1), K)
    return jnp.sum(e.reshape(-1)[:, None] == jnp.arange(E)[None], axis=0
                   ).astype(jnp.int32)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=20)
    a = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels as pk
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("expert_gmm_timing: needs a TPU")
    key = jax.random.key(0)
    impls = {
        "megablox_gmm": lambda l, r, s, dt: pk.expert_gmm(l, r, s, dt),
        "ragged_dot": lambda l, r, s, dt: jax.lax.ragged_dot(
            l, r, s, preferred_element_type=dt),
    }
    for case, rows in (("prefill_2x5120", 2 * 5120 * K),
                       ("decode_32", 32 * K)):
        sizes = _sizes(jax, jnp, rows, jax.random.fold_in(key, rows))
        hit = int((sizes > 0).sum())
        for part, (kk, nn, dt) in (("gate_up", (H, 2 * F, jnp.float32)),
                                   ("down", (F, H, jnp.bfloat16))):
            lhs = jax.random.normal(jax.random.fold_in(key, 2), (rows, kk),
                                    jnp.bfloat16)
            rhs = (0.02 * jax.random.normal(jax.random.fold_in(key, 3),
                                            (E, kk, nn))).astype(jnp.bfloat16)
            outs = {}
            for name, fn in impls.items():
                def loop(lhs, rhs, sizes, fn=fn):
                    def body(_, x):
                        y = fn(x, rhs, sizes, dt)
                        return x + (y[0, 0] * 0).astype(x.dtype)
                    return jax.lax.fori_loop(0, LOOP, body, lhs)
                run = jax.jit(loop)
                one = jax.jit(lambda l, r, s, fn=fn: fn(l, r, s, dt))
                jax.block_until_ready(run(lhs, rhs, sizes))
                outs[name] = one(lhs, rhs, sizes)
                times = []
                for _ in range(a.reps):
                    t = time.perf_counter()
                    jax.block_until_ready(run(lhs, rhs, sizes))
                    times.append((time.perf_counter() - t) / LOOP)
                s = statistics.median(times)
                flops = 2.0 * rows * kk * nn
                moved = hit * kk * nn * 2 + rows * (kk * 2 + nn
                                                    * jnp.dtype(dt).itemsize)
                print(json.dumps({
                    "case": case, "part": part, "impl": name, "rows": rows,
                    "experts_hit": hit, "ms": round(1e3 * s, 4),
                    "tflop_s": round(flops / s / 1e12, 2),
                    "gb_s": round(moved / s / 1e9, 1)}), flush=True)
            a_, b_ = (outs[n].astype(jnp.float32) for n in impls)
            live = int(sizes.sum())
            print(json.dumps({"case": case, "part": part,
                              "max_abs_diff": float(jnp.max(jnp.abs(
                                  a_[:live] - b_[:live])))}), flush=True)


if __name__ == "__main__":
    sys.exit(main())
