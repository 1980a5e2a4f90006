#!/usr/bin/env python3
"""The paged decode-attention kernel alone, on a TPU, at a few counts of
live lanes of 32: its time a call by the host's clock over a jitted
loop of calls.

    python tools/paged_decode_lanes.py [--also OTHER/pallas_kernels.py] \
        [--cases 0:200,1:200,4:200,18:200,32:200,32:496] [--calls 400]

GPT-2-large's decode shape as the serving engine runs it: 32 lanes, 20
heads of 64, pools of 1,024 pages [16, 1,280] bf16, tables of 64
entries. A case `live:tokens` gives `live` lanes, drawn at random, each
`tokens` positions in pages of its own in shuffled order; every other
lane carries an all-zero table row and a length of 1-4, as a decode
chunk's lanes without a request do. The loop feeds each call's output
to the next call as its query, so no call is skipped or merged; the
host's time a call is the difference of a loop of 2n calls and one of
n, over n. With `--also`, another copy of `ops/pallas_kernels.py` (of
another commit) is timed on the same inputs, and the live lanes' output
of one call is compared with this checkout's bit for bit. One JSON line
per kernel file and case. Fails where the device is not a TPU.
"""
import argparse
import importlib.util
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

LANES, HEADS, HEAD, PAGES, PAGE, WIDTH = 32, 20, 64, 1024, 16, 64


def _module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _inputs(jax, jnp, live, tokens, seed):
    rng = np.random.default_rng(seed)
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    d = HEADS * HEAD
    q = jax.random.normal(kq, (LANES, HEADS, HEAD), jnp.bfloat16)
    kp = jax.random.normal(kk, (PAGES, PAGE, d), jnp.bfloat16)
    vp = jax.random.normal(kv, (PAGES, PAGE, d), jnp.bfloat16)
    held = -(-tokens // PAGE)
    lanes = rng.choice(LANES, live, replace=False)
    pages = rng.permutation(PAGES - 1)[:live * held] + 1
    tables = np.zeros((LANES, WIDTH), np.int32)
    tables[lanes, :held] = pages.reshape(live, held)
    lengths = rng.integers(1, 5, LANES).astype(np.int32)
    lengths[lanes] = tokens
    return q, kp, vp, jnp.asarray(tables), jnp.asarray(lengths), lanes


def _host_us(jax, loops, args, calls, repeats=5):
    best = {}
    for n, fn in loops.items():
        jax.block_until_ready(fn(*args))
        times = []
        for _ in range(repeats):
            t = time.perf_counter()
            jax.block_until_ready(fn(*args))
            times.append(time.perf_counter() - t)
        best[n] = min(times)
    return 1e6 * (best[2 * calls] - best[calls]) / calls


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--also", help="another commit's pallas_kernels.py")
    ap.add_argument("--cases", default="0:200,1:200,4:200,18:200,32:200,"
                                       "32:496")
    ap.add_argument("--calls", type=int, default=400)
    ap.add_argument("--seed", type=int, default=2147483001)
    a = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU; found {dev.platform}")
    from paddle_tpu.ops import pallas_kernels as here
    kernels = {"this": here}
    if a.also:
        kernels["also"] = _module(a.also, "pallas_kernels_also")

    def loop(pk, n):
        def run(q, kp, vp, tables, lengths):
            return jax.lax.fori_loop(
                0, n, lambda _, x: pk.paged_decode_attention(
                    x, kp, vp, tables, lengths, 0.125), q)
        return jax.jit(run)

    for case in a.cases.split(","):
        live, tokens = (int(x) for x in case.split(":"))
        *args, lanes = _inputs(jax, jnp, live, tokens, a.seed + live)
        outs = {}
        for name, pk in kernels.items():
            loops = {n: loop(pk, n) for n in (a.calls, 2 * a.calls)}
            host = _host_us(jax, loops, args, a.calls)
            one = np.asarray(pk.paged_decode_attention(*args, 0.125),
                             np.float32)
            outs[name] = one
            dead = np.setdiff1d(np.arange(LANES), lanes)
            print(json.dumps({
                "kernel": name, "live": live, "tokens": tokens,
                "device": dev.device_kind, "calls": a.calls,
                "host_us_per_call": host,
                "inactive_rows_zero": bool(not one[dead].any()),
                "finite": bool(np.isfinite(one).all())}), flush=True)
        if len(outs) == 2:
            print(json.dumps({
                "live": live, "tokens": tokens,
                "live_rows_bit_equal": bool(np.array_equal(
                    outs["this"][lanes], outs["also"][lanes]))}),
                flush=True)


if __name__ == "__main__":
    main()
