#!/usr/bin/env python
"""Collective micro-bench: bus bandwidth for the XLA collectives.

BASELINE.md's last unmeasured target is "allreduce over ICI: GB/s —
measure; report vs ICI peak". The reference measures its NCCL ring with
nccl-tests-style bus bandwidth; this is the TPU-native equivalent over
`jax.sharding.Mesh` + shard_map collectives (psum / all_gather /
reduce_scatter / ppermute), reporting the standard algorithmic
bus-bandwidth formulas (Rabenseifner accounting, as nccl-tests):

  all_reduce:      busBW = bytes * 2 * (n-1)/n / t
  all_gather:      busBW = bytes * (n-1)/n / t      (bytes = full out)
  reduce_scatter:  busBW = bytes * (n-1)/n / t      (bytes = full in)
  ppermute (ring): busBW = bytes / t                (per-hop point2point)

On one chip this runs single-device (collectives are no-ops —
recorded as such); on the virtual 8-device CPU mesh it validates the
harness end to end; on a real v4/v5 slice it yields the ICI numbers vs
peak (v4: 100 GB/s/link ×6 links, v5e: 4×100 GB/s ICI per chip —
PD_ICI_PEAK_GBPS overrides). It runs where jax puts it.

Usage: python tools/collective_bench.py [--sizes-mb 1,16,64]
       [--json-out FILE]
(Pair with XLA_FLAGS=--xla_force_host_platform_device_count=8
JAX_PLATFORMS=cpu for the virtual-mesh validation run.)
"""
import argparse
import functools
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402


def _bench(fn, x, iters=10):
    import jax
    jax.block_until_ready(fn(x))  # compile + settle
    t0 = time.perf_counter()
    for _ in range(iters):
        r = fn(x)
    jax.block_until_ready(r)  # completion only — a host read of the
    # (up to multi-GB) gathered output would be timed into the window
    return (time.perf_counter() - t0) / iters


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes-mb", default="1,16,64")
    ap.add_argument("--json-out", default=None)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    devs = jax.devices()
    n = len(devs)
    results = {"devices": n,
               "platform": devs[0].platform,
               "device_kind": getattr(devs[0], "device_kind",
                                      devs[0].platform),
               "collectives": {}}
    print(f"# {n} x {results['device_kind']}", flush=True)
    if n == 1:
        results["note"] = ("single device: collectives are no-ops; "
                           "run on a slice for ICI numbers")

    mesh = Mesh(np.array(devs), ("x",))
    sizes = [float(s) for s in args.sizes_mb.split(",")]

    def make(op_name):
        # nccl-tests busBW formulas over S = the PER-RANK logical
        # buffer (shard_map hands each device a 1/n shard of the
        # global array, so S = global_bytes / n — using global bytes
        # would overstate bandwidth by n). all_gather's S is its full
        # per-device gathered output, which IS the global size.
        spec = P("x")
        if op_name == "all_reduce":
            body = lambda x: jax.lax.psum(x, "x")
            bus = lambda g, t: (g / n) * 2 * (n - 1) / n / t
        elif op_name == "all_gather":
            body = lambda x: jax.lax.all_gather(x, "x", tiled=True)
            bus = lambda g, t: g * (n - 1) / n / t
        elif op_name == "reduce_scatter":
            body = lambda x: jax.lax.psum_scatter(x, "x", tiled=True)
            bus = lambda g, t: (g / n) * (n - 1) / n / t
        else:  # ppermute ring hop: each device sends its shard
            perm = [(i, (i + 1) % n) for i in range(n)]
            body = lambda x: jax.lax.ppermute(x, "x", perm)
            bus = lambda g, t: (g / n) / t
        fn = jax.jit(shard_map(body, mesh=mesh, in_specs=spec,
                               out_specs=spec))
        return fn, bus

    for op_name in ("all_reduce", "all_gather", "reduce_scatter",
                    "ppermute"):
        per = {}
        for mb in sizes:
            # global array of mb MiB per device shard, f32
            elems = int(mb * (1 << 20) / 4) * n
            x = jnp.arange(elems, dtype=jnp.float32)
            try:
                fn, bus = make(op_name)
                t = _bench(fn, x)
                nbytes = elems * 4
                per[f"{mb:g}MB"] = {
                    "ms": round(t * 1e3, 3),
                    "busbw_GBps": round(bus(nbytes, t) / 1e9, 2)}
            except Exception as e:  # pragma: no cover
                per[f"{mb:g}MB"] = {"error": f"{type(e).__name__}: "
                                             f"{e}"[:120]}
        results["collectives"][op_name] = per
        print(json.dumps({op_name: per}), flush=True)

    peak = os.environ.get("PD_ICI_PEAK_GBPS")
    if peak:
        results["ici_peak_GBps"] = float(peak)
        best = max((v.get("busbw_GBps", 0) or 0)
                   for v in results["collectives"]["all_reduce"].values())
        results["allreduce_vs_ici_peak"] = round(best / float(peak), 3)

    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(results, f, indent=1)
    print("collective_bench:", json.dumps(results), flush=True)


if __name__ == "__main__":
    main()
