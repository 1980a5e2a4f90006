#!/usr/bin/env python3
"""The numbers that `correct` compares, read over many seeds in one
process: the program's (the lower readings) and the control's (the
reference in a lower precision in the program's place, or the program's
own lower-precision path: the upper readings). The limits in
perfbench/cells/ are set from what this prints.

    python3 perfbench/tools/readings.py --workload W --seeds 1,2,3 \
        --modes program,control:int8 [--seconds 12]

modes: program | control:<bfloat16|int8> (reference put in the
       program's place, same prompts and tokens) |
       control:engine-int8 (the program's own int8 path)
One JSON line per seed and mode on standard output; every position's
gap and margin go to chiprun_out/raw_<workload>_<seed>.json.
"""
import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench import run as _run  # noqa: E402
from perfbench.lib import common, correct  # noqa: E402


def serve_seed(a, seed, config, traffic, reference, program):
    from perfbench.lib import traffic as _traffic
    serve = common.kind_module("serve")
    sample = None
    raw = {}

    def dump(mode, keep):
        raw[mode] = keep
        os.makedirs("chiprun_out", exist_ok=True)
        with open(f"chiprun_out/raw_{a.workload}_{seed}.json", "w") as f:
            json.dump(raw, f)

    for mode in a.modes:
        kind, _, arg = mode.partition(":")
        over = {"quant": "int8"} if mode == "control:engine-int8" else None
        if kind == "program" or over:
            m = serve.measure(config, traffic, seed, a.seconds, reference,
                              program, engine_overrides=over)
            picked = correct.pick_sample(m["finished"], seed,
                                         int(traffic["check_requests"]))
            if kind == "program":
                sample = picked
            extra = {"attempted": m["attempted"], "failed": m["failed"],
                     **m["end_to_end"]}
            n_short = m["n_short"]
            del m
            gc.collect()
            params = reference.make_params(
                config, _traffic.jax_key(seed),
                config["precision"]["weights"])
            keep = {}
            numbers = correct.compare_serve(reference, params, config,
                                            picked, n_short, {}, keep=keep)
            dump(mode, keep)
            yield mode, {n: v for n, v, _ in numbers}, extra
        elif kind == "control":
            if sample is None:
                raise SystemExit("control:<precision> needs `program` "
                                 "before it, for the prompts and tokens")
            params = reference.make_params(
                config, _traffic.jax_key(seed),
                config["precision"]["weights"])
            keep = {}
            numbers = correct.control_serve(reference, params, config,
                                            sample, arg, {}, keep=keep)
            dump(mode, keep)
            yield mode, {n: v for n, v, _ in numbers}, {}
        else:
            raise SystemExit(f"unknown serve mode {mode!r}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--modes", default="program")
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--rehearse-on-cpu", action="store_true")
    a = ap.parse_args(argv)
    a.modes = a.modes.split(",")
    bm, cell, config, traffic, limits, reference, program = \
        common.cell_files(a.workload)
    if a.rehearse_on_cpu:
        config = {**config, **config["toy"]}
        traffic = {**traffic, **traffic.get("toy", {})}
    _run._start_jax(cell["chips"], a.rehearse_on_cpu)
    t = time.perf_counter()
    for seed in (int(x) for x in a.seeds.split(",")):
        for mode, numbers, extra in serve_seed(a, seed, config, traffic,
                                               reference, program):
            now = time.perf_counter()
            print(json.dumps({"workload": a.workload, "seed": seed,
                              "mode": mode, "numbers": numbers,
                              "extra": extra,
                              "seconds_since_last": round(now - t, 1)}),
                  flush=True)
            t = now
    return 0


if __name__ == "__main__":
    sys.exit(main())
