#!/usr/bin/env python3
"""What the program's ring costs when it is on. One process, one engine:
windows of the cell's traffic with `reqtrace` off and on in pairs, both
sides of a pair at one seed, the side that runs first alternating pair
by pair, no profiler. Prints each window's end-to-end metrics, then per
metric each side's median and quartiles (`statistics.quantiles(n=4)`)
and the pairs' relative differences (on less off, over off).

    python3 perfbench/tools/ring_cost.py --workload gpt2-large.score \
        --pairs 6 --seconds 15 --seed 2147493001

JSON lines on standard output, the summary last.
"""
import argparse
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench import run as _run  # noqa: E402
from perfbench.lib import common  # noqa: E402


def quartiles(values) -> dict:
    q1, q2, q3 = statistics.quantiles(sorted(values), n=4)
    return {"q1": q1, "median": q2, "q3": q3,
            "spread": (q3 - q1) / q2 if q2 > 0 else None}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--pairs", type=int, default=6)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--rehearse-on-cpu", action="store_true")
    a = ap.parse_args(argv)
    bm, cell, config, traffic, limits, reference, program = \
        common.cell_files(a.workload)
    serve = common.kind_module(config["kind"])
    if a.rehearse_on_cpu:
        config = {**config, **config["toy"]}
        traffic = {**traffic, **traffic.get("toy", {})}
    seconds = float(bm["run_seconds"]) if a.seconds is None else a.seconds
    _run._start_jax(cell["chips"], a.rehearse_on_cpu)
    eng = serve.build_engine(config, a.seed, reference, program)
    got = {False: {}, True: {}}
    for i in range(a.pairs):
        seed = a.seed + i
        for on in ((False, True) if i % 2 == 0 else (True, False)):
            m = serve.measure(config, traffic, seed, seconds, reference,
                              program, spans=on, eng=eng)
            print(json.dumps({"pair": i, "seed": seed, "ring": on,
                              "failed": m["failed"], **m["end_to_end"]}),
                  flush=True)
            for k, v in m["end_to_end"].items():
                got[on].setdefault(k, []).append(v)
            del m
    summary = {"workload": a.workload, "pairs": a.pairs,
               "seconds": seconds, "metrics": {}}
    for k, off in got[False].items():
        on = got[True][k]
        summary["metrics"][k] = {
            "off": quartiles(off), "on": quartiles(on),
            "pair_change": quartiles([b / x - 1.0 for x, b in zip(off, on)]),
            "off_runs": off, "on_runs": on}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
