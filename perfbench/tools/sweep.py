#!/usr/bin/env python3
"""Find the knee of an open-loop mix once, on the chip: the highest
offered rate at which the backlog does not grow over a window. One
process, one engine, one window per rate with the engine drained
between. The cell's rate (0.8 of the knee) is then written into the
traffic file as a number; the benchmark never searches.

    python3 perfbench/tools/sweep.py --workload gpt2-large.chat \
        --rates 1.5,2,2.5,3,3.5,4 --seconds 30 --seed 7
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np  # noqa: E402

from perfbench import run as _run  # noqa: E402
from perfbench.lib import common  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rehearse-on-cpu", action="store_true")
    a = ap.parse_args(argv)
    bm, cell, config, traffic, limits, reference, program = \
        common.cell_files(a.workload)
    serve = common.kind_module(config["kind"])
    if a.rehearse_on_cpu:
        config = {**config, **config["toy"]}
        traffic = {**traffic, **traffic.get("toy", {})}
    _run._start_jax(cell["chips"], a.rehearse_on_cpu)
    eng = serve.build_engine(config, a.seed, reference, program)
    for rate in (float(x) for x in a.rates.split(",")):
        mix = {**traffic, "rate_per_s": rate}
        m = serve.measure(config, mix, a.seed, a.seconds, reference,
                          program, eng=eng)
        b = m["bench"]
        steps = b["steps"]
        t = np.array([s["t1"] for s in steps]) - b["t_open"]
        n = np.array([s["in_system"] for s in steps], float)
        slope = float(np.polyfit(t, n, 1)[0]) if len(t) > 2 else 0.0
        third = max(len(n) // 3, 1)
        done = b["records"]
        multi = [r for r in done if len(r.out) > 1]
        row = {
            "rate_per_s": rate, "attempted": m["attempted"],
            "failed": m["failed"],
            "in_system_first_third": float(n[:third].mean()),
            "in_system_last_third": float(n[-third:].mean()),
            "in_system_slope_per_s": slope,
            "queue_last_third": float(np.mean(
                [s["queue"] for s in steps[-third:]])),
            "tpot_p50_ms": 1e3 * common.quantile(
                [(r.done - r.first_token) / (len(r.out) - 1)
                 for r in multi], 0.5),
            "tpot_p90_ms": m["end_to_end"].get("tpot_p90_ms"),
            "ttft_p50_ms": 1e3 * common.quantile(
                [r.first_token - r.due for r in done], 0.5),
            "ttft_p90_ms": 1e3 * common.quantile(
                [r.first_token - r.due for r in done], 0.9),
            "lateness_p90_ms": 1e3 * common.quantile(b["lateness"], 0.9),
            "occupancy": float(np.mean([s["active"] for s in steps]))
            / b["max_slots"],
            "new_tokens_per_s": sum(s["new_tokens"] for s in steps)
            / b["elapsed_s"],
            "step_ms_p50": 1e3 * common.quantile(
                [s["t1"] - s["t0"] for s in steps], 0.5),
        }
        print(json.dumps(row), flush=True)
        while eng.has_work():       # drained before the next rate
            eng.step()
    return 0


if __name__ == "__main__":
    sys.exit(main())
