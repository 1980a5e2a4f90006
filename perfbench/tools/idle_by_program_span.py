#!/usr/bin/env python3
"""Put a cell's device idle gaps down to the program's own spans, once,
by hand. The benchmark's reduction names only its own `bench:` host
spans (`trace_reduce.load`); the program's engine annotates every
phase of a step as `serve:<phase>` on the profiler's clock and writes
the same phase to its ring on `perf_counter` (reqtrace). This runs one
window of the cell the way run.py does, keeps the capture, lays the
`serve:` spans (named `serve:<phase>.<kind>`) under the `bench:` ones
and reduces with trace_reduce's own functions. It prints:

- idle seconds by innermost span, over the captured window;
- per phase the median seconds a step spends in it (the ring);
- the offset between each captured `serve:step` start and the ring's
  `step.t0` (median, spread), which is what joining the two by
  `(name, step)` rests on;
- how long after the device's last op of a dispatch its `serve:sync`
  returns, and how long after `serve:dispatch` begins the first op
  starts: launch and fetch latency plus whatever the device and host
  lanes of a capture are apart;
- the ring's host-gap share over the same captured seconds, beside the
  capture's idle share.

    python3 perfbench/tools/idle_by_program_span.py \
        --workload gpt2-large.score --seed 11

One JSON document on standard output and in
chiprun_out/idle_by_program_span_<workload>.json.
"""
import argparse
import bisect
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench import run as _run  # noqa: E402
from perfbench.lib import common, step_spans, trace_reduce  # noqa: E402

PROGRAM_PREFIX = "serve:"


def program_spans(path: str):
    """[(name, step, start_s, end_s)] of the capture's `serve:` host
    events; a phase that carries a `kind` is named
    `serve:<phase>.<kind>`."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith(PROGRAM_PREFIX):
                    continue
                stats = dict(ev.stats)
                name = ev.name + ("." + str(stats["kind"])
                                  if "kind" in stats else "")
                s = ev.start_ns / 1e9
                out.append((name, stats.get("step"), s,
                            s + ev.duration_ns / 1e9))
    return out


def spread(values) -> dict:
    """Median and quartiles (`statistics.quantiles(n=4)`), seconds in,
    microseconds out."""
    v = sorted(values)
    if len(v) < 2:
        return {"n": len(v)}
    q1, q2, q3 = statistics.quantiles(v, n=4)
    return {"n": len(v), "median_us": 1e6 * q2, "q1_us": 1e6 * q1,
            "q3_us": 1e6 * q3, "min_us": 1e6 * v[0],
            "max_us": 1e6 * v[-1]}


def lane_latencies(spans, device_ops, window):
    """Per dispatch inside the window: first device op start less the
    `serve:dispatch` start, and `serve:sync` end less the end of the
    last device op that began before it."""
    ops = trace_reduce.merge([(s, e) for _, s, e in device_ops])
    op_starts = [a for a, _ in ops]
    starts, ends = [], []
    for name, _, s, e in spans:
        if s < window[0] or e > window[1]:
            continue
        if name.startswith("serve:dispatch"):
            i = bisect.bisect_left(op_starts, s)
            if i < len(ops):
                starts.append(op_starts[i] - s)
        elif name.startswith("serve:sync"):
            i = bisect.bisect_left(op_starts, e)
            if i:
                ends.append(e - ops[i - 1][1])
    return {"first_op_after_dispatch_begins": spread(starts),
            "sync_returns_after_last_op_ends": spread(ends)}


def phase_table(ctx):
    """Median over the window's steps of the seconds in each phase
    (by kind), of the step, and of the step's self time; ms."""
    per = {}
    whole, own = [], []
    window = step_spans.in_window(ctx)
    for st in window:
        sums = {}
        for ph in st["phases"]:
            key = ph["comp"] + ("." + ph["kind"] if "kind" in ph else "")
            sums[key] = sums.get(key, 0.0) + ph["t1"] - ph["t0"]
        for k, v in sums.items():
            per.setdefault(k, []).append(v)
        dur = st["span"]["t1"] - st["span"]["t0"]
        whole.append(dur)
        own.append(dur - sum(sums.values()))
    if not window:
        return {}
    table = {k: {"steps": len(v),
                 "median_ms": 1e3 * common.quantile(v, 0.5),
                 "total_s": sum(v)}
             for k, v in sorted(per.items())}
    table["step"] = {"steps": len(whole),
                     "median_ms": 1e3 * common.quantile(whole, 0.5),
                     "total_s": sum(whole)}
    table["step self time"] = {
        "steps": len(own), "median_ms": 1e3 * common.quantile(own, 0.5),
        "total_s": sum(own)}
    return table


def shares(ctx) -> dict:
    """Of the ctx's window: the host-gap share and the share spent
    inside `dispatch` phases (the enqueue, during which the device may
    still be idle)."""
    b = ctx["bench"]
    window = (b["t_open"], b["t_close"])
    calls = [trace_reduce.clip((ph["t0"], ph["t1"]), window)
             for st in step_spans.steps(ctx) for ph in st["phases"]
             if ph["comp"] == "dispatch"]
    return {"seconds": window[1] - window[0],
            "host_gap_share_pct": step_spans.host_gap_share(ctx),
            "dispatch_share_pct": 100.0 * sum(
                e - s for s, e in filter(None, calls))
            / (window[1] - window[0])}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
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
    jax, dev, _ = _run._start_jax(cell["chips"], a.rehearse_on_cpu)
    tracer = _run.Tracer(jax, True)
    m = serve.measure(config, traffic, a.seed, seconds, reference,
                      program, tracer=tracer, spans=True)
    tracer.stop()
    path = trace_reduce.find_xplane(tracer.dir)
    trace = trace_reduce.load(path)
    spans = program_spans(path)
    trace["host"] += [(n, s, e) for n, _, s, e in spans]
    red = trace_reduce.reduce(trace, n_top=64)
    idle_s = red["window_s"] - red["busy_s"]
    ctx = {"bench": m["bench"]}
    ring = {s["span"]["step"]: s["span"] for s in step_spans.steps(ctx)}
    captured = [(n, s, e) for name, n, s, e in spans
                if name == "serve:step" and n in ring]
    offsets = [s - ring[n]["t0"] for n, s, e in captured]
    out = {
        "workload": a.workload, "seed": a.seed, "device": dev,
        "rehearsal_on_cpu": bool(a.rehearse_on_cpu),
        "window_s": red["window_s"], "busy_s": red["busy_s"],
        "idle_s": idle_s,
        "idle_share_pct": 100.0 * idle_s / red["window_s"],
        "idle_by_innermost_span_s": red["idle_gaps"],
        "phase_table_ms": phase_table(ctx),
        "capture_less_ring_step_start": spread(offsets),
        "capture_less_ring_step_length": spread(
            [(e - s) - (ring[n]["t1"] - ring[n]["t0"])
             for n, s, e in captured]),
        "lanes": lane_latencies(
            spans, [op for ops in trace["device"].values()
                    for op in ops], red["window"]),
        "whole_window": shares(ctx),
        "whole_window_readers": {
            "engine_phases_host_ms_p50": step_spans.phases_ms_p50(
                ctx, step_spans.ENGINE_PHASES),
            "cache_phases_host_ms_p50": step_spans.phases_ms_p50(
                ctx, step_spans.CACHE_PHASES),
            "dispatch_call_ms_p50": step_spans.dispatch_ms_p50(ctx),
            "recompiles_in_window": step_spans.recompiles(ctx)},
    }
    if offsets:
        # the captured seconds on the ring's clock, through the median
        # offset: the ring's host-gap and dispatch shares over the very
        # seconds whose idle share the capture gives
        shift = statistics.median(offsets)
        out["captured_seconds_on_the_ring"] = shares(
            {"bench": dict(m["bench"], t_open=red["window"][0] - shift,
                           t_close=red["window"][1] - shift)})
    os.makedirs("chiprun_out", exist_ok=True)
    dest = f"chiprun_out/idle_by_program_span_{a.workload}.json"
    with open(dest, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
