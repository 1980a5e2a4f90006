#!/usr/bin/env python3
"""Split a cell's device idle time around its dispatches, once, by hand:
the host's share, the time a program was being enqueued or was running
with the device idle, and the fetch's tail. The engine stamps a traced
`sync` with `ready`, when the fetched output's device buffer was ready,
and enters `serve:fetch` from then to the phase's end (reqtrace). This
runs one window of the cell the way run.py does, keeps the capture and
prints:

- the capture's idle share beside the sum of its three parts over the
  same seconds: the ring's host-gap share, `inflight_idle_share` and
  `fetch_tail_share` (perfbench/lib/dispatch_spans.py), the captured
  window mapped onto the ring as `[t_close - window_s, t_close]`;
- how far that mapping is off: each captured `serve:step` start less
  the ring's `step.t0`, both measured from their window's start;
- per dispatch on the capture's own clock, by the program's kind: the
  first device op's start less `serve:dispatch`'s (launch), the idle
  gaps between the program's first and last op, `serve:fetch`'s start
  (`ready`) less the last op's end, and `serve:sync`'s end less
  `serve:fetch`'s start (the copy to the host); each as a median with
  quartiles, and summed as seconds of the window.

    python3 perfbench/tools/fetch_by_program_span.py \
        --workload brumby-14b-base.complete --seed 11 --seconds 20

One JSON document on standard output.
"""
import argparse
import bisect
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench import run as _run  # noqa: E402
from perfbench.lib import common, dispatch_spans, step_spans  # noqa: E402
from perfbench.lib import trace_reduce  # noqa: E402
from perfbench.tools import idle_by_program_span as _idle  # noqa: E402

PARTS = ("launch", "between_ops", "last_op_to_ready", "ready_to_sync_end")


def by_dispatch(spans, ops, window):
    """{kind: {part: [seconds a dispatch]}} over the dispatches whose
    `serve:fetch` lies inside the window. `spans` as `program_spans`
    gives them, `ops` the merged device op intervals."""
    starts = [s for s, _ in ops]
    found = {}
    for name, step, s, e in spans:
        base, _, kind = name.partition(".")
        if base in ("serve:dispatch", "serve:fetch", "serve:sync"):
            found.setdefault((step, kind), {})[base] = (s, e)
    out = {}
    for (_, kind), got in sorted(found.items(), key=lambda kv: str(kv[0])):
        if len(got) < 3:
            continue
        d0 = got["serve:dispatch"][0]
        f0 = got["serve:fetch"][0]
        s1 = got["serve:sync"][1]
        if d0 < window[0] or s1 > window[1]:
            continue
        lo = bisect.bisect_left(starts, d0)
        hi = bisect.bisect_left(starts, f0)
        inside = ops[lo:hi]
        if not inside:
            continue
        first, last = inside[0][0], inside[-1][1]
        busy = sum(e - s for s, e in inside)
        row = out.setdefault(kind, {p: [] for p in PARTS})
        row["launch"].append(first - d0)
        row["between_ops"].append((last - first) - busy)
        row["last_op_to_ready"].append(f0 - last)
        row["ready_to_sync_end"].append(s1 - f0)
    return out


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
    spans = _idle.program_spans(path)
    red = trace_reduce.reduce(trace)
    window = red["window"]
    ctx = {"bench": m["bench"], "trace": red}
    lo, hi = dispatch_spans.captured_window(ctx)
    ring = {s["span"]["step"]: s["span"] for s in step_spans.steps(ctx)}
    steps = [(n, s) for name, n, s, _ in spans
             if name == "serve:step" and n in ring
             and window[0] <= s <= window[1]]
    host = step_spans.host_gap_share(
        {"bench": dict(m["bench"], t_open=lo, t_close=hi)})
    inflight = dispatch_spans.inflight_idle_share(ctx)
    tail = dispatch_spans.fetch_tail_share(ctx)
    idle_pct = 100.0 * (1.0 - red["busy_s"] / red["window_s"])
    # the first chip's ops (a rehearsal's capture has no device plane)
    first_chip = sorted(trace["device"])[:1]
    ops = trace_reduce.merge([(s, e) for plane in first_chip
                              for _, s, e in trace["device"][plane]])
    parts = by_dispatch(spans, ops, window)
    ring_tails = [ph["t1"] - ph["ready"] for st in step_spans.steps(ctx)
                  for ph in st["phases"] if "ready" in ph
                  and lo <= ph["ready"] <= hi]
    out = {
        "workload": a.workload, "seed": a.seed, "device": dev,
        "rehearsal_on_cpu": bool(a.rehearse_on_cpu),
        "window_s": red["window_s"], "busy_s": red["busy_s"],
        "idle_share_pct": idle_pct,
        "host_gap_share_pct_captured": host,
        "inflight_idle_share": inflight,
        "fetch_tail_share": tail,
        "parts_sum_less_idle_pct": None if None in (host, inflight, tail)
        else host + inflight + tail - idle_pct,
        "mapped_capture_less_ring_step_start": _idle.spread(
            [(s - window[0]) - (ring[n]["t0"] - lo) for n, s in steps]),
        "capture_less_ring_step_start": _idle.spread(
            [s - ring[n]["t0"] for n, s in steps]),
        "ring_ready_to_sync_end": _idle.spread(ring_tails),
        "by_dispatch": {
            kind: {p: dict(_idle.spread(v), total_s=sum(v))
                   for p, v in row.items()}
            for kind, row in parts.items()},
        "whole_window_readers": {
            "engine_host_gap_share": step_spans.host_gap_share(ctx),
            "dispatch_call_ms_p50": step_spans.dispatch_ms_p50(ctx),
            "step_lanes_live_share": dispatch_spans.lanes_live_share(ctx),
            "step_pages_live_peak_share":
                dispatch_spans.pages_live_peak_share(ctx)},
    }
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
