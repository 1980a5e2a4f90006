#!/usr/bin/env python3
"""The benchmark's one command.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process that loads one cell, warms it up, measures for `--seconds`,
compares what the timed path produced with the plain reference, prints
one JSON line (correct, attempted, failed, metrics, device; breakdown in
a traced run) and exits. It fails without a TPU. `--rehearse-on-cpu`
runs the same control flow at the configuration's toy widths on the CPU
backend: every line says so, nothing it prints is a device fact, and
there is no result line.

Everything about a cell comes from files found by the names in
BENCHMARK.json: the configuration, its plain reference and the recipe
that builds it out of the program (configs/<name>.json,
.reference.py, .program.py), the traffic mix (traffic/), the limits of
the comparison (cells/), one reader per per-layer metric (metrics/),
and the loop that drives a configuration of its `kind` (kinds/). A new
cell, model or kind adds files; nothing here dispatches on a name.
"""
import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from perfbench.lib import common, correct, trace_reduce  # noqa: E402


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="toy widths on the CPU backend, to debug the "
                         "runner; prints no result line")
    return ap.parse_args(argv)


def _start_jax(chips: int, rehearsal: bool):
    """Place the compile cache, find the devices, refuse anything but
    the chips the cell asks for."""
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(common.ROOT, ".jax_cache"))
    # small programs too: every run is a new process, and a program
    # that compiles again in each is set-up paid in every check
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if rehearsal:
        return jax, dev, None
    if dev["platform"] != "tpu":
        raise SystemExit(
            f"perfbench: needs a TPU, jax found platform="
            f"{dev['platform']} ({dev['count']} x {dev['kind']}); the "
            "CPU rehearsal is --rehearse-on-cpu")
    if dev["count"] != chips:
        raise SystemExit(f"perfbench: the cell asks for {chips} chip(s), "
                         f"jax shows {dev['count']}")
    return jax, dev, common.peaks_for(dev["kind"])


def _memory_peak(jax, say):
    """Peak on the fullest chip. The TPU runtime keeps a running
    program's scratch apart from the buffers (`bytes_reserved`, not in
    `peak_bytes_in_use`); both are memory the cell takes."""
    peaks = []
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        peaks.append(st.get("peak_bytes_in_use", 0)
                     + st.get("peak_bytes_reserved",
                              st.get("bytes_reserved", 0)))
    say("memory_stats of device 0: "
        + json.dumps(jax.local_devices()[0].memory_stats()))
    return int(max(peaks))


class Tracer:
    """The profiler around the last part of the measured window, in a
    traced run. Writing the capture stalls the host
    for many seconds, so `stop` is called once nothing timed is in
    flight any more; `close` only ends the ``bench:window`` span that
    the reduction clips to."""

    def __init__(self, jax, on: bool):
        self.jax, self.on = jax, on
        self.dir = os.path.join(common.ROOT, ".perfbench_trace")
        self._span = None
        self._running = False

    def open(self):
        if not self.on or self._running:
            return
        shutil.rmtree(self.dir, ignore_errors=True)
        t = time.perf_counter()
        self.jax.profiler.start_trace(self.dir)
        self._running = True
        self._span = self.jax.profiler.TraceAnnotation("bench:window")
        self._span.__enter__()
        common.say(f"the profiler took {time.perf_counter() - t:.2f} s "
                   "to start, inside the window")

    def close(self):
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def stop(self):
        self.close()
        if not self._running:
            return
        t = time.perf_counter()
        self.jax.profiler.stop_trace()
        self._running = False
        common.say(f"the capture took {time.perf_counter() - t:.2f} s to "
                   "write, after the window and its drain")

    def reduce(self):
        self.stop()
        try:
            return trace_reduce.reduce(trace_reduce.load(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def main(argv=None):
    a = _args(argv)
    bm, cell, config, traffic, limits, reference, program = \
        common.cell_files(a.workload)
    kind = common.kind_module(config["kind"])
    if a.rehearse_on_cpu:
        config = {**config, **config["toy"]}
        traffic = {**traffic, **traffic.get("toy", {})}

    def say(msg):
        common.say(msg, a.rehearse_on_cpu)

    jax, dev, peaks = _start_jax(cell["chips"], a.rehearse_on_cpu)
    from paddle_tpu.observability import sentinel
    sentinel.attach_jax_compile_hook()
    say(f"{a.workload} seed={a.seed} seconds={a.seconds} trace={a.trace} "
        f"on {dev['count']} x {dev['kind']}")
    tracer = Tracer(jax, bool(a.trace) and not a.rehearse_on_cpu)
    out = kind.run(a, config, traffic, limits, reference, program,
                   tracer, say, _T0, lambda: _memory_peak(jax, say))
    ok = correct.verdict(out["numbers"]) and out["failed"] == 0
    device = dict(dev, memory_peak_bytes=out["memory_peak"])
    result = {"correct": ok, "attempted": out["attempted"],
              "failed": out["failed"]}
    by_name = {m["name"]: m for m in bm["end_to_end"] + bm["per_layer"]}
    if a.trace:
        # a rehearsal has no capture: the readers of the benchmark's
        # own records run all the same, the device's find nothing
        reduced = tracer.reduce() if tracer.on else trace_reduce.EMPTY
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        ctx = {"trace": reduced, "bench": out["bench"], "config": config,
               "peaks": peaks, "chips": cell["chips"]}
        metrics = {}
        for m in bm["per_layer"]:
            if "workloads" in m and a.workload not in m["workloads"]:
                continue
            value = common.metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    else:
        result["metrics"] = {
            k: {"value": float(v), "unit": by_name[k]["unit"]}
            for k, v in out["end_to_end"].items()}
        result["device"] = device
    result["compared"] = correct.as_json(out["numbers"])
    correct.print_numbers(out["numbers"], ok)
    if a.rehearse_on_cpu:
        say(f"rehearsal only, no result line; would have printed keys "
            f"{sorted(result)} with metrics {sorted(result['metrics'])}")
        return 0
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
