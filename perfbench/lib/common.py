"""Files found by name, the device table, quantiles and printing."""
from __future__ import annotations

import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "perfbench")


def say(msg: str, rehearsal: bool = False):
    tag = "[perfbench REHEARSAL toy-widths-on-cpu]" if rehearsal \
        else "[perfbench]"
    print(f"{tag} {msg}", file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import one file by path: reference, recipe and metric files
    carry names (`gpt2-large.reference.py`, `ttft_p90_ms.chat.py`) that
    are not Python identifiers."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod     # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def kind_module(kind: str):
    """The loop that drives configurations of one `kind`
    (perfbench/kinds/<kind>.py)."""
    path = os.path.join(BENCH, "kinds", kind + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"perfbench: no {path} for kind {kind!r}")
    return load_module(path, "perfbench_kind_" + kind)


def cell_files(workload: str):
    """BENCHMARK.json entry -> (benchmark, cell, config dict, traffic
    dict, limits dict, reference module, program-recipe module).
    Everything else about a cell is in those files; nothing dispatches
    on the cell's name."""
    bm = benchmark()
    cells = [w for w in bm["workloads"] if w["name"] == workload]
    if not cells:
        raise SystemExit(f"perfbench: no workload {workload!r} in "
                         "BENCHMARK.json")
    cell = cells[0]
    cfg_entry = [c for c in bm["configs"] if c["name"] == cell["config"]][0]
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    traffic = load_json("traffic", cell["traffic"] + ".json")
    limits = load_json("cells", workload + ".json")
    beside = os.path.join(ROOT, os.path.dirname(cfg_entry["file"]),
                          cell["config"])
    reference = load_module(beside + ".reference.py",
                            "perfbench_reference")
    program = load_module(beside + ".program.py", "perfbench_program")
    return bm, cell, config, traffic, limits, reference, program


def peaks_for(device_kind: str) -> dict:
    table = load_json("lib", "peaks.json")["by_device_kind"]
    if device_kind not in table:
        raise SystemExit(
            f"perfbench: device_kind {device_kind!r} is not in "
            "perfbench/lib/peaks.json; add its published peaks there")
    return table[device_kind]


def annot(name: str):
    """A host span in the profiler's own trace (``bench:...``)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def compile_cache_hits() -> int:
    """Persistent compile-cache hits of this process so far."""
    from paddle_tpu.observability import metrics
    return int(metrics.counter("jax.compile_cache.hits",
                               _always=True).value())


def quantile(values, q: float) -> float:
    """Linear-interpolated quantile (numpy's default), on a sorted
    copy; q in [0, 1]."""
    v = sorted(float(x) for x in values)
    if not v:
        raise ValueError("quantile of nothing")
    pos = q * (len(v) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def metric_reader(name: str):
    path = os.path.join(BENCH, "metrics", name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"perfbench: no reader {path} for per-layer "
                         f"metric {name!r}")
    return load_module(path, "perfbench_metric_" + name.replace(".", "_")
                       .replace("-", "_")).read
