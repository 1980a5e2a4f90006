"""XPlane / trace.json device-time attribution: the measured tier.

``anatomy`` prices the program statically (FLOPs shares from the HLO);
this module reads what the chip actually DID: the XPlane protobuf a
``jax.profiler.trace`` capture writes (or its chrome-trace twin), maps
kernel names back to the anatomy scope taxonomy, and produces

  - per-scope device milliseconds (which component the step really
    spends time on — the in-situ counterpart of
    tools/tpu_breakdown.py's isolated numbers),
  - step-gap / idle time (device span minus the union of kernel
    intervals: dispatch stalls, host-bound gaps),
  - the **comm-overlap receipt**: of the device time spent in
    collectives (fused grad-sync buckets included — their HLO names
    carry the ``grad_sync`` scope), how much ran CONCURRENTLY with
    compute on the same device vs exposed on the critical path.
    ``overlap_fraction = hidden_ms / comm_ms`` is published as the
    ``comm.overlap_fraction`` gauge through the PR 3 exporters and
    ``fleet.aggregate()`` — the receipt ROADMAP 3(d) needs to decide
    whether bucketed grad sync actually overlaps backward.

One parser, one glob contract: ``find_xplane`` owns the
``**/*.xplane.pb`` discovery every consumer previously inlined (like
PR 4 unified dump paths through ``flight_recorder.default_dump_path``).
Inputs accepted: a profiler logdir, a ``.xplane.pb`` file (parsed via
``jax.profiler.ProfileData`` when this runtime ships it), or a chrome
``trace.json``/``trace.json.gz`` — the format the recorded-trace
tier-1 fixture uses, so the whole attribution path is testable on CPU
with no hardware and no ProfileData dependency.

This module imports jax only inside the XPlane loader — the trace.json
path and the overlap math must work on a triage host (same discipline
as flight_recorder).
"""
from __future__ import annotations

import glob as _glob
import gzip
import json
import os
from typing import Dict, Iterable, List, Optional, Tuple

from . import anatomy, metrics

__all__ = [
    "find_xplane", "load_profile", "is_comm_kernel", "scope_of_event",
    "attribute_device_time", "overlap_receipt", "publish", "top_ops",
    "format_top_ops",
]

# substrings that mark a device event as collective communication
# (XLA kernel spellings + our fused grad-sync op labels)
COMM_TOKENS = (
    "all-reduce", "all_reduce", "allreduce", "all-gather", "all_gather",
    "allgather", "reduce-scatter", "reduce_scatter", "all-to-all",
    "alltoall", "collective-permute", "collective_permute", "ppermute",
    "fused_allreduce", "psum", "collective",
)

# stat/arg keys that may carry the HLO metadata path for an event
_ARG_KEYS = ("tf_op", "hlo_op", "long_name", "name", "op_name",
             "kernel_details")

_DEVICE_PLANE_TOKENS = ("/device:", "tpu", "gpu", "accelerator")

# aggregate/marker LANES inside a device plane whose events span whole
# steps or modules rather than individual kernels ("XLA Modules" holds
# one jit_step-sized event; "Steps" holds step markers). Left in, they
# sit in the compute union and saturate the overlap receipt at ~1.0 and
# zero the idle figure on every real capture — exactly the numbers this
# parser exists to measure. Matched case-insensitively on the lane name.
_AGGREGATE_LINE_TOKENS = ("xla modules", "module", "steps", "step",
                          "framework", "source", "xla traceme",
                          "scope range")


def _is_aggregate_line(line_name: str) -> bool:
    ln = (line_name or "").lower()
    return any(tok in ln for tok in _AGGREGATE_LINE_TOKENS)


def find_xplane(logdir: str) -> Optional[str]:
    """THE ``**/*.xplane.pb`` glob contract (newest capture wins), for
    every consumer that lets jax.profiler.trace pick the subdirectory."""
    hits = _glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not hits:
        return None
    return max(hits, key=os.path.getmtime)


# ---------------------------------------------------------------------------
# loading: XPlane pb / chrome trace.json -> normalized event dicts
# ---------------------------------------------------------------------------
# Event: {"device": plane/process name, "line": lane name, "name": str,
#         "ts": start µs, "dur": duration µs, "args": {str: str}}

def _load_xplane(path: str) -> List[dict]:
    try:
        from jax.profiler import ProfileData
    except ImportError as e:  # pragma: no cover — runtime-dependent
        raise RuntimeError(
            "this jax runtime has no jax.profiler.ProfileData; convert "
            "the capture to trace.json (TensorBoard writes one next to "
            "the xplane.pb) and pass that instead") from e
    pd = ProfileData.from_serialized_xspace(open(path, "rb").read())
    events: List[dict] = []
    for plane in pd.planes:
        pname = plane.name
        if not any(t in pname.lower() for t in _DEVICE_PLANE_TOKENS):
            continue
        for line in plane.lines:
            lname = getattr(line, "name", "")
            if _is_aggregate_line(lname):
                continue
            for ev in line.events:
                # event stats carry the HLO metadata (tf_op/long_name)
                # on real captures; the API has shipped both (name,
                # value) pairs and XStat-like objects — best-effort
                # either way, the kernel name alone still attributes
                args = {}
                try:
                    for stat in getattr(ev, "stats", ()) or ():
                        if isinstance(stat, (tuple, list)) \
                                and len(stat) == 2:
                            args[str(stat[0])] = str(stat[1])
                        else:
                            name = getattr(stat, "name", None)
                            if name is not None:
                                args[str(name)] = str(
                                    getattr(stat, "value", ""))
                except Exception:
                    pass
                events.append({
                    "device": pname, "line": lname, "name": ev.name,
                    "ts": ev.start_ns / 1e3,
                    "dur": ev.duration_ns / 1e3, "args": args})
    return events


def _load_trace_json(path: str) -> List[dict]:
    op = gzip.open if path.endswith(".gz") else open
    with op(path, "rt") as f:
        doc = json.load(f)
    raw = doc.get("traceEvents", doc if isinstance(doc, list) else [])
    pid_names: Dict[int, str] = {}
    tid_names: Dict[Tuple[int, int], str] = {}
    for ev in raw:
        if ev.get("ph") == "M":
            nm = (ev.get("args") or {}).get("name", "")
            if ev.get("name") == "process_name":
                pid_names[ev.get("pid")] = nm
            elif ev.get("name") == "thread_name":
                tid_names[(ev.get("pid"), ev.get("tid"))] = nm
    device_pids = {p for p, n in pid_names.items()
                   if any(t in n.lower() for t in _DEVICE_PLANE_TOKENS)}
    events: List[dict] = []
    for ev in raw:
        if ev.get("ph") != "X":
            continue
        pid = ev.get("pid")
        if device_pids and pid not in device_pids:
            continue
        lname = tid_names.get((pid, ev.get("tid")),
                              str(ev.get("tid")))
        if _is_aggregate_line(lname):
            continue
        events.append({
            "device": pid_names.get(pid, str(pid)),
            "line": lname,
            "name": ev.get("name", ""),
            "ts": float(ev.get("ts", 0.0)),
            "dur": float(ev.get("dur", 0.0)),
            "args": {k: str(v) for k, v in
                     (ev.get("args") or {}).items()}})
    return events


def load_profile(path: str) -> List[dict]:
    """Normalize a capture into device-event dicts. Accepts a profiler
    logdir (xplane.pb preferred, trace.json fallback), an .xplane.pb
    file, or a chrome trace.json(.gz)."""
    if os.path.isdir(path):
        xp = find_xplane(path)
        if xp is not None:
            return _load_xplane(xp)
        js = sorted(
            _glob.glob(os.path.join(path, "**", "*trace.json*"),
                       recursive=True), key=os.path.getmtime)
        if js:
            return _load_trace_json(js[-1])
        raise FileNotFoundError(
            f"no *.xplane.pb or *trace.json* under {path!r}")
    if path.endswith(".pb"):
        return _load_xplane(path)
    return _load_trace_json(path)


# ---------------------------------------------------------------------------
# classification
# ---------------------------------------------------------------------------

def is_comm_kernel(name: str, args: Optional[dict] = None) -> bool:
    hay = name.lower()
    if args:
        hay += " " + " ".join(str(v).lower() for v in args.values())
    return any(tok in hay for tok in COMM_TOKENS)


def scope_of_event(ev: dict,
                   scopes: Optional[Iterable[str]] = None
                   ) -> Optional[str]:
    """Map one device event to the anatomy taxonomy: HLO metadata paths
    in the event args first (tf_op/long_name carry the op_name the
    scopes lowered into), then the kernel name's own tokens."""
    args = ev.get("args") or {}
    for k in _ARG_KEYS:
        v = args.get(k)
        if v:
            sc = anatomy.scope_of_op_name(str(v), scopes)
            if sc is not None:
                return sc
    return anatomy.scope_of_op_name(
        ev.get("name", "").replace(".", "/"), scopes)


# ---------------------------------------------------------------------------
# interval math
# ---------------------------------------------------------------------------

def _merge(intervals: List[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _union_len(intervals: List[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in _merge(intervals))


def _overlap_with(iv: Tuple[float, float],
                  merged: List[Tuple[float, float]]) -> float:
    s, e = iv
    got = 0.0
    for ms, me in merged:
        if me <= s:
            continue
        if ms >= e:
            break
        got += min(e, me) - max(s, ms)
    return got


# ---------------------------------------------------------------------------
# attribution + the overlap receipt
# ---------------------------------------------------------------------------

def overlap_receipt(events: List[dict]) -> dict:
    """Per-device: comm intervals vs the union of concurrent compute
    intervals on the SAME device (other lanes or async-pair gaps).
    hidden = comm time with compute in flight; exposed = the rest —
    the part of grad sync the step actually waits for."""
    comm_ms = hidden_ms = 0.0
    by_dev: Dict[str, List[dict]] = {}
    for ev in events:
        by_dev.setdefault(ev["device"], []).append(ev)
    for evs in by_dev.values():
        compute = _merge([(e["ts"], e["ts"] + e["dur"]) for e in evs
                          if not is_comm_kernel(e["name"], e["args"])])
        for e in evs:
            if not is_comm_kernel(e["name"], e["args"]):
                continue
            iv = (e["ts"], e["ts"] + e["dur"])
            comm_ms += e["dur"] / 1e3
            hidden_ms += _overlap_with(iv, compute) / 1e3
    exposed = comm_ms - hidden_ms
    return {
        "comm_ms": round(comm_ms, 6),
        "hidden_ms": round(hidden_ms, 6),
        "exposed_ms": round(exposed, 6),
        "overlap_fraction": (round(hidden_ms / comm_ms, 6)
                             if comm_ms > 0 else -1.0),
    }


def attribute_device_time(events: List[dict],
                          scopes: Optional[Iterable[str]] = None,
                          steps: int = 1) -> dict:
    """The device-time anatomy: per-scope ms (comm events land on their
    HLO scope when one is named, else the ``comm`` row), busy/idle
    split, and the comm-overlap receipt. ``steps`` divides the *_per_step
    figures for multi-step captures."""
    steps = max(int(steps), 1)
    per: Dict[str, float] = {}
    span_ms = busy_ms = 0.0
    by_dev: Dict[str, List[Tuple[float, float]]] = {}
    for ev in events:
        sc = scope_of_event(ev, scopes)
        if sc is None:
            sc = "comm" if is_comm_kernel(ev["name"], ev["args"]) \
                else "unattributed"
        per[sc] = per.get(sc, 0.0) + ev["dur"] / 1e3
        by_dev.setdefault(ev["device"], []).append(
            (ev["ts"], ev["ts"] + ev["dur"]))
    for ivs in by_dev.values():
        busy_ms += _union_len(ivs) / 1e3
        span_ms += (max(e for _, e in ivs) - min(s for s, _ in ivs)) / 1e3
    total = sum(per.values())
    comm = overlap_receipt(events)
    return {
        "per_scope_ms": {k: round(v / steps, 6) for k, v in
                         sorted(per.items(), key=lambda kv: -kv[1])},
        "per_scope_share": {k: round(v / total, 6) if total else 0.0
                            for k, v in per.items()},
        "device_busy_ms": round(busy_ms / steps, 6),
        "device_span_ms": round(span_ms / steps, 6),
        "idle_ms": round(max(span_ms - busy_ms, 0.0) / steps, 6),
        "comm": comm,
        "devices": len(by_dev),
        "events": len(events),
        "steps": steps,
    }


def publish(result: dict, prefix: str = "anatomy"):
    """Gauges for the measured tier — always-on, same contract as
    anatomy.publish: ``comm.overlap_fraction`` is THE ROADMAP 3(d)
    receipt and must ride every exporter and fleet.aggregate() even
    when the hot-path metrics gate is down."""
    comm = result.get("comm", {})
    metrics.gauge("comm.overlap_fraction", _always=True).set(
        comm.get("overlap_fraction", -1.0))
    metrics.gauge("comm.exposed_ms", _always=True).set(
        comm.get("exposed_ms", -1.0))
    metrics.gauge("comm.device_ms", _always=True).set(
        comm.get("comm_ms", -1.0))
    for name, ms in result.get("per_scope_ms", {}).items():
        metrics.gauge(f"{prefix}.device_ms", _always=True,
                      scope=name).set(ms)
    metrics.gauge(f"{prefix}.idle_ms", _always=True).set(
        result.get("idle_ms", -1.0))
    return result


# ---------------------------------------------------------------------------
# the top-list of device ops
# ---------------------------------------------------------------------------

def top_ops(events: List[dict], n: int = 15,
            steps: int = 1) -> List[Tuple[str, float]]:
    """Heaviest device ops as (name, ms/step)."""
    steps = max(int(steps), 1)
    tot: Dict[str, float] = {}
    for ev in events:
        tot[ev["name"]] = tot.get(ev["name"], 0.0) + ev["dur"]
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [(name, us / 1e3 / steps) for name, us in ranked]


def format_top_ops(events: List[dict], n: int = 15,
                   steps: int = 1) -> str:
    lines = [f"top device ops over {steps} steps:"]
    for name, ms in top_ops(events, n=n, steps=steps):
        lines.append(f"  {ms:9.2f} ms/step  {name[:90]}")
    return "\n".join(lines)
