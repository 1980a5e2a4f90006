"""From a profiler capture (.xplane.pb) to busy/idle, per-op time,
exposed collectives and idle gaps named by what the host was doing.

Copied in substance from paddle_tpu/observability/xprof.py (interval
union, aggregate-lane filter, collective tokens) so that no later PR
can move the yardstick; the window clip, the host spans and the gap
attribution are the benchmark's own. Times are seconds on the trace's
clock. Checked against tests/data/small.xplane.pb.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

COMM_TOKENS = (
    "all-reduce", "all_reduce", "allreduce", "all-gather", "all_gather",
    "allgather", "reduce-scatter", "reduce_scatter", "all-to-all",
    "alltoall", "collective-permute", "collective_permute", "ppermute",
    "psum", "collective",
)
# lanes of a device plane whose events span whole modules or steps;
# left in, they cover every gap and the idle share reads 0
# ("Async XLA Ops" holds DMA spans from start to done, which lie over
# the ops of the core; what the core waits for shows on "XLA Ops" as the
# length of the -done op)
_AGGREGATE_LINE_TOKENS = ("xla modules", "module", "steps", "step",
                          "framework", "source", "xla traceme",
                          "scope range", "sparsecore", "async",
                          "overlay")
# ops of the op lane that only hold other ops (a decode chunk's loop):
# their children are on the lane too, so they count in the busy union
# and not in the per-op sums
_CONTAINER_OPS = ("while", "conditional", "call")
SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:window"
# idle gaps shorter than this are between back-to-back ops, not waits
MIN_GAP_S = 50e-6

Interval = Tuple[float, float]

_HLO = re.compile(r"^%?([\w\-.]+?)(?:\.\d+)? = \(?(\w+)\[([\d,]*)\]")
_SUFFIX = re.compile(r"\.\d+$")


def op_label(name: str) -> str:
    """An op lane's event is named by its HLO line, `%copy.17 =
    bf16[2048,16,20,64]{...} copy(...)`. That becomes
    `copy_bf16_2048_16_20_64_`: the op with its (first) result shape, so
    that a copy of the page pool can be told from any other copy and a
    name stays the same from run to run. Anything else keeps its name
    without the trailing counter."""
    m = _HLO.match(name)
    if m:
        return "{}_{}_{}_".format(m.group(1), m.group(2),
                                  m.group(3).replace(",", "_"))
    return _SUFFIX.sub("", name.lstrip("%"))


def find_xplane(logdir: str) -> Optional[str]:
    hits = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                     recursive=True)
    return max(hits, key=os.path.getmtime) if hits else None


def is_comm(name: str) -> bool:
    low = name.lower()
    return any(tok in low for tok in COMM_TOKENS)


def merge(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def union_len(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in merge(intervals))


def overlap_with(iv: Interval, merged: List[Interval]) -> float:
    s, e = iv
    got = 0.0
    for ms, me in merged:
        if me <= s:
            continue
        if ms >= e:
            break
        got += min(e, me) - max(s, ms)
    return got


def clip(iv: Interval, window: Interval) -> Optional[Interval]:
    s, e = max(iv[0], window[0]), min(iv[1], window[1])
    return (s, e) if e > s else None


def load(path: str) -> dict:
    """{"device": {plane: [(name, start, end)]}, "host": [(name, start,
    end)]}: device ops of the kernel lanes, and the benchmark's own
    host spans (names starting ``bench:``)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        found = find_xplane(path)
        if found is None:
            raise FileNotFoundError(f"no *.xplane.pb under {path!r}")
        path = found
    pd = ProfileData.from_file(path)
    device: Dict[str, list] = {}
    host: list = []
    for plane in pd.planes:
        pname = plane.name
        is_dev = pname.startswith("/device:") and "TPU" in pname.upper()
        for line in plane.lines:
            lname = (getattr(line, "name", "") or "").lower()
            if is_dev:
                if any(t in lname for t in _AGGREGATE_LINE_TOKENS):
                    continue
                ops = device.setdefault(pname, [])
                for ev in line.events:
                    s = ev.start_ns / 1e9
                    ops.append((op_label(ev.name), s,
                                s + ev.duration_ns / 1e9))
            elif pname.startswith("/host:"):
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = ev.start_ns / 1e9
                        host.append((ev.name, s,
                                     s + ev.duration_ns / 1e9))
    return {"device": device, "host": host}


def window_of(trace: dict) -> Interval:
    spans = [(s, e) for n, s, e in trace["host"] if n == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"no {WINDOW_SPAN} span in the capture")
    return max(spans, key=lambda iv: iv[1] - iv[0])


def reduce(trace: dict, n_top: int = 10) -> dict:
    """Everything the per-layer readers and the result line need,
    inside the ``bench:window`` span."""
    window = window_of(trace)
    window_s = window[1] - window[0]
    planes = sorted(trace["device"])
    per_op: Dict[str, float] = {}
    per_op_calls: Dict[str, int] = {}
    busy = []
    comm_s = exposed_s = 0.0
    gaps_by_span: Dict[str, float] = {}
    host = [(n, s, e) for n, s, e in trace["host"] if n != WINDOW_SPAN]
    for plane in planes:
        ops = []
        for name, s, e in trace["device"][plane]:
            iv = clip((s, e), window)
            if iv is not None:
                ops.append((name, iv))
        for name, (s, e) in ops:
            if name.split("_")[0] in _CONTAINER_OPS:
                continue
            per_op[name] = per_op.get(name, 0.0) + (e - s)
            per_op_calls[name] = per_op_calls.get(name, 0) + 1
        merged = merge([iv for _, iv in ops])
        busy.append(sum(e - s for s, e in merged))
        compute = merge([iv for n, iv in ops if not is_comm(n)])
        for name, iv in ops:
            if is_comm(name):
                comm_s += iv[1] - iv[0]
                exposed_s += (iv[1] - iv[0]) - overlap_with(iv, compute)
        if plane == planes[0]:
            # idle gaps of the first chip, named by the innermost (the
            # shortest) benchmark span that covers each piece
            edges = [window[0]] + [x for iv in merged for x in iv] \
                + [window[1]]
            for gs, ge in zip(edges[0::2], edges[1::2]):
                if ge - gs < MIN_GAP_S:
                    continue
                for hs, he, name in _innermost_cover((gs, ge), host):
                    gaps_by_span[name] = gaps_by_span.get(name, 0.0) \
                        + (he - hs)
    n = max(len(planes), 1)
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:n_top]
    return {
        "window": window,
        "window_s": window_s,
        "chips": len(planes),
        "busy_s": sum(busy) / n,
        "per_op_s": {k: v / n for k, v in per_op.items()},
        "per_op_calls": {k: v / n for k, v in per_op_calls.items()},
        "device_ops": [[k, v / n] for k, v in top],
        "comm_s": comm_s / n,
        "comm_exposed_s": exposed_s / n,
        "idle_gaps": [[k, v] for k, v in sorted(
            gaps_by_span.items(), key=lambda kv: -kv[1])[:n_top]],
    }


# what `reduce` gives for a capture with nothing in it (a rehearsal)
EMPTY = {"window": (0.0, 0.0), "window_s": 0.0, "chips": 0, "busy_s": 0.0,
         "per_op_s": {}, "per_op_calls": {}, "device_ops": [],
         "comm_s": 0.0, "comm_exposed_s": 0.0, "idle_gaps": []}


def _innermost_cover(gap: Interval, host: list):
    """Split one idle gap among the host spans under it: each instant
    goes to the shortest span that contains it, the rest to
    ``bench:none``. Yields (start, end, name) pieces."""
    gs, ge = gap
    cuts = {gs, ge}
    live = []
    for name, s, e in host:
        if e > gs and s < ge:
            live.append((name, s, e))
            cuts.update(x for x in (s, e) if gs < x < ge)
    pts = sorted(cuts)
    for a, b in zip(pts, pts[1:]):
        mid = (a + b) / 2.0
        covering = [(e - s, name) for name, s, e in live if s <= mid < e]
        yield a, b, (min(covering)[1] if covering else "bench:none")
