"""The program's engine-step spans (reqtrace: one `step` span a
`ServingEngine.step()` that had work, contiguous phase spans under it,
joined by `(replica, step)`), reduced for the per-layer readers. All
from `ctx["bench"]`: `spans` (the ring as the run left it), `t_open`,
`t_close`, on the host's `perf_counter`. A program that writes no step
spans (the parent of the PR that added them) gives every reader None.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from . import common
from .trace_reduce import clip, union_len

ENGINE_PHASES = ("retire", "admit", "keys", "accept", "observe")
CACHE_PHASES = ("alloc", "build")


def steps(ctx) -> List[dict]:
    """Every step in the ring, oldest first: `{"span": the step span,
    "phases": its phase spans in the order written}`."""
    by_key: Dict[Tuple, dict] = {}
    for ev in ctx["bench"].get("spans", []):
        if ev.get("comp") == "step":
            by_key.setdefault((ev.get("replica"), ev["step"]),
                              {"phases": []})["span"] = ev
        elif ev.get("parent") == "step":
            by_key.setdefault((ev.get("replica"), ev["step"]),
                              {"phases": []})["phases"].append(ev)
    whole = [s for s in by_key.values() if "span" in s]
    return sorted(whole, key=lambda s: s["span"]["t0"])


def in_window(ctx, all_steps: Optional[List[dict]] = None) -> List[dict]:
    """The steps (of `steps(ctx)`) that began inside `[t_open,
    t_close]`."""
    b = ctx["bench"]
    return [s for s in (steps(ctx) if all_steps is None else all_steps)
            if b["t_open"] <= s["span"]["t0"] <= b["t_close"]]


def in_flight(step: dict) -> List[Tuple[float, float]]:
    """From each `dispatch`'s start to the end of the next `sync` of the
    same step: the time a program was enqueued or running. A dispatch
    that nothing fetched (the draft's prompt prefill) runs into the
    next one's interval."""
    out = []
    start = None
    for ph in step["phases"]:
        if ph["comp"] == "dispatch" and start is None:
            start = ph["t0"]
        elif ph["comp"] == "sync" and start is not None:
            out.append((start, ph["t1"]))
            start = None
    return out


def host_gap_share(ctx) -> Optional[float]:
    """Share (%) of `[t_open, t_close]` outside every in-flight
    interval: the host's share of the window, with no profiler."""
    b = ctx["bench"]
    window = (b["t_open"], b["t_close"])
    all_steps = steps(ctx)
    if not all_steps or window[1] <= window[0]:
        return None
    held = [iv for s in all_steps for iv in
            (clip(x, window) for x in in_flight(s)) if iv is not None]
    return 100.0 * (1.0 - union_len(held) / (window[1] - window[0]))


def phases_ms_p50(ctx, names: Sequence[str]) -> Optional[float]:
    """Per step the seconds in the phases called `names`, summed;
    median over the window's steps, in ms."""
    per_step = [sum(ph["t1"] - ph["t0"] for ph in s["phases"]
                    if ph["comp"] in names) for s in in_window(ctx)]
    if not per_step:
        return None
    return 1e3 * common.quantile(per_step, 0.5)


def dispatch_ms_p50(ctx) -> Optional[float]:
    """Median `dispatch` span of the window's steps, in ms: the jitted
    call until it returns."""
    calls = [ph["t1"] - ph["t0"] for s in in_window(ctx)
             for ph in s["phases"] if ph["comp"] == "dispatch"]
    if not calls:
        return None
    return 1e3 * common.quantile(calls, 0.5)


def recompiles(ctx) -> Optional[float]:
    """`executables` of the window's last step less that of the last
    step before the window opened (of the window's first step, where
    the ring holds none before it)."""
    all_steps = steps(ctx)
    window = in_window(ctx, all_steps)
    if not window:
        return None
    before = [s for s in all_steps
              if s["span"]["t0"] < ctx["bench"]["t_open"]]
    base = (before[-1] if before else window[0])["span"]["executables"]
    return float(window[-1]["span"]["executables"] - base)
