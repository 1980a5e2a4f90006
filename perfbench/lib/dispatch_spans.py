"""The program's dispatches split where the fetched output's device
buffer was ready, and the counts the engine writes on its `step` spans,
reduced for the per-layer readers.

reqtrace writes on a traced step: a `sync` phase carries `ready`, the
`perf_counter` time at which the output it fetches was ready on the
device (the rest of the phase is the copy to the host); the `step` span
carries `lanes` (the requests its decode dispatch carried) where the
step decodes, and `pages_live` (the paged cache's live pages at its
close). From `ctx["bench"]` (`spans`, the ring as the run left it;
`t_open`, `t_close`, `max_slots`, `pages_total`) and `ctx["trace"]`
(`window_s`, `busy_s`). A program that writes none of these (the parent
of the PR that added them) gives every reader None.

The capture's window on the ring's clock is `[t_close - window_s,
t_close]`: in the closed loops the kind's `on_close` ends the
`bench:window` span at the completion stamp `t_close`.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

from . import step_spans
from .trace_reduce import clip, union_len


def captured_window(ctx) -> Optional[Tuple[float, float]]:
    """The profiler's window on the ring's clock, or None without a
    capture."""
    window_s = ctx["trace"]["window_s"]
    if not window_s:
        return None
    t_close = ctx["bench"]["t_close"]
    return (t_close - window_s, t_close)


def fetches(step: dict) -> List[Tuple[float, float, float]]:
    """Per dispatch of one step that fetched, and whose `sync` carries
    `ready`: (the `dispatch` start, `ready`, the `sync` end). A
    dispatch that nothing fetched (the draft's prompt prefill) runs into
    the next one, as in `step_spans.in_flight`."""
    out = []
    start = None
    for ph in step["phases"]:
        if ph["comp"] == "dispatch" and start is None:
            start = ph["t0"]
        elif ph["comp"] == "sync" and start is not None:
            if "ready" in ph:
                out.append((start, ph["ready"], ph["t1"]))
            start = None
    return out


def _captured(ctx, part) -> Optional[float]:
    """Union (s) of `part(dispatch start, ready, sync end)` over the
    window's dispatches, each clipped to the captured window; None where
    nothing carries `ready` or there is no capture."""
    window = captured_window(ctx)
    if window is None:
        return None
    ivs = [part(*f) for s in step_spans.steps(ctx) for f in fetches(s)]
    if not ivs:
        return None
    return union_len([iv for iv in (clip(x, window) for x in ivs)
                      if iv is not None])


def inflight_idle_share(ctx) -> Optional[float]:
    """Share (%) of the captured window in which a program was being
    enqueued or was running (a `dispatch` start to its `sync`'s
    `ready`) and the device ran no op: that union less the capture's
    busy seconds."""
    held = _captured(ctx, lambda t0, ready, t1: (t0, ready))
    if held is None:
        return None
    tr = ctx["trace"]
    return 100.0 * (held - tr["busy_s"]) / tr["window_s"]


def fetch_tail_share(ctx) -> Optional[float]:
    """Share (%) of the captured window in which the host was taking a
    result the device had finished (`ready` to the `sync`'s end)."""
    tail = _captured(ctx, lambda t0, ready, t1: (ready, t1))
    if tail is None:
        return None
    return 100.0 * tail / ctx["trace"]["window_s"]


def _window_counts(ctx, field: str) -> List[float]:
    """`field` of the `step` spans that began inside `[t_open,
    t_close]` and carry it."""
    return [s["span"][field] for s in step_spans.in_window(ctx)
            if field in s["span"]]


def lanes_live_share(ctx) -> Optional[float]:
    """Requests a decode dispatch carried, of the slots there are: mean
    over the window's steps that decoded."""
    lanes = _window_counts(ctx, "lanes")
    if not lanes:
        return None
    return 100.0 * sum(lanes) / len(lanes) / ctx["bench"]["max_slots"]


def pages_live_peak_share(ctx) -> Optional[float]:
    """Most live pages at a step's close, of the pages there are (the
    scratch page apart), over the window's steps."""
    pages = _window_counts(ctx, "pages_live")
    if not pages:
        return None
    return 100.0 * max(pages) / ctx["bench"]["pages_total"]
