"""The retention decode kernel (`ops/pallas_kernels.py`, named
`retention_decode` in a device trace) against the bytes that any
implementation of the step has to read, and the program's count of
live state rows. From `ctx["trace"]` (`per_op_s`, `per_op_calls`,
`window_s`), the program's `decode` spans in `ctx["bench"]["spans"]`
(one a live slot of a dispatch) and its `step` spans, which carry
`state_rows_live`. A program without the kernel, the spans or the
field (the parent of the PR that added them) gives None.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

KERNEL = "retention_decode"
_STATE_ITEMSIZE = 4          # the state is float32


def state_features(cfg: dict) -> int:
    """D: the size of the symmetric second tensor power of a head, each
    unordered pair of components once."""
    n = cfg["head_dim"]
    return n * (n + 1) // 2


def state_bytes_per_slot(cfg: dict) -> int:
    """Bytes of one request's state in ONE layer: for each key-value
    head S [D, head] and z [D], float32. The least a token-step has to
    read of it; the write-back, and whatever a layout pads D to, are
    the implementation's cost and are not counted."""
    d, hd = state_features(cfg), cfg["head_dim"]
    return cfg["num_key_value_heads"] * (d * hd + d) * _STATE_ITEMSIZE


def live_slots_mean(ctx) -> Optional[float]:
    """Live slots of one decode dispatch (the `decode` spans of one
    tick that share a start are one dispatch, one span a live slot),
    mean over the window's dispatches."""
    b = ctx["bench"]
    live: Dict[Tuple, int] = {}
    for ev in b.get("spans", []):
        if ev.get("comp") == "decode" and \
                b["t_open"] <= ev["t0"] <= b["t_close"]:
            key = (ev.get("tick"), ev["t0"])
            live[key] = live.get(key, 0) + 1
    if not live:
        return None
    return sum(live.values()) / len(live)


def _kernel(ctx):
    """(device seconds, calls) of the kernel in the traced window."""
    tr = ctx["trace"]
    names = [k for k in tr["per_op_s"] if k.startswith(KERNEL)]
    return (sum(tr["per_op_s"][k] for k in names),
            sum(tr["per_op_calls"].get(k, 0) for k in names))


def roofline(ctx) -> Optional[float]:
    """Share (%) of the chip's memory bandwidth that a call of the
    kernel (one layer, one token-step) reaches, counting the state of
    the dispatch's live slots read ONCE: a kernel that also writes
    every row back each step cannot read above 50%, one that defers
    the write cannot read above 100%. Bound by bytes."""
    secs, calls = _kernel(ctx)
    live = live_slots_mean(ctx)
    if not secs or not calls or live is None or not ctx["peaks"]:
        return None
    least_s = live * state_bytes_per_slot(ctx["config"]) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (secs / calls)


def device_share(ctx) -> Optional[float]:
    """The kernel's device time as a share (%) of the traced window."""
    secs, calls = _kernel(ctx)
    if not calls or not ctx["trace"]["window_s"]:
        return None
    return 100.0 * secs / ctx["trace"]["window_s"]


def rows_live_share(ctx) -> Optional[float]:
    """State rows held by requests, of the rows there are (the scratch
    row apart): mean over the window's engine steps, from the `step`
    spans' `state_rows_live`."""
    b = ctx["bench"]
    rows = [ev["state_rows_live"] for ev in b.get("spans", [])
            if ev.get("comp") == "step" and "state_rows_live" in ev
            and b["t_open"] <= ev["t0"] <= b["t_close"]]
    if not rows:
        return None
    return 100.0 * sum(rows) / len(rows) / b["pages_total"]
