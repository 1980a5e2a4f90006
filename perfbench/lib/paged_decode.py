"""The paged decode-attention kernel (`ops/pallas_kernels.py`, named
`paged_decode_attention` in a device trace) against the bytes it has
to read. From `ctx["trace"]` (`per_op_s`, `per_op_calls`) and the
program's `decode` spans in `ctx["bench"]["spans"]`, which carry
`tokens`: what the request held at the dispatch. A program without the
kernel or the field (the parent of the PR that added them) gives None.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

KERNEL = "paged_decode_attention"
_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def tokens_held_mean(ctx) -> Optional[float]:
    """Tokens held by the slots of one decode dispatch (the spans of
    one tick that share a start are one dispatch), mean over the
    window's dispatches."""
    b = ctx["bench"]
    held: Dict[Tuple, int] = {}
    for ev in b.get("spans", []):
        if ev.get("comp") == "decode" and "tokens" in ev and \
                b["t_open"] <= ev["t0"] <= b["t_close"]:
            key = (ev.get("tick"), ev["t0"])
            held[key] = held.get(key, 0) + ev["tokens"]
    if not held:
        return None
    return sum(held.values()) / len(held)


def roofline(ctx) -> Optional[float]:
    """Share (%) of the chip's memory bandwidth that a call of the
    kernel reaches, counting the logical K/V bytes: tokens held x 2
    (K and V) x heads x head size x the cache's item size. Not the
    padded page, not the table's width: the same work whatever
    implements it, so padding and dead steps show as a low share."""
    tr, cfg = ctx["trace"], ctx["config"]
    names = [k for k in tr["per_op_s"] if k.startswith(KERNEL)]
    secs = sum(tr["per_op_s"][k] for k in names)
    calls = sum(tr["per_op_calls"].get(k, 0) for k in names)
    tokens = tokens_held_mean(ctx)
    if not secs or not calls or tokens is None or not ctx["peaks"]:
        return None
    per_token = 2 * cfg["n_embd"] * _ITEMSIZE[cfg["precision"]["cache"]]
    least_s = tokens * per_token / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (secs / calls)
