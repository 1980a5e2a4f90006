"""Readers of the engine's batches that do not depend on the cell:
how full the prefill buckets and the decode slots were. A reader takes
`ctx` and returns a number, or None where it finds nothing to read
(perfbench/lib/readers.py)."""
from __future__ import annotations


def bucket_fill(ctx):
    """True prompt tokens over the tokens the prefill programs computed
    (admit width x bucket), over the window's prefill dispatches."""
    b = ctx["bench"]
    plen = {r.rid: len(r.item.ids) for r in b["records"]}
    true = {}
    padded = {}
    for ev in b.get("spans", []):
        if ev.get("comp") != "prefill" or ev["rid"] not in plen:
            continue
        key = (ev.get("tick"), ev["t0"])
        true[key] = true.get(key, 0) + plen[ev["rid"]]
        padded[key] = ev["bucket"] * ev["width"]
    if not padded:
        return None
    return 100.0 * sum(true.values()) / sum(padded.values())


def batch_occupancy(ctx):
    """Decode slots in use over the slots there are, mean over the
    engine steps of the window that decoded."""
    b = ctx["bench"]
    act = [s["active"] for s in b["steps"] if s["active"]]
    if not act:
        return None
    return 100.0 * sum(act) / len(act) / b["max_slots"]
