"""The flash prefill-attention kernel (`ops/pallas_kernels.py`, named
`flash_prefill_attention` in a device trace) against the arithmetic the
prompts' causal attention needs. From `ctx["trace"]` (`per_op_s`,
`per_op_calls`), the program's `prefill` spans in `ctx["bench"]["spans"]`
and the prompt lengths of `ctx["bench"]["records"]`, as
`prefill_bucket_fill` takes them. A program without the kernel (the
parent of the PR that added it) gives None.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

KERNEL = "flash_prefill_attention"


def attention_flops_mean(ctx) -> Optional[float]:
    """FLOPs of one kernel call: the causal attention of the TRUE
    prompts of one prefill dispatch (the spans of one tick that share
    a start are one dispatch), both matmuls over the triangle only,
    2 x n_embd x len x (len + 1) a row; mean over the window's
    dispatches."""
    b = ctx["bench"]
    plen = {r.rid: len(r.item.ids) for r in b["records"]}
    flops: Dict[Tuple, float] = {}
    for ev in b.get("spans", []):
        if ev.get("comp") == "prefill" and ev["rid"] in plen and \
                b["t_open"] <= ev["t0"] <= b["t_close"]:
            n = plen[ev["rid"]]
            key = (ev.get("tick"), ev["t0"])
            flops[key] = flops.get(key, 0.0) + \
                2.0 * ctx["config"]["n_embd"] * n * (n + 1)
    if not flops:
        return None
    return sum(flops.values()) / len(flops)


def roofline(ctx) -> Optional[float]:
    """Share (%) of the chip's bf16 peak that a call of the kernel
    reaches, counting the triangle of the true prompts at the model's
    head size. Not the bucket, not the block-granular diagonal, not a
    head padded to the MXU's width: the same work whatever implements
    it, so padding and skipped-but-paid steps show as a low share.
    Bound by arithmetic (the scores never leave the chip)."""
    tr = ctx["trace"]
    names = [k for k in tr["per_op_s"] if k.startswith(KERNEL)]
    secs = sum(tr["per_op_s"][k] for k in names)
    calls = sum(tr["per_op_calls"].get(k, 0) for k in names)
    flops = attention_flops_mean(ctx)
    if not secs or not calls or flops is None or not ctx["peaks"]:
        return None
    least_s = flops / ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * least_s / (secs / calls)
