"""Readers of an expert model behind the paged cache (the configuration
`mellum2-12b-a2.5b`): the routed experts' grouped matmuls, the load the
router puts on them, and the two paged attention kernels with K/V heads
shared by groups of query heads and a window on some layers.

From `ctx["trace"]` (`per_op_s`, `window_s`), `ctx["config"]`, and the
program's spans in `ctx["bench"]["spans"]`: a traced step's `sync`
phase carries `expert_rows` (rows routed in all, over layers and
token-steps), `expert_rows_peak` (the sum over layers of the busiest
expert's rows) and `experts_hit` (the sum over layers and token-steps
of the experts that got a row: whose weights were read); `decode` spans
carry `tokens` (what a request held at the dispatch); `prefill` spans
join the prompt lengths of `ctx["bench"]["records"]`. A program without
the kernels or the fields (the parent of the PR that added them) gives
None.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from . import dispatch_spans, step_spans

# the expert layer's grouped matmul in a device trace: JAX's megablox
# kernel (`gmm`), or XLA's ragged dot
EXPERT_KERNELS = ("gmm", "ragged-dot")
DECODE_KERNEL = "paged_decode_attention"
PREFILL_KERNEL = "flash_prefill_attention"
_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def _kernel_s(ctx, names) -> float:
    return sum(v for k, v in ctx["trace"]["per_op_s"].items()
               if k.startswith(names))


def _calls(ctx, names) -> int:
    return sum(v for k, v in ctx["trace"]["per_op_calls"].items()
               if k.startswith(names))


def windows(cfg: dict) -> List[Optional[int]]:
    """Each layer's window (None: full attention)."""
    return [cfg["sliding_window"] if kind == "sliding_attention" else None
            for kind in cfg["layer_types"][:cfg["num_hidden_layers"]]]


def expert_bytes(cfg: dict) -> int:
    """One expert's weights: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] \
        * _ITEMSIZE[cfg["precision"]["weights"]]


def least_expert_s(cfg: dict, peaks: dict, rows: int, hit: int) -> float:
    """The least time the chip could take for a dispatch's expert
    matmuls: FLOPs 2 x rows x H x F x 3 at the bf16 peak, or the bytes
    of the experts hit plus each row's activation in and out at the
    memory bandwidth, whichever is longer."""
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    item = _ITEMSIZE[cfg["precision"]["compute"]]
    flops = 2.0 * rows * h * f * 3
    moved = hit * expert_bytes(cfg) + rows * 2 * h * item
    return max(flops / peaks["bf16_flops_per_s"],
               moved / peaks["hbm_bytes_per_s"])


def _syncs(ctx, window: Optional[Tuple[float, float]]) -> List[dict]:
    """The `sync` phases that carry expert counts, those that ended in
    `window` ([t_open, t_close] when None)."""
    b = ctx["bench"]
    lo, hi = window or (b["t_open"], b["t_close"])
    return [ph for s in step_spans.steps(ctx) for ph in s["phases"]
            if ph["comp"] == "sync" and "expert_rows" in ph
            and lo <= ph["t1"] <= hi]


def gmm_roofline(ctx) -> Optional[float]:
    """Share (%) of its roofline that the expert matmuls reach: the sum,
    over the dispatches fetched inside the capture, of the least time
    their expert rows need (`least_expert_s`), over the expert kernels'
    device time in the capture."""
    window = dispatch_spans.captured_window(ctx)
    secs = _kernel_s(ctx, EXPERT_KERNELS)
    if window is None or not secs or not ctx["peaks"]:
        return None
    syncs = _syncs(ctx, window)
    if not syncs:
        return None
    least = sum(least_expert_s(ctx["config"], ctx["peaks"],
                               ph["expert_rows"], ph["experts_hit"])
                for ph in syncs)
    return 100.0 * least / secs


def device_share(ctx) -> Optional[float]:
    """The expert kernels' device time, of the traced window."""
    window_s = ctx["trace"]["window_s"]
    if not window_s or not _calls(ctx, EXPERT_KERNELS):
        return None
    return 100.0 * _kernel_s(ctx, EXPERT_KERNELS) / window_s


def load_peak_over_mean(ctx) -> Optional[float]:
    """The busiest expert's rows over an expert's mean rows, summed over
    layers (`expert_rows_peak` / (`expert_rows` / n_experts)), mean
    over the window's dispatches: 1 for a router that spreads rows
    evenly, n_experts / top_k at most (every token of a layer routed
    to the same expert)."""
    n = ctx["config"]["num_experts"]
    ratios = [ph["expert_rows_peak"] / (ph["expert_rows"] / n)
              for ph in _syncs(ctx, None) if ph["expert_rows"]]
    if not ratios:
        return None
    return sum(ratios) / len(ratios)


def decode_kv_bytes(cfg: dict, tokens: List[int]) -> float:
    """The logical K/V bytes one decode token-step reads over all
    layers for lanes holding `tokens`: each layer's keys and values of
    the positions it attends (a windowed layer the last `window`),
    K/V heads x head size, in the cache's type."""
    row = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] \
        * _ITEMSIZE[cfg["precision"]["cache"]]
    return float(sum(row * (n if w is None else min(n, w))
                     for w in windows(cfg) for n in tokens))


def _decode_tokens(ctx) -> Dict[Tuple, List[int]]:
    """The tokens held by each lane of each decode dispatch in
    [t_open, t_close] (the spans of one tick that share a start are one
    dispatch)."""
    b = ctx["bench"]
    held: Dict[Tuple, List[int]] = {}
    for ev in b.get("spans", []):
        if ev.get("comp") == "decode" and "tokens" in ev and \
                b["t_open"] <= ev["t0"] <= b["t_close"]:
            held.setdefault((ev.get("tick"), ev["t0"]), []).append(
                ev["tokens"])
    return held


def paged_decode_roofline(ctx) -> Optional[float]:
    """Share (%) of the memory roofline that a call of the decode kernel
    (one layer of one token-step) reaches: the logical K/V bytes of a
    dispatch's token-step (`decode_kv_bytes`), mean over the window's
    dispatches and over the layers, over the kernel's mean device time
    a call. Bound by bytes."""
    cfg = ctx["config"]
    secs, calls = _kernel_s(ctx, DECODE_KERNEL), _calls(ctx, DECODE_KERNEL)
    held = _decode_tokens(ctx)
    if not secs or not calls or not held or not ctx["peaks"]:
        return None
    per_call = sum(decode_kv_bytes(cfg, t) for t in held.values()) \
        / len(held) / cfg["num_hidden_layers"]
    return 100.0 * per_call / ctx["peaks"]["hbm_bytes_per_s"] \
        / (secs / calls)


def prompt_attention_flops(cfg: dict, n: int) -> float:
    """FLOPs of one prompt of n tokens' attention over all layers: both
    matmuls, 4 x query heads x head size a (query, key) link, over the
    causal triangle on a full layer and the band of `window` on a
    windowed one."""
    per_link = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]
    links = 0.0
    for w in windows(cfg):
        m = n if w is None else min(n, w)
        # sum over t < n of min(t + 1, m)
        links += m * (m + 1) / 2.0 + (n - m) * m
    return per_link * links


def prefill_attention_roofline(ctx) -> Optional[float]:
    """Share (%) of the bf16 peak that a call of the prefill kernel (one
    layer of a dispatch) reaches, counting the TRUE prompts' links
    (`prompt_attention_flops`), mean over the window's dispatches and
    the layers, over the kernel's mean device time a call. Bound by
    arithmetic."""
    b, cfg = ctx["bench"], ctx["config"]
    secs, calls = _kernel_s(ctx, PREFILL_KERNEL), _calls(ctx, PREFILL_KERNEL)
    plen = {r.rid: len(r.item.ids) for r in b["records"]}
    flops: Dict[Tuple, float] = {}
    for ev in b.get("spans", []):
        if ev.get("comp") == "prefill" and ev["rid"] in plen and \
                b["t_open"] <= ev["t0"] <= b["t_close"]:
            key = (ev.get("tick"), ev["t0"])
            flops[key] = flops.get(key, 0.0) + prompt_attention_flops(
                cfg, plen[ev["rid"]])
    if not secs or not calls or not flops or not ctx["peaks"]:
        return None
    per_call = sum(flops.values()) / len(flops) / cfg["num_hidden_layers"]
    return 100.0 * per_call / ctx["peaks"]["bf16_flops_per_s"] \
        / (secs / calls)
