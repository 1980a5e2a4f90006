"""What the per-layer readers share. A reader takes `ctx` (the reduced
trace, the benchmark's own records, the cell's files, the peaks) and
returns a number, or None where it finds nothing to read."""
from __future__ import annotations

from . import common


def share_of_peak(ctx, model_flops: float, seconds: float):
    if not seconds or not model_flops or not ctx["peaks"]:
        return None
    return 100.0 * model_flops / seconds / ctx["chips"] \
        / ctx["peaks"]["bf16_flops_per_s"]


def _pages_share(ctx, n_pages: int, ops):
    """Device time of the ops (by the start of their name) whose result
    is `n_pages` K/V pages, [n_pages, block_size, heads, head], of the
    traced window."""
    e, cfg = ctx["config"]["engine"], ctx["config"]
    shape = "_{}_{}_{}_{}_".format(n_pages, e["block_size"], cfg["n_head"],
                                   cfg["n_embd"] // cfg["n_head"])
    tr = ctx["trace"]
    if not tr["window_s"]:
        return None
    secs = sum(v for k, v in tr["per_op_s"].items()
               if k.startswith(ops) and k.endswith(shape))
    return 100.0 * secs / tr["window_s"]


def pool_copy_share(ctx):
    """Copies of a whole K/V page pool."""
    return _pages_share(ctx, ctx["config"]["engine"]["n_blocks"],
                        ("copy",))


def decode_gather_share(ctx):
    """The decode program's gather of every slot's whole block table
    (max_slots x table width pages a layer, whatever the slots hold)
    and the copy that re-lays it out."""
    e = ctx["config"]["engine"]
    width = -(-e["max_total_tokens"] // e["block_size"])
    return _pages_share(ctx, e["max_slots"] * width, ("copy", "fusion"))


def span_ms_p50(ctx, comp: str):
    """Median host duration of the program's own `comp` spans
    (reqtrace), one per dispatch (spans of one tick are one dispatch)."""
    b = ctx["bench"]
    seen = {}
    for ev in b.get("spans", []):
        if ev.get("comp") == comp and \
                b["t_open"] <= ev["t0"] <= b["t_close"]:
            seen[(ev.get("tick"), ev["t0"])] = ev["t1"] - ev["t0"]
    if not seen:
        return None
    return 1e3 * common.quantile(list(seen.values()), 0.5)


def serve_mfu(ctx):
    b = ctx["bench"]
    return share_of_peak(ctx, b.get("flops_in_window", 0.0),
                         b["elapsed_s"])
