"""Step anatomy: in-graph scope attribution for the fused train step.

The reference ships a first-class profiler that attributes time to
named regions (platform/profiler.h:210 RecordEvent); our single-dispatch
engines deliberately destroyed that visibility — the whole train step is
ONE jitted (shard_map) program, so host-side spans see only its outer
edge. This module restores attribution INSIDE the one executable:

1. **Scopes** — ``scope("attn")`` wraps ``jax.named_scope``: the name
   rides the jaxpr name stack into HLO op metadata
   (``op_name="jit(step)/.../attn/dot_general"``) and survives every
   transform XLA applies — backward ops carry
   ``transpose(jvp(attn))``, fusions keep the root op's path. Scope
   annotation is pure metadata: it changes no jaxpr, no cache key, no
   executable (RecompileSentinel-guarded in tests/test_anatomy.py).
   When the flight recorder is armed, the first entry of each scope
   name leaves a ``scope`` breadcrumb (once per name — model blocks
   enter scopes every forward; flooding the ring would evict real
   forensics).

2. **Static attribution (CPU-testable tier)** — ``attribute_hlo_text``
   walks the compiled executable's HLO text, prices every instruction
   with a local mini cost model (dot: 2·prod(result)·prod(contracted);
   convolution: 2·prod(result)·prod(kernel)/out_features; elementwise/
   transcendental: 1 FLOP/element; data movement: 0), groups FLOPs and
   result bytes by the innermost registered scope in each op's
   metadata path, and emits a per-scope share table that sums to
   exactly 1.0 (an ``unattributed`` row catches strays). This runs in
   tier-1 on CPU from AOT lowering alone — every future PR gets a free
   "which component grew" receipt without hardware. The compiler's own
   ``cost_analysis()`` total rides alongside as ``cost_analysis_flops``
   so the mini model's coverage is itself measurable.

Caveats (documented, not hidden): instructions inside ``while`` bodies
(lax.scan — grad_accum>1, scan_layers, the spmd_1f1b tick loop) are
counted once, not per trip — the same convention XLA's HloCostAnalysis
uses; shares WITHIN the loop stay comparable, cross-loop shares
understate the loop. The TrainStep path the tier-1 receipt pins has no
loops at grad_accum=1.

Device-time attribution (tier two — which scope the chip actually spent
ms on, and whether comm overlapped backward) lives in
``observability.xprof``; both tiers share this module's taxonomy so the
static and measured tables line up row-for-row.
"""
from __future__ import annotations

import logging
import re
from contextlib import contextmanager
from typing import Any, Dict, Iterable, Optional, Set

from . import flight_recorder as _fr
from . import metrics

__all__ = [
    "scope", "known_scopes", "register_scope", "CORE_SCOPES",
    "scope_of_op_name", "attribute_hlo_text", "attribute_compiled",
    "compile_uncached", "train_step_anatomy", "publish",
    "format_table",
]

logger = logging.getLogger("paddle_tpu.observability")

# The step taxonomy every attribution surface shares (anatomy static
# tier, xprof device tier, tools/tpu_breakdown.py isolated components,
# tools/step_anatomy.py): the named pieces of one ERNIE-class train
# step. scope() registers any further name on first use.
CORE_SCOPES = (
    "embed",        # token/position/type embeddings + their norm
    "attn",         # qkv/proj matmuls, SDPA/flash, residual + norm
    "mlp",          # ffn matmuls (or MoE experts), residual + norm
    "mlm_head_ce",  # mlm transform + tied-decoder logits + softmax-CE
    "loss_scale",   # amp scale/unscale, finite check, skip-step select
    "optimizer",    # the update rule (AdamW etc.)
    "grad_sync",    # comm.py fused-bucket gradient collectives
    "pp_ring",      # pipeline ppermute activation/grad transfers
)

_SCOPES: Set[str] = set(CORE_SCOPES)
_BREADCRUMBED: Set[str] = set()

_jax = None  # lazily bound: this module must import without jax
#              (xprof/tools triage paths; same rule as flight_recorder)


def _get_jax():
    global _jax
    if _jax is None:
        import jax
        _jax = jax
    return _jax


def register_scope(name: str) -> str:
    """Add a name to the attribution taxonomy (scope() does this
    automatically; exposed for parsers fed externally-annotated HLO)."""
    if not name or "/" in name:
        raise ValueError(f"scope name {name!r}: non-empty, no '/'")
    _SCOPES.add(name)
    return name


def known_scopes() -> Set[str]:
    """The registered taxonomy (a copy)."""
    return set(_SCOPES)


@contextmanager
def scope(name: str):
    """Annotate everything traced inside with `name`.

    Wraps ``jax.named_scope``: at trace time the name lands in HLO op
    metadata (and survives jvp/transpose into the backward); in eager
    mode it is a thread-local push/pop (~µs). Registers the name in the
    taxonomy and, when the flight recorder is armed, records a one-time
    ``scope`` breadcrumb so dumps carry the taxonomy that was live.
    """
    _SCOPES.add(name)
    if _fr.enabled() and name not in _BREADCRUMBED:
        _BREADCRUMBED.add(name)
        _fr.record("scope", name=name)
    with _get_jax().named_scope(name):
        yield


# ---------------------------------------------------------------------------
# scope extraction from HLO op metadata
# ---------------------------------------------------------------------------

_TOKEN_SPLIT = re.compile(r"[()\[\]{} ]+")


def scope_of_op_name(op_name: str,
                     scopes: Optional[Iterable[str]] = None
                     ) -> Optional[str]:
    """Innermost registered scope in an HLO ``op_name`` path.

    Paths look like ``jit(step)/jit(main)/transpose(jvp(attn))/mlp/dot``
    — components are named_scope frames, possibly wrapped by transform
    frames (``jvp(...)``, ``transpose(...)``, ``vmap(...)``). The
    deepest component containing a registered scope token wins (a
    backward op of a nested scope attributes to the nested scope).
    """
    want = _SCOPES if scopes is None else set(scopes)
    for comp in reversed(op_name.split("/")):
        toks = [t for t in _TOKEN_SPLIT.split(comp) if t]
        for tok in reversed(toks):
            if tok in want:
                return tok
    return None


# ---------------------------------------------------------------------------
# the mini cost model over HLO text
# ---------------------------------------------------------------------------

# one instruction line: `  [ROOT] %name = <type> opcode(...), ...`
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*"
    r"(?P<type>\(?[a-z0-9]+\[[\d,]*\][^\s]*)\s+"
    r"(?P<op>[\w\-]+)\(")
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([\d,]*)\]")
_META_RE = re.compile(r'metadata=\{[^{}]*op_name="([^"]+)"')
_LHS_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_FEATURE_GROUP_RE = re.compile(r"feature_group_count=(\d+)")
_DIM_LABELS_RE = re.compile(r"dim_labels=\w+_(\w+)->")

_ITEMSIZE = {"pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2,
             "u16": 2, "f16": 2, "bf16": 2, "f8e4m3": 1, "f8e5m2": 1,
             "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
             "f64": 8, "c64": 8, "c128": 16}

# opcodes priced at 1 FLOP per result element (arithmetic +
# transcendental — precision of the per-op constant washes out of a
# SHARE table; matmuls dominate any real step)
_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum",
    "negate", "abs", "exponential", "exponential-minus-one", "log",
    "log-plus-one", "tanh", "logistic", "rsqrt", "sqrt", "cbrt",
    "power", "atan2", "sine", "cosine", "tan", "erf", "sign",
    "remainder", "floor", "ceil", "round-nearest-afz",
    "round-nearest-even", "clamp", "select", "and", "or", "xor", "not",
    "compare", "shift-left", "shift-right-arithmetic",
    "shift-right-logical",
}
# containers: their member instructions are priced where they are
# listed, so the call site itself is skipped outright (counting it
# would double the bytes/op count of the fused root)
_CONTAINERS = {"fusion", "call", "while", "conditional", "map"}

# pure data movement / bookkeeping: 0 FLOPs (bytes still counted)
_ZERO_FLOP = {
    "parameter", "constant", "broadcast", "reshape", "transpose",
    "copy", "copy-start", "copy-done", "bitcast", "bitcast-convert",
    "convert", "tuple", "get-tuple-element", "slice", "dynamic-slice",
    "dynamic-update-slice", "concatenate", "pad", "iota", "reverse",
    "gather", "scatter", "rng", "rng-bit-generator", "after-all",
    "partition-id", "replica-id", "domain", "optimization-barrier",
    "fusion", "call", "while", "conditional", "custom-call", "map",
    "sort", "infeed", "outfeed", "send", "send-done", "recv",
    "recv-done", "all-reduce", "all-gather", "reduce-scatter",
    "all-to-all", "collective-permute", "all-reduce-start",
    "all-reduce-done", "collective-permute-start",
    "collective-permute-done", "async-start", "async-update",
    "async-done", "get-dimension-size",
}


def _first_shape(type_str: str):
    """(dtype, dims) of the first shape in a type expression (tuple
    types attribute by their first element — close enough for shares)."""
    m = _SHAPE_RE.search(type_str)
    if not m:
        return None, ()
    dims = tuple(int(d) for d in m.group(2).split(",") if d)
    return m.group(1), dims


def _prod(dims) -> float:
    out = 1.0
    for d in dims:
        out *= d
    return out


def _operand_shapes(line: str, op: str, dims_of: Dict[str, tuple]):
    """Shapes of the operands of `op(...)` on this line. Older XLA
    printed them inline (``dot(f32[8,4]{1,0} %a, ...)``); the installed
    one prints names only (``dot(%a, %b)``), which resolve through
    `dims_of` — every instruction's result dims by name."""
    i = line.find(op + "(")
    if i < 0:
        return []
    j = line.find(")", i)
    seg = line[i + len(op) + 1: j if j > 0 else len(line)]
    inline = [tuple(int(d) for d in m.group(2).split(",") if d)
              for m in _SHAPE_RE.finditer(seg)]
    if inline:
        return inline
    names = [t.strip().lstrip("%") for t in seg.split(",")]
    return [dims_of[n] for n in names if n in dims_of]


def _instr_flops(op: str, line: str, result_dims,
                 dims_of: Dict[str, tuple]) -> float:
    if op == "dot":
        ops = _operand_shapes(line, "dot", dims_of)
        m = _LHS_CONTRACT_RE.search(line)
        if ops and m is not None:
            lhs = ops[0]
            contracted = _prod(
                lhs[int(d)] for d in m.group(1).split(",") if d)
            return 2.0 * _prod(result_dims) * contracted
        return 2.0 * _prod(result_dims)
    if op == "convolution":
        ops = _operand_shapes(line, "convolution", dims_of)
        if len(ops) >= 2:
            kernel = ops[1]
            groups = 1
            g = _FEATURE_GROUP_RE.search(line)
            if g:
                groups = int(g.group(1))
            out_feat = kernel[-1]
            dl = _DIM_LABELS_RE.search(line)
            if dl:  # kernel dim labels, e.g. 01io: 'o' = out features
                o = dl.group(1).find("o")
                if 0 <= o < len(kernel):
                    out_feat = kernel[o]
            per_out = _prod(kernel) / max(out_feat, 1) / max(groups, 1)
            return 2.0 * _prod(result_dims) * per_out
        return 2.0 * _prod(result_dims)
    if op in ("reduce", "reduce-window"):
        ops = _operand_shapes(line, op, dims_of)
        return _prod(ops[0]) if ops else _prod(result_dims)
    if op in _ELEMENTWISE:
        return _prod(result_dims)
    return 0.0


def attribute_hlo_text(text: str,
                       scopes: Optional[Iterable[str]] = None) -> dict:
    """Walk HLO text (``compiled.as_text()``) and group the mini cost
    model's FLOPs / result bytes / op counts by scope.

    Returns ``{"scopes": {name: {flops, share, bytes, ops}},
    "total_flops", "total_bytes", "unattributed_share"}``. Shares are
    over the counted total, so they sum to exactly 1.0 (the
    ``unattributed`` row holds ops whose metadata names no registered
    scope). Fused computations are priced by their member instructions;
    the ``fusion`` call itself is free (no double count). While-loop
    bodies count once per program, not per trip (module docstring).
    """
    per: Dict[str, Dict[str, float]] = {}
    total_flops = 0.0
    total_bytes = 0.0
    parsed = [(line, m) for line in text.splitlines()
              for m in (_INSTR_RE.match(line),) if m]
    dims_of = {m.group("name"): _first_shape(m.group("type"))[1]
               for _, m in parsed}
    for line, m in parsed:
        op = m.group("op")
        if op in _CONTAINERS:
            continue
        dtype, dims = _first_shape(m.group("type"))
        if dtype is None:
            continue
        flops = _instr_flops(op, line, dims, dims_of)
        nbytes = _prod(dims) * _ITEMSIZE.get(dtype, 4)
        meta = _META_RE.search(line)
        sc = scope_of_op_name(meta.group(1), scopes) if meta else None
        key = sc or "unattributed"
        row = per.setdefault(key, {"flops": 0.0, "bytes": 0.0,
                                   "ops": 0})
        row["flops"] += flops
        row["bytes"] += nbytes
        row["ops"] += 1
        total_flops += flops
        total_bytes += nbytes
    table = {}
    for name, row in per.items():
        table[name] = {
            "flops": row["flops"],
            "share": (row["flops"] / total_flops) if total_flops else 0.0,
            "bytes": row["bytes"],
            "ops": int(row["ops"]),
        }
    unatt = table.get("unattributed", {}).get("share", 0.0)
    return {
        "scopes": dict(sorted(table.items(),
                              key=lambda kv: -kv[1]["flops"])),
        "total_flops": total_flops,
        "total_bytes": total_bytes,
        "unattributed_share": unatt,
    }


def attribute_compiled(compiled,
                       scopes: Optional[Iterable[str]] = None) -> dict:
    """Attribute a compiled executable (jax ``Compiled``); adds the
    compiler's own ``cost_analysis_flops`` next to the mini model's
    total so coverage is a measurable receipt, not an assumption."""
    out = attribute_hlo_text(compiled.as_text(), scopes)
    from .mfu import flops_of_compiled
    out["cost_analysis_flops"] = flops_of_compiled(compiled)
    return out


def compile_uncached(lowered):
    """Compile a Lowered OUTSIDE the persistent compilation cache.

    jax's cache key deliberately strips op metadata (renames must not
    bust the cache) — so a cache HIT can hand back an executable
    compiled BEFORE the current scope annotations existed, whose
    op_names silently attribute everything to ``unattributed`` (found
    live: a stale .jax_cache from a pre-anatomy round zeroed bench's
    share table). Attribution pays one fresh compile instead; the
    restore path resets jax's cache latches (jax latches the
    cache-disabled verdict at the first compile it sees) so the
    trainer's cache keeps working.
    """
    import jax
    prev = bool(jax.config.jax_enable_compilation_cache)
    try:
        jax.config.update("jax_enable_compilation_cache", False)
        return lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        if prev:
            try:
                from jax._src import compilation_cache as _cc
                _cc.reset_cache()  # un-latch the disabled verdict
            except Exception:  # pragma: no cover — internal API drift
                pass


def train_step_anatomy(step, inputs, labels=(), *,
                       publish_gauges: bool = False) -> dict:
    """Per-scope share table of a TrainStep's ONE train executable.

    AOT-lowers the step from avals (``TrainStep.aot_lower`` — separate
    from the jit call cache, so the recompile sentinel never sees it)
    and compiles cache-bypassed (``compile_uncached``): the text being
    attributed must be THIS program's, not a metadata-stripped cache
    ancestor's.
    """
    from ..jit.api import _unwrap_tree

    inputs = inputs if isinstance(inputs, (list, tuple)) else (inputs,)
    labels = labels if isinstance(labels, (list, tuple)) else (labels,)
    compiled = compile_uncached(
        step.aot_lower(_unwrap_tree(tuple(inputs)),
                       _unwrap_tree(tuple(labels))))
    out = attribute_compiled(compiled)
    if publish_gauges:
        publish(out)
    return out


def publish(result: dict, prefix: str = "anatomy"):
    """Route a share table through the metrics runtime:
    ``anatomy.flops_share{scope=}`` gauges + totals — always-on, so the
    receipt rides the Prometheus/JSONL exporters and fleet.aggregate()
    whether or not the hot-path gate is up."""
    for name, row in result.get("scopes", {}).items():
        metrics.gauge(f"{prefix}.flops_share", _always=True,
                      scope=name).set(round(row["share"], 6))
    metrics.gauge(f"{prefix}.total_flops", _always=True).set(
        result.get("total_flops", -1.0))
    ca = result.get("cost_analysis_flops")
    if ca is not None:
        metrics.gauge(f"{prefix}.cost_analysis_flops",
                      _always=True).set(ca)
    return result


def format_table(result: dict, title: str = "step anatomy") -> str:
    """Human-readable share table (tools/step_anatomy.py + bench)."""
    lines = [f"{title}: {result.get('total_flops', 0):.3e} FLOPs "
             f"(cost_analysis: {result.get('cost_analysis_flops', -1):.3e})"]
    lines.append(f"  {'scope':<14} {'share':>7} {'gflops':>10} "
                 f"{'mbytes':>9} {'ops':>5}")
    for name, row in result.get("scopes", {}).items():
        lines.append(
            f"  {name:<14} {row['share']:>6.1%} "
            f"{row['flops'] / 1e9:>10.3f} {row['bytes'] / 1e6:>9.2f} "
            f"{row['ops']:>5}")
    return "\n".join(lines)
