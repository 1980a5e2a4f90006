"""ShardingPlan + MeshPlan: one layout declaration for the whole mesh.

TPU-native replacement for the reference's graph-surgery parallelism:
- DP          ≡ batch sharded over 'dp', params replicated; XLA emits the
               grad all-reduce (fleet c_allreduce_sum rewrite,
               meta_optimizers/graph_execution_optimizer.py)
- ZeRO 1/2/3  ≡ optimizer state / grads / params sharded over 'dp'
               (sharding_optimizer.py:33 — broadcast/reduce become
               compiler-placed all-gather/reduce-scatter)
- FSDP        ≡ params sharded over 'fsdp' (a second data axis); the
               compiler places the param all-gathers / grad
               reduce-scatters, and the explicit eager path
               (comm.ParamSynchronizer) reuses the fused buckets +
               bf16/int8-EF wire tiers
- TP          ≡ layer-annotated PartitionSpecs over 'tp'
               (collective.py:566 paddle.distributed.split)
- SP/CP       ≡ sequence dim sharded over 'sp' (ring attention)
- PP          ≡ stage params stacked on a leading dim sharded over 'pp'

ShardingPlan computes NamedShardings for every leaf of TrainStep's
pytrees. MeshPlan sits one level above: declare the logical axes
(data/fsdp/tp/pp) ONCE and the planner derives every param /
activation / optimizer-state spec for ERNIE-class models (embedding
tables over fsdp×tp, attention/FFN projections row/col-sharded per
their layer annotations, norms replicated), plus a GC3/TVM-flavored
cost model (bytes moved per collective × wire tier vs per-chip HBM)
that selects the layout from mesh shape + model dims when the caller
passes ``layout="auto"``.
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..framework import Tensor

__all__ = ["ShardingPlan", "PartitionSpec", "shard_tensor",
           "NamedSharding", "MeshPlan", "ModelDims", "LayoutCost",
           "candidate_layouts", "estimate_layout", "choose_layout",
           "LOGICAL_AXES"]

PartitionSpec = P

#: the planner's logical axis taxonomy, outermost to innermost:
#: 'pp' (stage ring), 'dp' (pure replication), 'fsdp' (data axis that
#: ALSO shards params/grads/opt state), 'tp' (operator sharding —
#: innermost so the heaviest collectives ride the fastest links)
LOGICAL_AXES = ("dp", "fsdp", "tp", "pp")


def _spec_for_param(name: str, tensor, rules):
    # explicit layer annotation wins (TP layers set `.sharding_spec`)
    spec = getattr(tensor, "sharding_spec", None) if tensor is not None \
        else None
    if spec is None:
        for pattern, s in rules.items():
            if re.search(pattern, name):
                spec = P(*s) if not isinstance(s, P) else s
                break
    return spec if spec is not None else P()


def _add_axis(spec: P, tensor, axis: str, axis_size: int):
    parts = list(spec) if len(spec) else []
    shape = tensor._data.shape if isinstance(tensor, Tensor) else \
        tensor.shape
    while len(parts) < len(shape):
        parts.append(None)
    if axis in [p for p in parts if p is not None]:
        return P(*parts)
    # choose the largest dim not already sharded and evenly divisible
    order = sorted(range(len(shape)), key=lambda i: -shape[i])
    for i in order:
        if parts[i] is None and shape[i] > 1 and \
                shape[i] % max(axis_size, 1) == 0:
            parts[i] = axis
            return P(*parts)
    return P(*parts)


class ShardingPlan:
    """Derives NamedShardings for params / optimizer state / data.

    zero_stage: 0 = plain DP (state replicated), 1/2 = optimizer state
    sharded over dp, 3 = params sharded too (FSDP).
    """

    def __init__(self, mesh: Mesh, rules: Dict[str, P] = None,
                 zero_stage: int = 0, dp_axis="dp", data_axes=("dp",),
                 batch_dim: int = 0, fsdp_axis: Optional[str] = None):
        self.mesh = mesh
        self.rules = rules or {}
        self.zero_stage = zero_stage
        self.dp_axis = dp_axis if dp_axis in mesh.axis_names else None
        self.fsdp_axis = fsdp_axis if (fsdp_axis and
                                       fsdp_axis in mesh.axis_names) \
            else None
        if self.fsdp_axis and self.fsdp_axis not in data_axes:
            data_axes = tuple(data_axes) + (self.fsdp_axis,)
        self.data_axes = tuple(a for a in data_axes
                               if a in mesh.axis_names)
        self.batch_dim = batch_dim

    def named(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def replicated(self) -> NamedSharding:
        return self.named(P())

    def _dp_size(self) -> int:
        if self.dp_axis is None:
            return 1
        return int(self.mesh.shape[self.dp_axis])

    def _sanitize(self, spec: P) -> P:
        """Drop spec axes absent from this plan's mesh, so a model
        annotated for (say) tp degrades to replicated on a dp-only mesh."""
        names = set(self.mesh.axis_names)

        def keep(p):
            if p is None:
                return None
            if isinstance(p, (tuple, list)):
                kept = tuple(a for a in p if a in names)
                return kept if kept else None
            return p if p in names else None
        return P(*[keep(p) for p in spec])

    def param_spec(self, name: str, tensor) -> P:
        # sanitize BEFORE the ZeRO-3 axis addition: a stale 'tp' label on
        # a dp-only mesh must not block _add_axis from dp-sharding the dim
        spec = self._sanitize(_spec_for_param(name, tensor, self.rules))
        if self.fsdp_axis:
            spec = _add_axis(spec, tensor, self.fsdp_axis,
                             int(self.mesh.shape[self.fsdp_axis]))
        if self.zero_stage >= 3 and self.dp_axis:
            spec = _add_axis(spec, tensor, self.dp_axis, self._dp_size())
        return spec

    def state_spec(self, name: str, tensor) -> P:
        """Optimizer-state sharding: ZeRO>=1 shards moments over dp."""
        base = self.param_spec(name, tensor)
        if self.zero_stage >= 1 and self.dp_axis:
            return _add_axis(base, tensor, self.dp_axis, self._dp_size())
        return base

    def data_spec(self, array) -> P:
        nd = np.ndim(array) if not isinstance(array, jax.ShapeDtypeStruct) \
            else len(array.shape)
        if nd == 0 or not self.data_axes:
            return P()
        parts = [None] * nd
        parts[self.batch_dim] = (self.data_axes if len(self.data_axes) > 1
                                 else self.data_axes[0])
        return P(*parts)

    # -- TrainStep integration ----------------------------------------------
    def step_shardings(self, train_step):
        """(in_shardings, out_shardings) for TrainStep._build's step fn
        signature:
            step(params, opt_state, buffers, strat, key, lr, inputs, labels)
              -> (params, opt_state, buffers, strat, loss, extras)
        The inputs/labels shardings are appended by TrainStep at first call
        (structure unknown until then) via data_spec()."""
        params = train_step.params
        state_tensors = train_step.layer.state_dict()

        p_shard = {k: self.named(self.param_spec(k, state_tensors.get(k)))
                   for k in params}
        # optimizer state mirrors each param's spec (+zero); leaves may
        # be ShapeDtypeStructs on the abstract (aot_lower) path
        def _nd(v):
            return len(v.shape) if hasattr(v, "shape") else np.ndim(v)
        opt_shard = {}
        for k, st in train_step.opt_state.items():
            opt_shard[k] = {
                n: (self.named(self.state_spec(k, state_tensors.get(k)))
                    if _nd(v) > 0 else self.replicated())
                for n, v in st.items()}
        buf_shard = {k: self.replicated() for k in train_step.buffers}

        # strategy state (DGC momentum/error buffers...): leaves keyed by
        # a param name shard like that param's optimizer state (so ZeRO's
        # memory win extends to them); other leaves replicate
        def strat_shardings(node):
            if isinstance(node, dict):
                out = {}
                for k, v in node.items():
                    if k in params and not isinstance(v, dict):
                        out[k] = self.named(self.state_spec(
                            k, state_tensors.get(k)))
                    else:
                        out[k] = strat_shardings(v)
                return out
            return self.replicated()
        strat_sh = strat_shardings(getattr(train_step, "strategy_state",
                                           {}))

        in_shardings = (p_shard, opt_shard, buf_shard, strat_sh,
                        self.replicated(), self.replicated())
        # extras (amp skip flag / sentry scalars) are tiny replicated
        # scalars riding the step outputs
        out_shardings = (p_shard, opt_shard, buf_shard, strat_sh,
                         self.replicated(), self.replicated())
        return in_shardings, out_shardings

    def place(self, array, spec: P):
        return jax.device_put(array, self.named(spec))

    def place_batch(self, arrays):
        """Shard a host batch across the dp axis (the DataLoader's
        device-put stage)."""
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(a, self.named(self.data_spec(a))),
            arrays)


def shard_tensor(tensor, mesh=None, placements=None, spec: P = None):
    """paddle.distributed.shard_tensor analogue: place a tensor with a
    PartitionSpec on the (global) mesh."""
    from .env import ensure_mesh
    mesh = mesh or ensure_mesh()
    spec = spec if spec is not None else P(*placements) \
        if placements else P()
    arr = tensor._data if isinstance(tensor, Tensor) else tensor
    placed = jax.device_put(arr, NamedSharding(mesh, spec))
    if isinstance(tensor, Tensor):
        tensor._data = placed
        tensor.sharding_spec = spec
        return tensor
    return Tensor(placed)


# ---------------------------------------------------------------------------
# MeshPlan: the unified planner. One layout declaration -> every spec.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ModelDims:
    """The handful of numbers the cost model needs about a model.

    Everything is in *elements* except dtype_bytes. ``opt_slots`` counts
    f32 optimizer moments per param (Adam = 2). ``largest_layer_params``
    bounds the transient full-layer all-gather FSDP materializes — when
    0 we approximate with n_params / n_layers.
    """
    n_params: int
    hidden: int
    n_layers: int
    vocab: int = 0
    seq: int = 128
    batch: int = 8
    dtype_bytes: int = 4
    opt_slots: int = 2
    largest_layer_params: int = 0

    @property
    def layer_params(self) -> int:
        if self.largest_layer_params:
            return self.largest_layer_params
        return max(self.n_params // max(self.n_layers, 1), 1)

    @classmethod
    def from_state_dict(cls, state, hidden: int, n_layers: int,
                        seq: int = 128, batch: int = 8,
                        dtype_bytes: int = 4, opt_slots: int = 2):
        sizes = [int(np.prod(getattr(v, "shape", ()) or (1,)))
                 for v in state.values()]
        return cls(n_params=int(sum(sizes)), hidden=hidden,
                   n_layers=n_layers, seq=seq, batch=batch,
                   dtype_bytes=dtype_bytes, opt_slots=opt_slots,
                   largest_layer_params=int(max(sizes) if sizes else 0))

    @classmethod
    def infer(cls, state, batch: int = 8, seq: int = 128,
              n_layers: Optional[int] = None, opt_slots: int = 2):
        """Best-effort dims from a bare state dict (no architecture
        metadata): hidden = the widest trailing dim of any matrix,
        n_layers = the matrix count unless given. Good enough for the
        plan-audit receipt a planner engine stamps on itself — the
        audit measures how wrong it is."""
        shapes = [tuple(getattr(v, "shape", ()) or (1,))
                  for v in state.values()]
        sizes = [int(np.prod(s)) for s in shapes]
        mats = [s for s in shapes if len(s) >= 2]
        hidden = max((s[-1] for s in mats), default=1)
        return cls(n_params=int(sum(sizes)), hidden=int(hidden),
                   n_layers=int(n_layers if n_layers is not None
                                else max(len(mats), 1)),
                   seq=seq, batch=batch, opt_slots=opt_slots,
                   largest_layer_params=int(max(sizes) if sizes
                                            else 0))


@dataclasses.dataclass(frozen=True)
class LayoutCost:
    """One candidate layout scored by the cost model.

    Byte units score relative rank (``cost``); since PR 18 every
    candidate ALSO carries two absolute step-time estimates —
    ``analytic_step_time_s`` from nominal spec-sheet constants and
    ``calibrated_step_time_s`` from the committed calibration table
    (None when no table matched) — plus ``used``, naming which one
    ranked this candidate. ``wire_by_axis`` decomposes the wire bytes
    per logical axis with collective-call counts, the shape the
    calibration's latency+bandwidth model consumes.
    """
    sizes: Dict[str, int]
    hbm_per_chip: float      # params+grads+opt shards + gather ws + acts
    wire_per_chip: float     # collective bytes moved per step per chip
    bubble_penalty: float    # pp idle time expressed in byte-equivalents
    feasible: bool
    wire_by_axis: Dict[str, Dict[str, float]] = \
        dataclasses.field(default_factory=dict)
    analytic_step_time_s: float = 0.0
    calibrated_step_time_s: Optional[float] = None
    used: str = "analytic"   # which estimate ranked this candidate

    @property
    def cost(self) -> float:
        return self.wire_per_chip + self.bubble_penalty

    @property
    def step_time_s(self) -> float:
        """THE absolute prediction: calibrated when a table ranked the
        candidate, analytic otherwise."""
        if self.used == "calibrated" and \
                self.calibrated_step_time_s is not None:
            return self.calibrated_step_time_s
        return self.analytic_step_time_s

    def as_dict(self) -> Dict[str, Any]:
        return {"sizes": dict(self.sizes),
                "hbm_per_chip": round(self.hbm_per_chip),
                "wire_per_chip": round(self.wire_per_chip),
                "bubble_penalty": round(self.bubble_penalty),
                "feasible": self.feasible,
                "cost": round(self.cost),
                "wire_by_axis": {a: dict(r) for a, r in
                                 self.wire_by_axis.items()},
                "analytic_step_time_s": self.analytic_step_time_s,
                "calibrated_step_time_s": self.calibrated_step_time_s,
                "used": self.used}


def _factorizations(n: int) -> List[Tuple[int, int, int, int]]:
    """All (dp, fsdp, tp, pp) with dp*fsdp*tp*pp == n."""
    out = []
    for dp in range(1, n + 1):
        if n % dp:
            continue
        m = n // dp
        for fsdp in range(1, m + 1):
            if m % fsdp:
                continue
            k = m // fsdp
            for tp in range(1, k + 1):
                if k % tp:
                    continue
                out.append((dp, fsdp, tp, k // tp))
    return out


def candidate_layouts(n_devices: int,
                      max_tp: int = 8,
                      max_pp: int = 8) -> List[Dict[str, int]]:
    """Enumerate logical-axis factorizations of the device count.

    tp/pp are capped: tp beyond a node's fast links and pp beyond the
    model's layer count are never profitable, and the caps keep the
    search space trivial (GC3-style: layouts are enumerable programs).
    """
    cands = []
    for dp, fsdp, tp, pp in _factorizations(n_devices):
        if tp > max_tp or pp > max_pp:
            continue
        cands.append({"dp": dp, "fsdp": fsdp, "tp": tp, "pp": pp})
    return cands


#: matmul FLOPs a chip retires per byte of interconnect bandwidth —
#: the exchange rate that converts pipeline-bubble idle time into
#: wire-byte equivalents (v4-ish: ~275 TF/s vs ~2.4 TB/s ICI ≈ O(100))
_FLOPS_PER_WIRE_BYTE = 128.0


def _wire_tier(compress: str) -> float:
    """Bytes-on-the-wire per f32 element for a grad wire tier, reusing
    comm.py's accounting so the model and the runtime never disagree."""
    from .comm import _wire_bytes
    n = 1 << 20
    return _wire_bytes("flat", compress, n, 4, 256) / float(4 * n)


def estimate_layout(sizes: Dict[str, int], dims: ModelDims,
                    hbm_bytes_per_chip: float,
                    compress: str = "none",
                    num_micro: int = 4,
                    calibration=None) -> LayoutCost:
    """Score one layout: per-chip HBM residency vs bytes moved per step.

    HBM (per chip):
      params + grads            n_params·B / (fsdp·tp·pp)
      optimizer moments (f32)   opt_slots·n_params·4 / (fsdp·tp·pp)
      FSDP gather workspace     layer_params·B / tp     (transient full
                                layer while it computes; 0 when fsdp==1)
      activations               batch/(dp·fsdp) · seq · hidden · B
                                · 2·layers/pp           (fwd + saved)

    Wire (per chip per step), grad tiers via comm._wire_bytes:
      dp   ring all-reduce      2·(dp-1)/dp · grad_shard · tier
      fsdp ag(params)×2 + rs    [2 + tier]·(fsdp-1)/fsdp · P·B/(tp·pp)
      tp   4 act all-reduces/层 4·layers/pp · 2·(tp-1)/tp · b·s·h·B
      pp   ring fwd+bwd         2 · batch/(dp·fsdp) · s·h·B

    The pp bubble ((pp-1)/(m+pp-1)) is charged as idle byte-equivalents
    of the per-chip compute traffic, so pipeline only wins when it buys
    fit — the TVM lesson: model the *whole* step, not one collective.
    """
    dp, fsdp, tp, pp = (sizes.get(a, 1) for a in LOGICAL_AXES)
    B = dims.dtype_bytes
    n_dev = dp * fsdp * tp * pp
    model_shard = dims.n_params * B / (fsdp * tp * pp)
    opt_shard = dims.opt_slots * dims.n_params * 4 / (fsdp * tp * pp)
    gather_ws = (dims.layer_params * B / tp) if fsdp > 1 else 0.0
    local_batch = dims.batch / (dp * fsdp)
    layers_local = math.ceil(dims.n_layers / pp)
    acts = local_batch * dims.seq * dims.hidden * B * 2 * layers_local
    hbm = 2 * model_shard + opt_shard + gather_ws + acts

    tier = _wire_tier(compress)
    act_bytes = local_batch * dims.seq * dims.hidden * B
    wire = 0.0
    # per-axis decomposition with collective-call counts: the byte
    # factors above, plus how many collectives carry them per step —
    # the latency term of the calibrated model charges per call
    wire_by_axis: Dict[str, Dict[str, float]] = {}
    if dp > 1:
        b = 2 * (dp - 1) / dp * model_shard * tier
        wire += b
        wire_by_axis["dp"] = {"bytes": b, "calls": 1}   # fused ring AR
    if fsdp > 1:
        full_on_tp_pp = dims.n_params * B / (tp * pp)
        b = (2 + tier) * (fsdp - 1) / fsdp * full_on_tp_pp
        wire += b
        wire_by_axis["fsdp"] = {"bytes": b, "calls": 3}  # ag+ag+rs
    if tp > 1:
        b = 4 * layers_local * 2 * (tp - 1) / tp * act_bytes
        wire += b
        wire_by_axis["tp"] = {"bytes": b, "calls": 4 * layers_local}
    if pp > 1:
        b = 2 * act_bytes
        wire += b
        wire_by_axis["pp"] = {"bytes": b,
                              "calls": 2 * max(num_micro, 1)}

    # the bubble is charged in wire-byte equivalents: fwd+bwd is
    # ~6·n_params FLOPs per token, and a TPU core retires roughly
    # _FLOPS_PER_WIRE_BYTE matmul FLOPs in the time one byte crosses
    # the interconnect — so idle compute converts to "bytes not moved"
    bubble = (pp - 1) / (num_micro + pp - 1) if pp > 1 else 0.0
    flops = 6.0 * dims.n_params * dims.batch * dims.seq
    compute_equiv = flops / _FLOPS_PER_WIRE_BYTE / n_dev
    penalty = bubble / max(1.0 - bubble, 1e-6) * compute_equiv

    # absolute estimates ride every candidate: analytic always, the
    # calibrated one when a table matched — receipts show BOTH so a
    # mis-ranked layout is auditable in seconds, not byte-equivalents
    from ..observability import calibration as _calibration
    analytic_t = _calibration.predict_step_time_s(
        sizes, dims, wire_by_axis, None, num_micro=num_micro,
        compress=compress)["total_s"]
    calibrated_t = None
    used = "analytic"
    if calibration is not None:
        calibrated_t = _calibration.predict_step_time_s(
            sizes, dims, wire_by_axis, calibration,
            num_micro=num_micro, compress=compress)["total_s"]
        used = "calibrated"

    return LayoutCost(sizes={a: sizes.get(a, 1) for a in LOGICAL_AXES},
                      hbm_per_chip=hbm, wire_per_chip=wire,
                      bubble_penalty=penalty,
                      feasible=hbm <= hbm_bytes_per_chip,
                      wire_by_axis=wire_by_axis,
                      analytic_step_time_s=analytic_t,
                      calibrated_step_time_s=calibrated_t,
                      used=used)


def choose_layout(n_devices: int, dims: ModelDims,
                  hbm_bytes_per_chip: float, compress: str = "none",
                  num_micro: int = 4, max_tp: int = 8, max_pp: int = 8,
                  calibration=None
                  ) -> Tuple[Dict[str, int], List[LayoutCost]]:
    """Pick the cheapest feasible layout; raise with the full report if
    nothing fits (a layout that cannot fit must fail at plan time, not
    as a dispatch OOM — memory_anatomy proves it, this predicts it).

    With a matching ``observability.calibration.Calibration`` the rank
    key is the calibrated ABSOLUTE step time (measured FLOP/s + per-axis
    bandwidth/latency on THIS device); without one it is the analytic
    byte cost, exactly as before PR 18. Feasibility is byte math either
    way — calibration never un-fits a layout.
    """
    reports = [estimate_layout(c, dims, hbm_bytes_per_chip,
                               compress=compress, num_micro=num_micro,
                               calibration=calibration)
               for c in candidate_layouts(n_devices, max_tp=max_tp,
                                          max_pp=max_pp)]
    feasible = [r for r in reports if r.feasible]
    if not feasible:
        tight = min(reports, key=lambda r: r.hbm_per_chip)
        raise ValueError(
            "no layout of %d devices fits %d bytes/chip; closest %s "
            "needs %d" % (n_devices, int(hbm_bytes_per_chip),
                          tight.sizes, int(tight.hbm_per_chip)))
    # deterministic tie-break: prefer fewer pipeline stages, then less
    # tp, then less fsdp — the simplest layout that is also cheapest
    if calibration is not None:
        best = min(feasible,
                   key=lambda r: (r.calibrated_step_time_s,
                                  r.sizes["pp"], r.sizes["tp"],
                                  r.sizes["fsdp"]))
    else:
        best = min(feasible, key=lambda r: (r.cost, r.sizes["pp"],
                                            r.sizes["tp"],
                                            r.sizes["fsdp"]))
    return dict(best.sizes), reports


_EMBED_RE = re.compile(r"(embed|mlm_head\.decoder)", re.I)


class MeshPlan:
    """One layout declaration → every PartitionSpec in the program.

    >>> plan = MeshPlan(dp=2, tp=2, pp=2)
    >>> mesh = plan.build_mesh()
    >>> plan.param_spec("blk.qkv.weight", t)     # row/col from annotation
    >>> plan.data_spec(batch)                    # batch over (dp, fsdp)
    >>> plan.stacked_param_spec("qkv.weight", t) # P('pp', *param spec)

    Axis semantics (LOGICAL_AXES): 'dp' replicates params and shards the
    batch; 'fsdp' shards the batch AND params/grads/opt state (ZeRO-3
    over a dedicated axis, so dp×fsdp hierarchies stay expressible);
    'tp' follows the layer annotations (qkv col-, out row-sharded,
    embeddings fsdp×tp on the vocab dim); 'pp' shards the stacked stage
    dim of the whole-graph pipeline executable. Norm scales/biases carry
    no annotation and stay replicated unless fsdp evenly divides them.
    """

    def __init__(self, dp: int = 1, fsdp: int = 1, tp: int = 1,
                 pp: int = 1, *, rules: Dict[str, P] = None,
                 batch_dim: int = 0, compress: str = "none"):
        sizes = {"dp": int(dp), "fsdp": int(fsdp), "tp": int(tp),
                 "pp": int(pp)}
        for a, s in sizes.items():
            if s < 1:
                raise ValueError("axis %r size must be >= 1, got %d"
                                 % (a, s))
        self.sizes = sizes
        self.rules = dict(rules or {})
        self.batch_dim = batch_dim
        self.compress = compress
        self._mesh: Optional[Mesh] = None
        self.report: List[LayoutCost] = []
        #: the Calibration that ranked this plan (None = analytic) and
        #: the dims it was planned for — both feed .predict()
        self.calibration = None
        self.dims: Optional[ModelDims] = None
        #: the falsifiable prediction the planner engine stamps after
        #: its first live step joins the measured planes
        self.receipt = None

    # -- construction -------------------------------------------------------
    @classmethod
    def auto(cls, n_devices: int, dims: ModelDims,
             hbm_bytes_per_chip: float, *, rules: Dict[str, P] = None,
             compress: str = "none", num_micro: int = 4,
             max_tp: int = 8, max_pp: int = 8,
             calibration="auto") -> "MeshPlan":
        """layout="auto": cost-model search over the factorizations of
        the device count; the losing candidates ride along in .report
        so receipts can show WHY this layout won.

        ``calibration="auto"`` (default) loads the committed
        ``tools/cost_calibration.json`` when it matches the live
        (device_kind, topology) — a mismatch warns loudly and falls
        back to analytic constants (see observability.calibration).
        Pass None to force analytic ranking, or a Calibration to pin
        one.
        """
        calib = calibration
        if calib == "auto":
            from ..observability import calibration as _calibration
            try:
                calib = _calibration.load_for(n_devices=n_devices)
            except Exception:
                calib = None
        sizes, reports = choose_layout(
            n_devices, dims, hbm_bytes_per_chip, compress=compress,
            num_micro=num_micro, max_tp=max_tp, max_pp=max_pp,
            calibration=calib)
        plan = cls(rules=rules, compress=compress, **sizes)
        plan.report = reports
        plan.calibration = calib
        plan.dims = dims
        cls._ledger_layout(n_devices, dims, hbm_bytes_per_chip,
                           compress, num_micro, max_tp, max_pp,
                           calib, sizes, reports)
        return plan

    @staticmethod
    def _ledger_layout(n_devices, dims, hbm_bytes_per_chip, compress,
                       num_micro, max_tp, max_pp, calib, sizes,
                       reports):
        """Ledger the layout pick: the losing candidates + the ranking
        ruler ARE the evidence (incident_replay re-runs choose_layout
        from them and asserts the same winner); the outcome joins
        against PR 18's measured-vs-predicted audit — a pick whose
        calibrated prediction missed by >20% stamps `worse`."""
        from ..observability import decisions as _dec
        if not _dec.enabled():
            return
        from ..observability import metrics as _obs

        def _probe():
            g = _obs.get("planner.prediction_error",
                         metric="step_time")
            if g is None:
                return None
            return {"prediction_error": abs(float(g.value()))}

        def _judge(pre, post):
            err = post.get("prediction_error")
            if err is None:
                return "neutral"
            return "improved" if abs(err) <= 0.2 else "worse"

        _dec.record(
            "planner.layout", "layout",
            rule=("calibrated step-time ranking" if calib is not None
                  else "analytic byte-cost ranking"),
            evidence={
                "inputs": {
                    "n_devices": int(n_devices),
                    "dims": dataclasses.asdict(dims),
                    "hbm_bytes_per_chip": float(hbm_bytes_per_chip),
                    "compress": compress,
                    "num_micro": int(num_micro),
                    "max_tp": int(max_tp), "max_pp": int(max_pp),
                    "calibration": (dict(calib.table)
                                    if calib is not None else None)},
                "decision": {
                    "action": "layout", "sizes": dict(sizes),
                    "candidates": [r.as_dict() for r in reports]}},
            signals={"prediction_error": 0.0},
            settle_s=600.0, probe=_probe, judge=_judge)

    @property
    def n_devices(self) -> int:
        n = 1
        for s in self.sizes.values():
            n *= s
        return n

    def axis_names(self) -> Tuple[str, ...]:
        """Mesh axes, outermost first: pp, dp, fsdp, tp (size-1 axes are
        dropped — absent from the mesh means absent from every spec)."""
        order = ("pp", "dp", "fsdp", "tp")
        return tuple(a for a in order if self.sizes[a] > 1)

    def mesh_shape(self) -> Dict[str, int]:
        return {a: self.sizes[a] for a in self.axis_names()}

    def build_mesh(self, devices=None) -> Mesh:
        from .env import build_mesh
        shape = self.mesh_shape() or {"dp": 1}
        devices = devices if devices is not None \
            else jax.devices()[:self.n_devices]
        self._mesh = build_mesh(shape, devices=devices)
        return self._mesh

    @property
    def mesh(self) -> Mesh:
        if self._mesh is None:
            self.build_mesh()
        return self._mesh

    def _axis(self, a: str) -> Optional[str]:
        return a if self.sizes[a] > 1 else None

    # -- spec derivation ----------------------------------------------------
    def _sanitize(self, spec: P) -> P:
        """Drop spec axes absent from this layout (a model annotated
        for tp degrades to replicated on a dp-only plan). Pure layout
        math against the declared axis names — no device mesh needed,
        so spec derivation works on hosts that don't hold the gang's
        devices (a regrown elastic slot computing its resync plan)."""
        names = set(self.axis_names())

        def keep(p):
            if p is None:
                return None
            if isinstance(p, (tuple, list)):
                kept = tuple(a for a in p if a in names)
                return kept if kept else None
            return p if p in names else None
        return P(*[keep(p) for p in spec])

    def param_spec(self, name: str, tensor) -> P:
        """annotation → rules → P(), then fsdp on the largest free dim.

        Embedding tables are the special case the ISSUE calls out: a
        vocab dim already tp-sharded gains fsdp on the SAME dim
        (('fsdp','tp') product) so the table, the model's largest
        tensor, shards over both axes instead of falling back to the
        hidden dim."""
        spec = self._sanitize(_spec_for_param(name, tensor, self.rules))
        fsdp = self._axis("fsdp")
        if fsdp is None:
            return spec
        shape = tensor._data.shape if isinstance(tensor, Tensor) else \
            tuple(getattr(tensor, "shape", ()))
        parts = list(spec) + [None] * (len(shape) - len(spec))
        if (_EMBED_RE.search(name) and len(shape) == 2
                and parts and parts[0] is not None
                and parts[0] == self._axis("tp")
                and shape[0] % (self.sizes["fsdp"] * self.sizes["tp"])
                == 0):
            parts[0] = (fsdp, parts[0])
            return P(*parts)
        return _add_axis(P(*parts), tensor, fsdp, self.sizes["fsdp"])

    def state_spec(self, name: str, tensor) -> P:
        """Optimizer moments mirror the param layout exactly — FSDP's
        memory win is the whole point of the fsdp axis."""
        return self.param_spec(name, tensor)

    def data_spec(self, array) -> P:
        nd = len(array.shape) if hasattr(array, "shape") \
            else np.ndim(array)
        if nd == 0:
            return P()
        data_axes = tuple(a for a in ("dp", "fsdp") if self.sizes[a] > 1)
        if not data_axes:
            return P()
        parts = [None] * nd
        parts[self.batch_dim] = (data_axes if len(data_axes) > 1
                                 else data_axes[0])
        return P(*parts)

    def activation_spec(self, ndim: int, batch_dim: int = 0) -> P:
        """Per-microbatch activation spec inside the step body."""
        parts = [None] * ndim
        data_axes = tuple(a for a in ("dp", "fsdp") if self.sizes[a] > 1)
        if data_axes and ndim > batch_dim:
            parts[batch_dim] = (data_axes if len(data_axes) > 1
                                else data_axes[0])
        return P(*parts)

    def stacked_param_spec(self, name: str, tensor) -> P:
        """Spec for a stage-stacked [S, ...] param in the pipeline
        executable: leading dim over 'pp', trailing dims per
        param_spec."""
        base = self.param_spec(name, tensor)
        return P(self._axis("pp"), *base)

    def stacked_activation_spec(self, ndim: int) -> P:
        """[S, batch, ...] ring buffers: stage dim over pp, batch over
        the data axes."""
        inner = self.activation_spec(ndim - 1, batch_dim=0)
        return P(self._axis("pp"), *inner)

    # -- integration surfaces ----------------------------------------------
    def _sharding_plan_cache(self) -> "ShardingPlan":
        cached = getattr(self, "_splan", None)
        if cached is None or cached.mesh is not self.mesh:
            cached = ShardingPlan(
                self.mesh, rules=self.rules, dp_axis="dp",
                data_axes=tuple(a for a in ("dp", "fsdp")
                                if self.sizes[a] > 1),
                batch_dim=self.batch_dim,
                fsdp_axis=self._axis("fsdp"))
            object.__setattr__(self, "_splan", cached)
        return cached

    def sharding_plan(self) -> "ShardingPlan":
        """A ShardingPlan view over this plan's mesh, for TrainStep /
        fleet consumers that speak the older interface."""
        return self._sharding_plan_cache()

    def resync_assignments(self, named_params) -> Dict[str, str]:
        """Per-param re-sync collective for a regrown elastic slot:
        params replicated across the data axes arrive by 'broadcast'
        (any survivor owns the bytes); params sharded over fsdp need an
        'all_gather' so the stale slot reassembles every shard."""
        out = {}
        fsdp = self._axis("fsdp")
        for name, t in named_params.items():
            spec = self.param_spec(name, t)
            flat = []
            for p in spec:
                if isinstance(p, (tuple, list)):
                    flat.extend(p)
                elif p is not None:
                    flat.append(p)
            out[name] = "all_gather" if (fsdp and fsdp in flat) \
                else "broadcast"
        return out

    def predict(self, dims: Optional[ModelDims] = None, *,
                num_micro: int = 4, calibration="inherit",
                hbm_bytes_per_chip: float = float("inf")):
        """Score THIS plan's layout and return the PlanReceipt — the
        falsifiable prediction (step-time / HBM-peak / wire-bytes, in
        absolute units) the audit loop later joins measured values
        onto. Works for manual plans too: auto() remembers its dims,
        manual plans pass them (or a state dict via ModelDims.infer).

        ``calibration="inherit"`` uses whatever ranked the plan;
        "auto" re-resolves the committed table; None forces analytic.
        """
        from ..observability import calibration as _calibration
        dims = dims if dims is not None else self.dims
        if dims is None:
            raise ValueError(
                "MeshPlan.predict needs ModelDims — auto() plans carry "
                "them; manual plans must pass dims= (see "
                "ModelDims.infer)")
        calib = calibration
        if calib == "inherit":
            calib = self.calibration
        elif calib == "auto":
            try:
                calib = _calibration.load_for(n_devices=self.n_devices)
            except Exception:
                calib = None
        cost = estimate_layout(self.sizes, dims, hbm_bytes_per_chip,
                               compress=self.compress,
                               num_micro=num_micro, calibration=calib)
        if calib is not None:
            kind, topo = calib.device_kind, calib.topology
        else:
            ident = _calibration.device_identity()
            kind = ident["device_kind"]
            topo = _calibration.topology_fingerprint(
                kind, ident["n_devices"])
        receipt = _calibration.PlanReceipt(
            sizes=dict(self.sizes),
            predicted_step_time_s=cost.step_time_s,
            predicted_hbm_bytes=cost.hbm_per_chip,
            predicted_wire_bytes=cost.wire_per_chip,
            analytic_step_time_s=cost.analytic_step_time_s,
            calibrated_step_time_s=cost.calibrated_step_time_s,
            used=cost.used,
            device_kind=kind,
            topology=topo,
            calibration_match=calib is not None,
            breakdown={"wire_by_axis": {a: dict(r) for a, r in
                                        cost.wire_by_axis.items()},
                       "bubble_penalty": round(cost.bubble_penalty),
                       "num_micro": num_micro})
        self.receipt = receipt
        self.dims = dims
        return receipt

    def describe(self) -> Dict[str, Any]:
        d = {"sizes": dict(self.sizes), "axes": list(self.axis_names()),
             "n_devices": self.n_devices, "compress": self.compress}
        if self.report:
            d["report"] = [r.as_dict() for r in self.report]
        if self.calibration is not None:
            d["calibration"] = {"topology": self.calibration.topology,
                                "synthetic": self.calibration.synthetic}
        if self.receipt is not None:
            d["receipt"] = self.receipt.as_dict()
        return d


# ---------------------------------------------------------------------------
# serving spec derivation (tensor-parallel serving engine)
# ---------------------------------------------------------------------------
# The serving snapshot is NOT a training pytree: the embedding table is
# the lm_head (logits = h @ wte.T) and must stay REPLICATED for the
# greedy-parity contract (the training flavor's _EMBED_RE fsdp x tp
# vocab sharding would force an all-gather of logits per token).
# Megatron layout over the one 'tp' axis: qkv/fc1 column-parallel
# (out dim sharded), proj/fc2 row-parallel (in dim sharded, partial
# contraction all-reduced before the bias), norms + biases of
# row-parallel layers + embeddings replicated.

#: per-leaf tp specs, keyed by the serving-snapshot block leaf name
SERVING_TP_RULES = {
    "qkv_w": P(None, "tp"), "qkv_b": P("tp"),
    "proj_w": P("tp", None), "proj_b": P(),
    "fc1_w": P(None, "tp"), "fc1_b": P("tp"),
    "fc2_w": P("tp", None), "fc2_b": P(),
}

#: the paged K/V page pools [n_blocks, block_size, n_heads * hd] shard
#: over the merged heads axis — each chip holds exactly 1/tp of every
#: page: its own whole heads, (n_heads/tp) * hd contiguous lanes (the
#: heads-major qkv layout gives a chip a contiguous group of heads)
SERVING_POOL_SPEC = P(None, None, "tp")


def permute_qkv_heads(arr, n_heads):
    """Reorder a fused-qkv weight's output columns (or the bias) from
    (3, n_heads, hd) to (n_heads, 3, hd) so that a CONTIGUOUS tp shard
    of the last dim carries whole heads with their q, k and v. The
    permutation moves values without touching them — each output
    column's dot product is bitwise the tp=1 column — and it commutes
    with per-column int8 PTQ (codes and scales permute together when
    applied to the float weight first). Shapes are preserved, so the
    swap-validation treedef/shape contract is unchanged."""
    out = arr.shape[-1]
    hd = out // (3 * n_heads)
    x = arr.reshape(arr.shape[:-1] + (3, n_heads, hd))
    x = jax.numpy.swapaxes(x, -3, -2)
    return x.reshape(arr.shape)


def serving_param_specs(params):
    """PartitionSpec pytree matching a serving snapshot (float or int8
    ``{"q8","s"}`` leaves): block weights per SERVING_TP_RULES,
    everything else (wte/wpe/lnf/ln1/ln2) replicated. int8 leaves
    follow the parent weight: q8 mirrors the float weight's 2-D spec;
    the per-output-column scale vector s shards over 'tp' exactly when
    the out dim does (qkv/fc1), else replicates."""
    def spec_for(path, leaf):
        names = [str(getattr(k, "key", getattr(k, "name", k)))
                 for k in path]
        if names and names[-1] in ("q8", "s") and len(names) >= 2:
            base = SERVING_TP_RULES.get(names[-2], P(None, None))
            if names[-1] == "q8":
                return base
            return P("tp") if (len(base) > 1 and base[1] == "tp") \
                else P()
        return SERVING_TP_RULES.get(names[-1] if names else "", P())
    return jax.tree_util.tree_map_with_path(spec_for, params)


def serving_param_shardings(mesh: Mesh, params):
    """NamedSharding pytree for device_put'ing a serving snapshot onto
    a tp mesh (the one placement swap_weights must reproduce — a leaf
    re-placed differently is a new jit cache key, i.e. a recompile)."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), serving_param_specs(params),
        is_leaf=lambda x: isinstance(x, P))


__all__ += ["SERVING_TP_RULES", "SERVING_POOL_SPEC",
            "permute_qkv_heads", "serving_param_specs",
            "serving_param_shardings"]
