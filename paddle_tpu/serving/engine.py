"""ServingEngine: continuous-batching serving of a decoder over its cache.

Ties the pieces together: a weight snapshot (bf16 serving cast by
default — decode is HBM-bound on weight reads, PERF_PLAN lever #5; f32
parity mode is pinned bit-for-bit against generation.py greedy), the
cache the decoder's mixer asks for — page pools + host block tables
(paged_cache) for softmax attention, state rows (state_cache) for
retention layers — the FIFO continuous-batching scheduler, and the
per-engine compiled programs (programs.py). One ``step()`` is one
token boundary:

  retire finished -> admit queued (one bucketed prefill for the whole
  mixed-length admit batch) -> one decode step for every active slot
  -> sentinel check (executable count must stay == ladder size)

The engine is single-threaded and host-driven by design: continuous
batching NEEDS a host decision point every token (who retires, who
admits), so unlike training there is no lax.scan to fuse steps into —
the per-step dispatch is the price of in-flight admission, and the
bench shows the batch-shape wins dominate it.

Metrics ride the gated serving.* series (queue depth, active slots,
free pages, admitted/retired/evicted totals); a step's durations are
the request-trace spans of ``step()``, not histograms;
``serving_recompiles_total`` is always-on via the
RecompileSentinel. ``serving.retired_total`` counts FINISHED requests;
``serving.evicted_total`` counts requests pulled off the engine for
requeue (``evict_requests`` / fleet requeue) — nothing else.

Three raw-speed levers compose on top of the baseline loop, every one
off by default and each receipted end to end (tools/serving_bench.py):

- ``quant="int8"``: the build-time weight snapshot becomes per-channel
  PTQ int8 codes + f32 scales (quant/int8_serving) and every block
  matmul runs int8×int8→int32 on the MXU double-rate path; the f32
  parity mode stays the accuracy reference.
- ``speculative_k=k`` (+ a draft model): the draft proposes k greedy
  tokens in ONE scan dispatch, the target scores anchor+k proposals in
  ONE chunk dispatch, and the host keeps the longest agreeing prefix —
  every accepted token is bit-identical to non-speculative greedy
  (each emitted token IS a target argmax over a correct-by-induction
  cache prefix), so speculation changes latency, never output.
- ``prefix_sharing=True``: admission matches the longest radix-indexed
  prompt prefix, points the block table at the shared pages
  (refcounted, copy-on-write), and prefills ONLY the unshared suffix
  through the same chunk program.

The fleet surface (``serving/fleet.py``): ``swap_weights()`` flips
the weight snapshot at a token boundary without draining or
recompiling. ``evict_requests()`` is the single-engine operational
surface (drain a TRUSTED engine before shutdown/handoff) — the fleet
deliberately does NOT call it on a failed replica: a wedged or dead
engine can't be trusted to report its own state, so fleet eviction
rebuilds each request from the fleet-side harvested token stream and
increments ``serving.evicted_total`` itself.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, replace
from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..models.decoder import DecoderSpec
from ..models.generation import _cast_params, _gpt_params
from ..observability import memory as _mem
from ..observability import metrics as _obs
from ..observability import reqtrace as _rt
from ..observability.sentinel import RecompileSentinel
from .paged_cache import PagedKVCache
from .state_cache import StateCache
from .programs import (jit_tp_with_donated_pools,
                       jit_with_donated_pools, make_chunk_fn,
                       make_decode_fn, make_prefill_fn)
from .scheduler import BucketLadder, FifoScheduler, Request

__all__ = ["ServingConfig", "ServingEngine", "build_serving_snapshot",
           "serving_decoder_spec"]


def _qkv_heads_major(cfg) -> bool:
    """Fused-qkv columns as (heads, 3, hd), not (3, heads, hd): where a
    tp plan shards heads, so a chip's contiguous shard carries whole
    heads with their q, k, v. The programs' spec and the snapshot's
    weights both ask here, so they cannot disagree."""
    return cfg.tp > 1


def serving_decoder_spec(model_config, cfg) -> DecoderSpec:
    """The block description this config's programs run. Under a tp
    plan each chip runs n_heads/tp heads and all-reduces the proj/fc2
    partial contractions through the planned collectives (tp_wire
    picks the wire tier; f32 is exact)."""
    spec = DecoderSpec.of(model_config)
    if cfg.tp == 1:
        return spec
    from ..distributed.comm import CommConfig, planned_all_reduce
    comm_cfg = CommConfig(compress=cfg.tp_wire)
    return replace(
        spec, n_heads=spec.n_heads // cfg.tp,
        qkv_heads_major=_qkv_heads_major(cfg),
        reduce=lambda t: planned_all_reduce(t, config=comm_cfg,
                                            axes=("tp",)))


def build_serving_snapshot(params, cfg, n_heads: Optional[int] = None
                           ) -> dict:
    """Raw generation params -> this config's serving snapshot: the
    float cast first, then (``quant="int8"``) the four block matmul
    weights become ``{"q8", "s"}`` PTQ leaves. The ONE builder engine
    build, ``swap_weights(cast=True)`` and the fleet's standby staging
    all share — a snapshot built anywhere else risks a treedef
    mismatch that would reject every hot swap.

    Under a tensor-parallel plan (``cfg.plan`` with tp>1, which needs
    ``n_heads``) two more stages run IN ORDER: the fused-qkv columns
    permute to heads-major BEFORE quantization (so int8 codes + scales
    permute with their float columns, bitwise), and the finished
    snapshot device_puts onto the plan's mesh with the derived
    Megatron specs — qkv/fc1 column-parallel, proj/fc2 row-parallel,
    embeddings/norms replicated. Shapes and treedef are unchanged, so
    the swap-validation contract is dtype/shape-identical to tp=1."""
    snap = _cast_params(params, cfg.dtype)
    if _qkv_heads_major(cfg):
        if n_heads is None:
            raise ValueError(
                "build_serving_snapshot needs n_heads under a tp plan "
                "(the qkv head-major column permutation is per-head)")
        from ..distributed.sharding import permute_qkv_heads
        snap = dict(snap)
        snap["blocks"] = [dict(bp) for bp in snap["blocks"]]
        for bp in snap["blocks"]:
            bp["qkv_w"] = permute_qkv_heads(bp["qkv_w"], n_heads)
            bp["qkv_b"] = permute_qkv_heads(bp["qkv_b"], n_heads)
    if cfg.quant == "int8":
        from ..quant.int8_serving import quantize_params
        snap = quantize_params(snap, cfg.quant_config)
    if cfg.tp > 1:
        import jax
        from ..distributed.sharding import serving_param_shardings
        snap = jax.device_put(
            snap, serving_param_shardings(cfg.plan.mesh, snap))
    return snap


@dataclass
class ServingConfig:
    """The serving shape contract. Every field here is STATIC — it
    determines the executable ladder, and nothing a request carries
    can force a new compile."""
    max_slots: int = 8                 # concurrent decode lanes
    max_admit: int = 4                 # prefill batch width (padded)
    block_size: int = 16               # tokens per KV page
    n_blocks: int = 128                # page pool size (incl. scratch)
    prefill_buckets: Tuple[int, ...] = (32, 64, 128)
    decode_buckets: Optional[Tuple[int, ...]] = None  # default: (max_slots,)
    decode_chunk: int = 4              # token boundaries per dispatch
    max_total_tokens: int = 256        # per-request prompt + new cap
    dtype: Optional[str] = "bfloat16"  # None = f32 parity mode
    temperature: float = 0.0           # 0 = greedy
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    eos_token_id: Optional[int] = None # default; per-request override
    seed: int = 0
    # -- raw-speed levers (all off by default) -------------------------------
    quant: Optional[object] = None     # "int8" | QuantConfig(int8_compute)
    speculative_k: int = 0             # draft proposals per boundary
    prefix_sharing: bool = False       # radix/COW shared prompt pages
    # -- tensor parallelism --------------------------------------------------
    plan: Optional[object] = None      # MeshPlan(tp=N): shard_map serving
    tp_wire: str = "f32"               # tp all-reduce wire tier (comm.py)

    @property
    def tp(self) -> int:
        """Tensor-parallel degree (1 without a plan)."""
        return int(self.plan.sizes["tp"]) if self.plan is not None \
            else 1

    def __post_init__(self):
        if self.plan is not None:
            sizes = getattr(self.plan, "sizes", None)
            if not isinstance(sizes, dict) or "tp" not in sizes:
                raise ValueError(
                    "plan= takes a distributed.MeshPlan (e.g. "
                    "MeshPlan(tp=2))")
            off_axes = {a: s for a, s in sizes.items()
                        if a != "tp" and s > 1}
            if off_axes:
                raise ValueError(
                    f"serving plans shard over 'tp' only; drop "
                    f"{off_axes} (replica parallelism is the fleet's "
                    "job, not the engine's)")
        if self.tp > 1:
            if self.speculative_k:
                raise ValueError(
                    "speculative_k is not supported under a tp plan "
                    "yet: the draft engine would need its own sharded "
                    "cache + programs. Drop speculative_k or the plan.")
            if self.prefix_sharing:
                raise ValueError(
                    "prefix_sharing is not supported under a tp plan "
                    "yet: the COW page-copy program is not tp-sharded."
                    " Drop prefix_sharing or the plan.")
            if self.tp_wire not in ("f32", "bf16"):
                raise ValueError(
                    f"tp_wire={self.tp_wire!r}: the tp all-reduce wire "
                    "tier is 'f32' (exact, the parity default) or "
                    "'bf16' (half wire bytes)")
        self.quant_config = None
        if self.quant is not None and not isinstance(self.quant, str):
            # QuantConfig threading: the quant module's config object
            # opts into serving int8 via int8_compute
            if not getattr(self.quant, "int8_compute", False):
                raise ValueError(
                    "serving quant takes a QuantConfig with "
                    "int8_compute=True (or the string 'int8')")
            self.quant_config = self.quant
            self.quant = "int8"
        if self.quant not in (None, "int8"):
            raise ValueError(
                f"quant={self.quant!r}: only 'int8' (bf16/f32 are the "
                "dtype= cast, not a quant mode)")
        if self.speculative_k < 0:
            raise ValueError(
                f"speculative_k={self.speculative_k} must be >= 0")
        if self.speculative_k and self.temperature != 0.0:
            raise ValueError(
                "speculative decoding requires greedy (temperature=0):"
                " acceptance keeps the longest prefix agreeing with "
                "the target argmax")
        if self.decode_buckets is None:
            self.decode_buckets = (self.max_slots,)
        self.prefill_buckets = tuple(sorted(self.prefill_buckets))
        self.decode_buckets = tuple(sorted(self.decode_buckets))
        if self.decode_buckets[-1] != self.max_slots:
            raise ValueError(
                f"largest decode bucket {self.decode_buckets[-1]} "
                f"must equal max_slots {self.max_slots}")
        if self.max_total_tokens < self.prefill_buckets[-1]:
            raise ValueError(
                f"max_total_tokens={self.max_total_tokens} < largest "
                f"prefill bucket {self.prefill_buckets[-1]}")
        if self.decode_chunk < 1:
            raise ValueError(
                f"decode_chunk={self.decode_chunk} must be >= 1")

    @property
    def table_width(self) -> int:
        """Block-table columns: enough pages for the longest possible
        request (every program signature shares this width)."""
        return -(-self.max_total_tokens // self.block_size)


def _refuse_for_retention(cfg):
    """What the engine cannot do for a decoder whose cache is a state
    row a request, each with its reason."""
    if cfg.prefix_sharing:
        raise ValueError(
            "prefix_sharing is not supported for a retention decoder: "
            "a recurrent state cannot be cut at a shared prefix; "
            "sharing needs snapshots of the state at page boundaries, "
            "which nothing takes yet. Drop prefix_sharing.")
    if cfg.speculative_k:
        raise ValueError(
            "speculative_k is not supported for a retention decoder: "
            "a rejected proposal cannot be taken out of a state again "
            "(verification needs a state snapshot to fall back to), "
            "and the draft would need state rows of its own. Drop "
            "speculative_k.")
    if cfg.tp > 1:
        raise ValueError(
            "a tp plan is not supported for a retention decoder: the "
            "state rows and the decode kernel are not sharded over "
            "key-value heads yet. Drop the plan.")
    if cfg.quant is not None:
        raise ValueError(
            "quant is not supported for a retention decoder: the int8 "
            "snapshot knows the fused-qkv block's matmuls only. Drop "
            "quant.")


class ServingEngine:
    """Continuous-batching serving over one decoder LM: a
    GPTForCausalLM, or a model that describes its own block
    (``config.decoder_spec()``, ``decoder_params()``:
    models/retention.py).

    ``draft_model`` (required iff ``config.speculative_k >= 1``): the
    small proposer — any GPTForCausalLM over the same vocab; its own
    paged cache tracks the target position-for-position."""

    def __init__(self, model, config: Optional[ServingConfig] = None,
                 draft_model=None):
        import jax
        self.config = cfg = config or ServingConfig()
        own = hasattr(model, "decoder_params")
        mcfg = model.config if own else model.gpt.config
        retention = DecoderSpec.of(mcfg).mixer == "retention"
        if retention:
            _refuse_for_retention(cfg)
        spec = serving_decoder_spec(mcfg, cfg)
        if cfg.max_total_tokens > mcfg.max_seq_len:
            raise ValueError(
                f"max_total_tokens={cfg.max_total_tokens} exceeds the "
                f"model's max_seq_len={mcfg.max_seq_len}")
        self.n_heads = int(mcfg.num_heads)
        self.tp = int(cfg.tp)
        if self.tp > 1 and self.n_heads % self.tp:
            raise ValueError(
                f"plan tp={self.tp} must divide n_heads="
                f"{self.n_heads}: the paged pools shard their merged "
                f"heads axis ([n_blocks, block_size, n_heads="
                f"{self.n_heads} * head_dim]) by whole heads and the "
                f"qkv/proj weights shard per head "
                f"— {self.n_heads} % {self.tp} != 0 leaves a ragged "
                "shard no chip can own")
        # weight snapshot, cast (and PTQ-quantized under quant="int8",
        # qkv-permuted + mesh-sharded under a tp plan) ONCE at engine
        # build; new weights land only through swap_weights() at a
        # token boundary (same treedef/avals — the ladder never
        # recompiles)
        self.params = build_serving_snapshot(
            model.decoder_params() if own else _gpt_params(model), cfg,
            n_heads=self.n_heads)
        self.vocab_size = int(mcfg.vocab_size)
        pool_dtype = cfg.dtype or "float32"
        pool_sharding = None
        jit = jit_with_donated_pools
        if self.tp > 1:
            from jax.sharding import NamedSharding
            from ..distributed.sharding import (SERVING_POOL_SPEC,
                                                serving_param_specs)
            pool_sharding = NamedSharding(cfg.plan.mesh, SERVING_POOL_SPEC)
            # the same programs, shard_mapped over 'tp'
            jit = functools.partial(
                jit_tp_with_donated_pools, mesh=cfg.plan.mesh,
                params_specs=serving_param_specs(self.params),
                n_plain=3, n_out=2)
        if retention:
            # the cache kind is the mixer's: a state row a request
            # (n_blocks counts the rows, scratch included), in f32
            # whatever the weights' dtype: it sums over a whole request
            self.cache = StateCache(
                n_layers=int(mcfg.num_layers), n_rows=cfg.n_blocks,
                n_kv_heads=spec.n_kv_heads, head_dim=spec.head_dim)
        else:
            self.cache = PagedKVCache(
                n_layers=int(mcfg.num_layers), n_blocks=cfg.n_blocks,
                block_size=cfg.block_size, n_heads=self.n_heads,
                head_dim=int(mcfg.hidden_size) // self.n_heads,
                dtype=pool_dtype, prefix_sharing=cfg.prefix_sharing,
                pool_sharding=pool_sharding, tp=self.tp)
        self.ladder = BucketLadder(cfg.prefill_buckets,
                                   cfg.decode_buckets, cfg.block_size)
        self.sched = FifoScheduler(cfg.max_slots, cfg.max_admit)
        sampling = (float(cfg.temperature),
                    None if cfg.top_k is None else int(cfg.top_k),
                    None if cfg.top_p is None else float(cfg.top_p))

        def programs(spec, sampling, n_steps):
            """(prefill, decode, decode's row writes a dispatch or None)
            of one model: the target's or the draft's (which a tp plan
            refuses)."""
            decode = make_decode_fn(spec, cfg.block_size, sampling, n_steps)
            return (jit(make_prefill_fn(spec, cfg.block_size, sampling)),
                    jit(decode), getattr(decode, "writes_per_dispatch",
                                         None))

        self._prefill, self._decode, writes = programs(
            spec, sampling, int(cfg.decode_chunk))
        # state rows a live lane's decode dispatch writes (all layers):
        # the step span's `state_row_writes`
        self._row_writes_per_lane = None if writes is None \
            else writes * int(mcfg.num_layers)
        # the chunk program serves BOTH new levers (speculative verify
        # at [slots, k+1], shared-prefix suffix prefill at [admit,
        # bucket]) — one jit, shape-bucketed executables
        self._spec_k = int(cfg.speculative_k)
        self._chunk = None
        if cfg.prefix_sharing or self._spec_k:
            self._chunk = jit_with_donated_pools(make_chunk_fn(
                spec, cfg.block_size, sampling))
        self.draft_cache = self.draft_params = None
        self._draft_prefill = self._draft_decode = None
        if self._spec_k:
            if draft_model is None:
                raise ValueError(
                    "speculative_k >= 1 needs a draft_model — the "
                    "draft proposes, the target verifies")
            dcfg = draft_model.gpt.config
            if int(dcfg.vocab_size) != self.vocab_size:
                raise ValueError(
                    f"draft vocab {dcfg.vocab_size} != target vocab "
                    f"{mcfg.vocab_size}: proposals would not be "
                    "comparable token ids")
            if cfg.max_total_tokens > dcfg.max_seq_len:
                raise ValueError(
                    f"max_total_tokens={cfg.max_total_tokens} exceeds "
                    f"the draft's max_seq_len={dcfg.max_seq_len}")
            dspec = DecoderSpec.of(dcfg)
            # draft keeps the plain float cast (no int8): it is small
            # by construction, and its only job is proposal quality
            self.draft_params = _cast_params(_gpt_params(draft_model),
                                             cfg.dtype)
            self.draft_cache = PagedKVCache(
                n_layers=int(dcfg.num_layers), n_blocks=cfg.n_blocks,
                block_size=cfg.block_size, n_heads=dspec.n_heads,
                head_dim=dspec.head_dim, dtype=pool_dtype)
            # proposals are always argmax, and ONE scan dispatch
            # proposes all k tokens
            self._draft_prefill, self._draft_decode, _ = programs(
                dspec, (0.0, None, None), self._spec_k)
        self.sentinel = RecompileSentinel("serving")
        self._key = jax.random.key(int(cfg.seed))
        self._step_no = 0
        self._warmed = False
        # request-trace lane labels: a ServingFleet stamps the slot at
        # spawn and the fleet tick before every step(); standalone
        # engines trace as replica None on their own step counter
        self.trace_replica: Optional[int] = None
        self.trace_tick: Optional[int] = None

    # -- compile-count contract ----------------------------------------------
    def executable_count(self) -> int:
        n = self._prefill._cache_size() + self._decode._cache_size()
        if self._chunk is not None:
            n += self._chunk._cache_size()
        if self._draft_prefill is not None:
            n += (self._draft_prefill._cache_size()
                  + self._draft_decode._cache_size())
        n += self.cache.copy_executables()
        return int(n)

    @property
    def expected_executables(self) -> int:
        """The steady-state compile budget the sentinel pins. Feature
        legs swap programs rather than stack them (sharing replaces
        the dense prefill with chunk suffix prefills; speculation
        replaces the plain decode with draft-propose + chunk-verify),
        and chunk executables dedupe by SHAPE — a verify width that
        collides with a suffix bucket is one executable."""
        cfg = self.config
        n = 0
        chunk_shapes = set()
        if cfg.prefix_sharing:
            for s in self.ladder.prefill:
                chunk_shapes.add((self.sched.max_admit, s))
            n += 1                       # the COW page-copy program
        else:
            n += len(self.ladder.prefill)
        if self._spec_k:
            for b in self.ladder.decode:
                chunk_shapes.add((b, self._spec_k + 1))
            n += len(self.ladder.prefill)   # draft prompt prefill
            n += len(self.ladder.decode)    # draft k-proposal scan
        else:
            n += len(self.ladder.decode)
        return n + len(chunk_shapes)

    # -- request intake ------------------------------------------------------
    def submit(self, ids, max_new_tokens: int, rid=None,
               eos_token_id=None, arrival: Optional[float] = None):
        """Queue one request. Fails loudly on shapes the ladder cannot
        serve — a queued-then-unservable request would wedge FIFO
        admission forever."""
        req = Request(ids=ids, max_new_tokens=int(max_new_tokens),
                      rid=rid,
                      eos_token_id=(self.config.eos_token_id
                                    if eos_token_id is None
                                    else eos_token_id),
                      arrival=(time.perf_counter()
                               if arrival is None else arrival))
        self.ladder.pick_prefill(req.prompt_len)  # raises if too long
        if req.total_tokens > self.config.max_total_tokens:
            raise ValueError(
                f"request needs {req.total_tokens} tokens > "
                f"max_total_tokens={self.config.max_total_tokens}")
        need = self.cache.blocks_for(req.total_tokens)
        if need > self.cache.n_blocks - 1:
            raise ValueError(
                f"request needs {need} pages > pool size "
                f"{self.cache.n_blocks - 1}")
        if _rt._enabled:
            if self.trace_replica is None:
                # standalone engine: this call IS the request's arrival
                # into the serving plane (a fleet marks submit itself,
                # at the class-queue, with the trace-clock arrival)
                _rt.mark(req.rid, "submit", t=req.arrival)
            _rt.mark(req.rid, "dispatch", replica=self.trace_replica)
        self.sched.submit(req)
        if _obs._enabled:
            _obs.gauge("serving.queue_depth").set(self.sched.queue_depth)
        return req.rid

    def has_work(self) -> bool:
        return self.sched.has_work()

    # -- the ladder warmup ---------------------------------------------------
    def warmup(self):
        """Compile the WHOLE ladder up front on dummy lanes (all-zero
        tables: every write lands in the scratch page). A server pays
        its compiles at startup; steady state then runs a fixed
        executable set and the sentinel flags any growth."""
        import jax
        cfg = self.config
        W = cfg.table_width
        a = self.sched.max_admit
        key = jax.random.key(0)

        def dummy(n):
            # lanes without a request: the cache's scratch addressing
            return self.cache.table_array([None] * n, W)

        # prime the per-boundary key derivation as well: the first
        # step()'s fold_in chain otherwise traces+compiles mid-traffic
        # — ~100 ms the request traces pin on the first admit batch
        jax.random.fold_in(jax.random.fold_in(self._key, 1), 0)
        if cfg.prefix_sharing:
            # sharing serves EVERY admission through the chunk program
            # (starts=0 on a full miss IS a dense prefill, junk routed
            # to scratch instead of page-scattered); plus the COW copy
            for s in self.ladder.prefill:
                self.cache.pools, _, _ = self._chunk(
                    self.cache.pools, dummy(a),
                    np.zeros((a, s), np.int32),
                    np.zeros((a,), np.int32), np.ones((a,), np.int32),
                    self.params, key)
            self.cache.warm_copy()
        else:
            for s in self.ladder.prefill:
                self.cache.pools, _ = self._prefill(
                    self.cache.pools, dummy(a),
                    np.zeros((a, s), np.int32),
                    np.ones((a,), np.int32), self.params, key)
        if self._spec_k:
            # speculation replaces the plain decode with the draft's
            # prefill + k-proposal scan and the target's [b, k+1]
            # chunk verify, per decode bucket
            for b in self.ladder.decode:
                self.cache.pools, _, _ = self._chunk(
                    self.cache.pools, dummy(b),
                    np.zeros((b, self._spec_k + 1), np.int32),
                    np.zeros((b,), np.int32), np.ones((b,), np.int32),
                    self.params, key)
            for s in self.ladder.prefill:
                self.draft_cache.pools, _ = self._draft_prefill(
                    self.draft_cache.pools, np.zeros((a, W), np.int32),
                    np.zeros((a, s), np.int32),
                    np.ones((a,), np.int32), self.draft_params, key)
            for b in self.ladder.decode:
                self.draft_cache.pools, _ = self._draft_decode(
                    self.draft_cache.pools, np.zeros((b, W), np.int32),
                    np.zeros((b,), np.int32), np.zeros((b,), np.int32),
                    self.draft_params, key)
        else:
            for b in self.ladder.decode:
                self.cache.pools, _ = self._decode(
                    self.cache.pools, dummy(b),
                    np.zeros((b,), np.int32), np.zeros((b,), np.int32),
                    self.params, key)
        self.sentinel.observe(self.executable_count(),
                              expected=self.expected_executables,
                              signature=self._shape_signature(None, None))
        self._warmed = True
        return self

    # -- one token boundary --------------------------------------------------
    def step(self) -> List[Request]:
        """Retire, admit, decode — returns the requests that FINISHED
        at this boundary (their pages already freed). With request
        tracing on, a step that has work is one ``step`` span over
        contiguous phases (DESIGN.md "Request anatomy")."""
        import jax
        cfg = self.config
        rec = _obs._enabled
        self._step_no += 1
        tr = (_rt.open_step(self._step_no, self.trace_replica)
              if _rt._enabled and self.sched.has_work() else _rt.NO_STEP)
        n_exec = None
        counts = {}
        try:
            tr.phase("retire")
            finished = self.sched.retire_finished()
            for r in finished:
                self.cache.free(r.rid)
                if self.draft_cache is not None:
                    self.draft_cache.free(r.rid)
                r.done_ts = time.perf_counter()
            if _rt._enabled:
                for r in finished:
                    _rt.mark(r.rid, "retire", t=r.done_ts,
                             reason=r.finish_reason,
                             replica=self.trace_replica)
            if rec and finished:
                _obs.counter("serving.retired_total").add(len(finished))

            tr.phase("admit")
            batch = self.sched.take_admissible(
                self.cache,
                () if self.draft_cache is None else (self.draft_cache,))
            tr.phase("keys")
            # one fresh key per boundary, then DISTINCT subkeys for
            # the two programs: prefill's _pick consumes its key
            # directly while decode splits its own per chunk step —
            # handing both the same key would correlate the sampled
            # draws (greedy is unaffected)
            key = jax.random.fold_in(self._key, self._step_no)
            pf_key = jax.random.fold_in(key, 0)
            dec_key = jax.random.fold_in(key, 1)
            prefill_sig = decode_sig = None
            chunk_sigs: List[Tuple[int, int]] = []
            if batch:
                shape = (self.sched.max_admit,
                         self._prefill_batch(batch, pf_key, tr))
                if cfg.prefix_sharing:
                    chunk_sigs.append(shape)
                else:
                    prefill_sig = shape
            active = self.sched.active()
            if active and self._spec_k:
                decode_sig = (self._speculate(active, dec_key, tr),)
                chunk_sigs.append((decode_sig[0], self._spec_k + 1))
            elif active:
                decode_sig = (self._decode_chunk(active, dec_key, tr),)
                if self._row_writes_per_lane is not None:
                    counts["state_row_writes"] = \
                        len(active) * self._row_writes_per_lane

            tr.phase("observe")
            if batch or active or tr is not _rt.NO_STEP:
                n_exec = self.executable_count()
            if batch or active:
                self.sentinel.observe(
                    n_exec, expected=self.expected_executables,
                    signature=self._shape_signature(prefill_sig,
                                                    decode_sig,
                                                    chunk_sigs))
            if rec:
                _obs.gauge("serving.queue_depth").set(
                    self.sched.queue_depth)
                _obs.gauge("serving.active_slots").set(
                    len(self.sched.active()))
                _obs.gauge("serving.pages_free").set(self.cache.n_free)
                _obs.gauge("serving.pages_live").set(self.cache.n_live)
                if cfg.prefix_sharing:
                    _obs.gauge("serving.pages_shared").set(
                        self.cache.n_shared)
        finally:
            # also when a dispatch raised: no annotation stays entered
            tr.close(n_exec, **self.cache.span_counts(), **counts)
        return finished

    def _dispatch(self, tr, kind, bucket, width, fn, cache, args,
                  params, key, take):
        """Call one compiled program (``kind``; ``bucket`` x ``width``
        is its shape) over ``cache``'s pools and fetch output ``take``
        (None: nothing is fetched — the draft's prompt prefill). The
        one place that writes the ``dispatch`` (the jitted call, until
        it returns) and ``sync`` (the fetch that waits for the device)
        phases and holds the OOM sentry (zero cost on the success
        path): a RESOURCE_EXHAUSTED leaves the breadcrumb + post-mortem
        receipt before the engine dies."""
        tr.phase("dispatch", kind)
        try:
            out = fn(cache.pools, *args, params, key)
        except Exception as e:
            _mem.handle_dispatch_oom(
                "serving_" + kind, e, bucket=bucket, width=width,
                replica=self.trace_replica, step=self._step_no)
            raise
        cache.pools = out[0]
        if take is None:
            return None
        tr.phase("sync", kind)
        return np.asarray(out[take])

    def _pack_prompts(self, batch, bucket: int, skip=None):
        """[max_admit, bucket] ids and true lengths of each request's
        prompt (past its first ``skip[i]`` tokens), zero-padded."""
        a = self.sched.max_admit
        ids = np.zeros((a, bucket), np.int32)
        lens = np.ones((a,), np.int32)
        for i, r in enumerate(batch):
            tail = r.ids if skip is None else r.ids[skip[i]:]
            ids[i, :tail.size] = tail
            lens[i] = tail.size
        return ids, lens

    def _pack_slots(self, active, b: int):
        """Last token, position and rid of every active slot, padded to
        the decode bucket."""
        toks = np.zeros((b,), np.int32)
        positions = np.zeros((b,), np.int32)
        for i, r in enumerate(active):
            toks[i] = r.out[-1]
            positions[i] = r.pos
        rids = [r.rid for r in active] + [None] * (b - len(active))
        return toks, positions, rids

    def _span_tick(self) -> int:
        return self._step_no if self.trace_tick is None \
            else self.trace_tick

    def _prefill_batch(self, batch, key, tr) -> int:
        """Pages for the admit batch, ONE bucketed prefill of it, and
        each request's first token. Returns the bucket."""
        cfg = self.config
        a = self.sched.max_admit
        W = cfg.table_width
        t0 = time.perf_counter()
        tr.phase("alloc", t=t0)
        if cfg.prefix_sharing:
            # radix admission: longest indexed prompt prefix rides
            # shared pages (refcount++), fresh pages cover the rest
            for r in batch:
                _, r.shared_tokens = self.cache.alloc_shared(
                    r.rid, r.total_tokens, r.ids)
        else:
            for r in batch:
                self.cache.alloc(r.rid, r.total_tokens)
        t_match = time.perf_counter()
        rids = [r.rid for r in batch] + [None] * (a - len(batch))
        if self.draft_cache is not None:
            # the draft mirrors the target position-for-position;
            # its cache never shares, so it prefills the FULL
            # prompt regardless of the target's prefix hits
            for r in batch:
                self.draft_cache.alloc(r.rid, r.total_tokens)
            sd = self.ladder.pick_prefill(
                max(r.prompt_len for r in batch))
            tr.phase("build", "draft")
            d_ids, d_lens = self._pack_prompts(batch, sd)
            self._dispatch(
                tr, "draft", sd, a, self._draft_prefill,
                self.draft_cache,
                (self.draft_cache.table_array(rids, W), d_ids, d_lens),
                self.draft_params, key, None)
        # under sharing each row forwards ONLY its unshared tail
        # through the chunk program, starting at its shared-token
        # offset and attending the shared pages through the same table
        # gather decode uses (a full miss is starts=0 — a dense prefill
        # with junk routed to scratch instead of page-scattered)
        skip = [r.shared_tokens if cfg.prefix_sharing else 0
                for r in batch]
        s = self.ladder.pick_prefill(
            max(r.prompt_len - n for r, n in zip(batch, skip)))
        tr.phase("build", "prefill")
        ids, lens = self._pack_prompts(batch, s, skip)
        tables = self.cache.table_array(rids, W)
        if cfg.prefix_sharing:
            starts = np.zeros((a,), np.int32)
            starts[:len(batch)] = skip
            tok = self._dispatch(tr, "prefill", s, a, self._chunk,
                                 self.cache, (tables, ids, starts, lens),
                                 self.params, key, 2)
        else:
            tok = self._dispatch(tr, "prefill", s, a, self._prefill,
                                 self.cache, (tables, ids, lens),
                                 self.params, key, 1)
        now = time.perf_counter()
        tr.phase("accept", "prefill", now)
        for i, r in enumerate(batch):
            r.admitted_ts = t0
            r.first_token_ts = now
            r.pos = r.prompt_len
            r.accept(int(tok[i]))
        if cfg.prefix_sharing:
            # adopt this prompt's full-chunk pages into the radix
            # index AFTER the prefill landed their K/V — the NEXT
            # request with this prefix shares them
            for r in batch:
                self.cache.register_prefix(r.rid, r.ids)
        if _rt._enabled:
            tick = self._span_tick()
            for r in batch:
                if r.shared_tokens:
                    # the radix-match + shared-alloc slice of
                    # admission, so tail attribution sees sharing
                    # cost (and benefit) by name
                    _rt.record_span(
                        r.rid, "prefix_match", t0, t_match,
                        shared_tokens=r.shared_tokens,
                        replica=self.trace_replica, tick=tick)
                _rt.record_span(r.rid, "prefill",
                                t_match if r.shared_tokens else t0,
                                now, bucket=s, width=a,
                                replica=self.trace_replica, tick=tick)
        if _obs._enabled:
            _obs.counter("serving.admitted_total").add(len(batch))
            if cfg.prefix_sharing:
                hits = sum(1 for r in batch if r.shared_tokens)
                if hits:
                    _obs.counter("serving.prefix_hits_total").add(hits)
                    _obs.counter(
                        "serving.prefix_shared_pages_total").add(
                        sum(r.shared_tokens // cfg.block_size
                            for r in batch))
        return s

    def _decode_chunk(self, active, key, tr) -> int:
        """One chunked decode dispatch for every active slot. Returns
        the slot bucket."""
        cfg = self.config
        b = self.ladder.pick_decode(len(active))
        t0 = time.perf_counter()
        tr.phase("build", "decode", t0)
        toks, positions, rids = self._pack_slots(active, b)
        tables = self.cache.table_array(rids, cfg.table_width)
        toks_out = self._dispatch(          # [decode_chunk, B]
            tr, "decode", b, int(cfg.decode_chunk), self._decode,
            self.cache, (tables, toks, positions), self.params, key, 1)
        tr.phase("accept", "decode")
        accepted = 0
        for i, r in enumerate(active):
            for s in range(toks_out.shape[0]):
                if r.done:
                    break   # over-decoded junk: host trims
                r.pos += 1
                r.accept(int(toks_out[s, i]))
                accepted += 1
        if _rt._enabled:
            t1 = time.perf_counter()
            tick = self._span_tick()
            for i, r in enumerate(active):
                # tokens: what the request held at the dispatch, the
                # positions the first token-step's attention reads
                _rt.record_span(r.rid, "decode", t0, t1, bucket=b,
                                chunk=int(toks_out.shape[0]),
                                tokens=int(positions[i]),
                                replica=self.trace_replica, tick=tick)
        if _obs._enabled:
            _obs.counter("serving.tokens_total").add(accepted)
        return b

    def _speculate(self, active, key, tr) -> int:
        """One speculative boundary: draft proposes k tokens in one
        scan dispatch, target scores anchor + proposals in one chunk
        dispatch, host keeps the longest agreeing prefix. Every
        emitted token is a TARGET argmax over a cache prefix that held
        only accepted tokens — bit-identical to sequential greedy by
        induction; speculation can only change how many such tokens
        land per boundary. Returns the slot bucket."""
        cfg = self.config
        k = self._spec_k
        W = cfg.table_width
        b = self.ladder.pick_decode(len(active))
        t0 = time.perf_counter()
        tr.phase("build", "draft", t0)
        toks, positions, rids = self._pack_slots(active, b)
        props = self._dispatch(             # [k, B]
            tr, "draft", b, k, self._draft_decode, self.draft_cache,
            (self.draft_cache.table_array(rids, W), toks, positions),
            self.draft_params, key, 1)
        t_draft = time.perf_counter()
        tr.phase("build", "verify", t_draft)
        ids = np.zeros((b, k + 1), np.int32)
        lens = np.ones((b,), np.int32)
        for i, r in enumerate(active):
            # emission cap: proposals past the budget are junk the
            # chunk program routes to scratch (lens masks them)
            cap = min(k, r.max_new_tokens - len(r.out))
            ids[i, 0] = r.out[-1]
            ids[i, 1:] = props[:, i]
            lens[i] = cap + 1
        tables = self.cache.table_array(rids, W)
        all_tok = self._dispatch(           # [B, k+1]
            tr, "verify", b, k + 1, self._chunk, self.cache,
            (tables, ids, positions, lens), self.params, key, 1)
        tr.phase("accept", "verify")
        proposed = accepted = 0
        for i, r in enumerate(active):
            cap = int(lens[i]) - 1
            proposed += cap
            n = 0
            while n < cap:
                tokv = int(all_tok[i, n])     # target argmax
                r.pos += 1
                r.accept(tokv)
                n += 1
                if r.done or n >= cap:
                    break
                if int(props[n - 1, i]) != tokv:
                    break   # draft diverged: later scores are
                    #         junk-conditioned, stop here
            accepted += n
        if _rt._enabled:
            t1 = time.perf_counter()
            tick = self._span_tick()
            for r in active:
                _rt.record_span(r.rid, "draft", t0, t_draft, bucket=b,
                                k=k, replica=self.trace_replica,
                                tick=tick)
                _rt.record_span(r.rid, "decode", t_draft, t1, bucket=b,
                                chunk=k + 1,
                                replica=self.trace_replica, tick=tick)
        if _obs._enabled:
            _obs.counter("serving.tokens_total").add(accepted)
            _obs.counter("serving.spec_proposed_total").add(proposed)
            _obs.counter("serving.spec_accepted_total").add(accepted)
            if proposed:
                _obs.gauge("serving.spec_acceptance_rate").set(
                    accepted / proposed)
        return b

    # -- fleet surface: eviction + hot weight swap ---------------------------
    def evict_requests(self) -> List[Request]:
        """Strip EVERY in-flight request off a TRUSTED engine for
        exact requeue elsewhere (operational drain before shutdown or
        handoff — the fleet's failure path instead rebuilds from its
        own harvested streams, because a wedged engine can't be
        trusted to report its state). Returns running requests
        (admission order) then queued ones (FIFO); a running request
        keeps ``ids``/``pos``/``out``, and because page reservation is
        whole-lifetime, prompt + emitted tokens fully describe it — no
        other device state is needed for a bit-identical replay under
        the f32 greedy parity contract (resume = prefill(prompt +
        emitted) on the new engine). Pages are freed; increments the
        REAL ``serving.evicted_total``."""
        running = list(self.sched.running.values())
        for r in running:
            self.cache.free(r.rid)
            if self.draft_cache is not None:
                self.draft_cache.free(r.rid)
        self.sched.running.clear()
        queued = list(self.sched.queue)
        self.sched.queue.clear()
        evicted = running + queued
        if _obs._enabled and evicted:
            _obs.counter("serving.evicted_total").add(len(evicted))
            _obs.gauge("serving.queue_depth").set(0)
            _obs.gauge("serving.active_slots").set(0)
            _obs.gauge("serving.pages_free").set(self.cache.n_free)
        return evicted

    def swap_weights(self, params, cast: bool = True):
        """Install new weights at a token boundary without draining —
        the serve half of the train→serve continuous-deployment loop.
        The engine is host-driven, so any point between ``step()``
        calls IS a token boundary; running requests keep their pages
        and simply decode their next token under the new weights.

        Validates treedef + shape/dtype equality against the current
        snapshot BEFORE flipping, so a swap can never change a program
        signature: the compiled ladder stays byte-for-byte valid and
        the RecompileSentinel stays pinned (zero recompiles by
        construction). ``cast=True`` runs the standby through the
        engine's FULL snapshot build — serving cast plus the int8 PTQ
        under quant="int8", so the treedef matches — (pass
        ``cast=False`` for a snapshot already built once via
        build_serving_snapshot and shared across replicas)."""
        import jax
        import jax.numpy as jnp
        new = (build_serving_snapshot(params, self.config,
                                      n_heads=self.n_heads) if cast
               else params)
        old_leaves, old_def = jax.tree_util.tree_flatten(self.params)
        new_leaves, new_def = jax.tree_util.tree_flatten(new)
        if old_def != new_def:
            raise ValueError(
                "weight swap rejected: params tree structure differs "
                "from the serving snapshot (same model family only)")
        for i, (o, n) in enumerate(zip(old_leaves, new_leaves)):
            if (tuple(getattr(n, "shape", ())) != tuple(o.shape)
                    or str(getattr(n, "dtype", "?")) != str(o.dtype)):
                raise ValueError(
                    f"weight swap rejected: leaf {i} is "
                    f"{tuple(getattr(n, 'shape', ()))}/"
                    f"{getattr(n, 'dtype', '?')}, serving snapshot "
                    f"holds {tuple(o.shape)}/{o.dtype} — a mismatch "
                    "would recompile or corrupt the ladder")
        # normalize AFTER validation: the engine's build-time params
        # are UNCOMMITTED jax arrays, and commitment is part of the
        # jit cache key — an orbax-restored leaf arrives COMMITTED to
        # its device (and a raw numpy leaf is host-side), so flipping
        # either in directly would RETRACE the whole ladder on the
        # first post-flip dispatch. The host round-trip yields fresh
        # uncommitted arrays that hit the existing executables. Under
        # a tp plan the inverse holds: build-time params are COMMITTED
        # to the plan's mesh with the derived specs, so the one
        # placement that hits the compiled ladder is that same
        # device_put — a host round-trip would un-shard and retrace.
        if self.tp > 1:
            from ..distributed.sharding import serving_param_shardings
            self.params = jax.device_put(
                new, serving_param_shardings(self.config.plan.mesh,
                                             new))
        else:
            import numpy as _np
            self.params = jax.tree_util.tree_map(
                lambda a: jnp.asarray(_np.asarray(a)), new)
        if _obs._enabled:
            _obs.counter("serving.weight_swaps_total").add(1)
        return self

    def _shape_signature(self, prefill_sig, decode_sig, chunk_sigs=()):
        """Sentinel signature: the bucket shapes this step dispatched
        (a violation's diff then names the drifting bucket)."""
        sig = []
        if prefill_sig is not None:
            sig.append(("prefill", tuple(prefill_sig), "bucket"))
        if decode_sig is not None:
            sig.append(("decode", tuple(decode_sig), "bucket"))
        for cs in chunk_sigs:
            sig.append(("chunk", tuple(cs), "bucket"))
        return tuple(sig)

    # -- convenience drains --------------------------------------------------
    def run_to_completion(self, max_steps: int = 100000
                          ) -> List[Request]:
        """Drain the queue + running set; returns every finished
        request in completion order."""
        done: List[Request] = []
        for _ in range(max_steps):
            if not self.has_work():
                break
            done.extend(self.step())
        else:
            raise RuntimeError(
                f"run_to_completion: work left after {max_steps} "
                "steps (eos never fired and budgets did not expire?)")
        return done

    def generate_tokens(self, prompts: Sequence[np.ndarray],
                        max_new_tokens) -> List[List[int]]:
        """Batch convenience: submit all, drain, return per-prompt
        generated tokens in submit order (the parity-test surface)."""
        if isinstance(max_new_tokens, int):
            max_new_tokens = [max_new_tokens] * len(prompts)
        rids = [self.submit(p, n)
                for p, n in zip(prompts, max_new_tokens)]
        by_rid = {r.rid: r for r in self.run_to_completion()}
        return [list(by_rid[rid].out) for rid in rids]
