"""The serving engine's two compiled programs: bucketed prefill and the
paged decode step.

TVM's lesson (PAPERS.md) dictates the TPU shape: a SMALL, FIXED set of
pre-compiled executables over static shapes, never a recompile per
request. The whole steady-state serving loop is exactly

  n_prefill_buckets   prefill executables   (admit width x bucket len)
  n_decode_buckets    decode executables    (slot-count buckets)

and the RecompileSentinel pins that count every step.

Both programs take the page pools FIRST and donate them
(``donate_argnums=(0,)``), so XLA writes K/V pages in place — the
graph_lint donation rule proves the aliasing on the lowered module.
The math reuses models/generation.py's helpers (`_ln`, `_attend`,
`_prefill`, `_pick`) verbatim, which is what makes the paged-vs-dense
greedy parity contract hold token-for-token in f32: same ops in the
same order, only the cache addressing differs. That is the portable
path, and what every platform but a TPU runs. On a TPU the decode
step's attention is `ops/pallas_kernels.paged_decode_attention`: it
reads each slot's live pages in place through the block table (an
online softmax in f32) instead of gathering every table whole, chosen
by the platform as the flash kernel is, and held to the gather and
`_attend` by tests/test_paged_decode_attention.py (f32 within 1e-5,
the greedy token the same). The chunk program (several queries a slot)
keeps the gather.

Addressing: a pool is ``[n_blocks, block_size, n_heads * head_dim]``,
one row a token with the heads side by side; logical position ``p`` of
a request lives in page ``table[p // block_size]`` at offset
``p % block_size``. The programs write whole rows (``k_tok`` reshaped
to ``[B, nh*hd]``, a prefill's page chunks to ``[A, nblk, bs, nh*hd]``)
and split heads only after a gather. The shape is chosen for the
device: its default TPU layout is the row-major one these scatters
(and the kernel) address, so a donated pool is written in place; a
4-D page with head 64 lived pages-minor-most and every dispatch
converted all the pools at entry and at exit (paged_cache.py). Masked or
padded lanes carry an all-zeros table row — their writes land in the
reserved scratch page 0 and their reads are iota-masked, so inactive
lanes cost no conditional scatter. Junk K/V (pad positions a bucketed
prefill computes past a row's true length) is either routed to scratch
by table padding or progressively overwritten by the decode scatter —
and never attended, because every attention masks to the row's live
prefix.

Tensor parallelism (``ServingConfig(plan=MeshPlan(tp=N))``) reuses
these exact bodies inside a ``shard_map`` over the 'tp' axis: the
makers' ``qkv_heads_major``/``tp_reduce``/``head_dim`` hooks switch the
qkv column layout to heads-major (whole heads per contiguous shard)
and all-reduce the proj/fc2 partial contractions before their biases —
with both hooks off, the tp=1 graph is byte-for-byte the one these
makers always built, which is what keeps the parity contract intact.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..models.generation import _attend, _ln, _mm, _pick, _prefill
from ..observability.anatomy import scope as _scope

__all__ = ["make_decode_fn", "make_prefill_fn", "make_chunk_fn",
           "jit_with_donated_pools", "jit_tp_with_donated_pools"]


def _gathered(pool, tables, n_heads, hd):
    """Pages -> contiguous logical cache: [n_blocks, bs, nh*hd]
    gathered by [B, W] tables into [B, nh, W*bs, hd] (table order IS
    logical order, so index j along the length axis is position j);
    heads are split after the gather."""
    b, w = tables.shape
    pages = pool[tables]                       # [B, W, bs, nh*hd]
    flat = pages.reshape(b, w * pool.shape[1], n_heads, hd)
    return jnp.einsum("bsnh->bnsh", flat)


def make_decode_fn(eps: float, n_heads: int, block_size: int,
                   temperature: float, top_k, top_p,
                   n_steps: int = 1, qkv_heads_major: bool = False,
                   tp_reduce=None, head_dim=None):
    """``n_steps`` token boundaries for every running slot, fused into
    one dispatch (lax.scan over the single-token body).

    run(pools, tables, toks, positions, params, key)
        -> (pools', toks [n_steps, B])

    toks [B] is each slot's last emitted token, positions [B] the
    logical index where its K/V land (== tokens held so far). The body
    mirrors generation.py's ragged decode body exactly, with the
    dynamic_update_slice cache write swapped for the paged scatter
    (and, on a TPU, the gather and `_attend` for the paged kernel).

    n_steps > 1 is the multi-step-scheduling lever: admission/retire
    decisions then happen every n_steps tokens instead of every token,
    trading a bounded TTFT granularity for host-dispatch amortization
    (the per-token jit round-trip is the serving loop's overhead
    floor). Rows whose budget or eos fires mid-chunk over-decode at
    most n_steps-1 junk tokens; their writes land in their own
    reserved pages (or clamp to their last page), which die with the
    request — the host trims the emitted stream.
    """

    def step(pools, tables, toks, positions, params, key):
        # anatomy scopes (pure HLO metadata, zero program change): the
        # memory plane attributes the paged cache's scatter/gather and
        # the per-layer matmuls row-for-row with the train taxonomy
        b = toks.shape[0]
        hd = head_dim or params["wte"].shape[1] // n_heads
        scale = 1.0 / math.sqrt(hd)
        # one algorithm, two executions, chosen by the platform as the
        # flash kernel is: on a TPU the paged kernel reads the live
        # pages in place; elsewhere the gather and _attend, which are
        # the reference the kernel is tested against
        from ..ops import pallas_kernels as _pk
        on_tpu = _pk.pallas_available()
        with _scope("embed"):
            x = (params["wte"][toks]
                 + params["wpe"][positions])[:, None, :]
        bi = jnp.arange(b)
        blk = tables[bi, positions // block_size]        # [B]
        off = positions % block_size                     # [B]
        new_pools = []
        for bp, (kp, vp) in zip(params["blocks"], pools):
            with _scope("attn"):
                xn = _ln(x, bp["ln1_w"], bp["ln1_b"], eps)
                qkv = _mm(xn, bp, "qkv") + bp["qkv_b"]
                if qkv_heads_major:
                    qkv = jnp.einsum("bsnch->bscnh", qkv.reshape(
                        b, 1, n_heads, 3, hd))
                else:
                    qkv = qkv.reshape(b, 1, 3, n_heads, hd)
                q = jnp.einsum("bsnh->bnsh", qkv[:, :, 0])  # [B,nh,1,hd]
                k_tok = qkv[:, 0, 1].reshape(b, -1)      # [B,nh*hd]
                v_tok = qkv[:, 0, 2].reshape(b, -1)
                kp = kp.at[blk, off].set(k_tok)
                vp = vp.at[blk, off].set(v_tok)
                if on_tpu:
                    ctx = _pk.paged_decode_attention(
                        q[:, :, 0], kp, vp, tables, positions + 1, scale)
                else:
                    kc = _gathered(kp, tables, n_heads, hd)
                    vc = _gathered(vp, tables, n_heads, hd)
                    ctx = jnp.einsum("bnsh->bsnh", _attend(
                        q, kc, vc, positions + 1, scale))
                ctx = ctx.reshape(b, 1, -1)
                proj = _mm(ctx, bp, "proj")
                if tp_reduce is not None:
                    proj = tp_reduce(proj)
                x = x + proj + bp["proj_b"]
            with _scope("mlp"):
                ff = _ln(x, bp["ln2_w"], bp["ln2_b"], eps)
                ff = jax.nn.gelu(_mm(ff, bp, "fc1") + bp["fc1_b"],
                                 approximate=False)
                f2 = _mm(ff, bp, "fc2")
                if tp_reduce is not None:
                    f2 = tp_reduce(f2)
                x = x + f2 + bp["fc2_b"]
            new_pools.append((kp, vp))
        with _scope("lm_head"):
            h = _ln(x, params["lnf_w"], params["lnf_b"], eps)
            logits = h[:, 0] @ params["wte"].T
            tok = _pick(logits, key, temperature, top_k, top_p)
        return tuple(new_pools), tok

    def run(pools, tables, toks, positions, params, key):
        def body(carry, step_key):
            pools, toks, positions = carry
            pools, tok = step(pools, tables, toks, positions, params,
                              step_key)
            return (pools, tok, positions + 1), tok
        keys = jax.random.split(key, n_steps)
        (pools, _, _), out = jax.lax.scan(
            body, (pools, toks, positions), keys)
        return pools, out                              # [n_steps, B]

    return run


def make_prefill_fn(eps: float, n_heads: int, block_size: int,
                    temperature: float, top_k, top_p,
                    qkv_heads_major: bool = False, tp_reduce=None,
                    head_dim=None):
    """Bucketed admission prefill: the whole admit batch — MIXED true
    lengths — shares ONE executable per (admit width, bucket len).

    run(pools, tables, ids, prompt_lens, params, key) -> (pools', tok)

    ids [A, S] is right-padded to the bucket width S (a multiple of
    block_size); prompt_lens [A] drives generation.py's iota prefill
    mask, so each row's hidden state at its own last true token is
    exactly what the dense ragged path computes. The per-layer dense
    K/V [A, nh, S, hd] is then scattered page-wise into the pools and
    the first generated token is picked from the last-token logits.
    """

    def run(pools, tables, ids, prompt_lens, params, key):
        a, s = ids.shape
        if s % block_size:
            raise ValueError(
                f"prefill bucket {s} is not a multiple of "
                f"block_size {block_size}")
        nblk = s // block_size
        with _scope("attn"):
            # the dense forward (generation.py's _prefill: embeddings,
            # per-layer attention + FFN) traces inside the transformer
            # helper — its own layers carry no finer scopes, so the
            # whole forward attributes to attn (the dominant term)
            x, caches = _prefill(params, eps, n_heads, ids, s,
                                 prompt_lens=prompt_lens,
                                 qkv_heads_major=qkv_heads_major,
                                 tp_reduce=tp_reduce,
                                 head_dim=head_dim)
            new_pools = []
            for (kp, vp), (kc, vc) in zip(pools, caches):
                # [A, nh, S, hd] -> page chunks [A, nblk, bs, nh*hd]
                kcs = jnp.einsum("ansh->asnh", kc).reshape(
                    a, nblk, block_size, -1)
                vcs = jnp.einsum("ansh->asnh", vc).reshape(
                    a, nblk, block_size, -1)
                kp = kp.at[tables[:, :nblk]].set(kcs)
                vp = vp.at[tables[:, :nblk]].set(vcs)
                new_pools.append((kp, vp))
        with _scope("lm_head"):
            idx = (prompt_lens - 1).astype(jnp.int32)
            last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
            h_last = _ln(last, params["lnf_w"], params["lnf_b"], eps)
            logits = h_last[:, 0] @ params["wte"].T
            tok = _pick(logits, key, temperature, top_k, top_p)
        return tuple(new_pools), tok

    return run


def make_chunk_fn(eps: float, n_heads: int, block_size: int,
                  temperature: float, top_k, top_p,
                  qkv_heads_major: bool = False, tp_reduce=None,
                  head_dim=None):
    """Mid-stream multi-token forward over the PAGED cache — the one
    program behind both new raw-speed levers:

    - **speculative verify**: the target model scores a draft's k
      proposals plus the anchor token in ONE dispatch (shape
      ``[slots, k+1]``) and returns every position's greedy argmax, so
      the host can keep the longest agreeing prefix;
    - **shared-prefix suffix prefill**: an admitted request whose
      prompt head already lives in shared pages forwards ONLY the
      unshared tail (shape ``[admit, suffix_bucket]``), its queries
      attending the shared pages through the same table gather decode
      uses.

    run(pools, tables, toks, starts, lens, params, key)
        -> (pools', all_tok [B, S], picked [B])

    toks [B, S] right-padded token window; starts [B] the absolute
    logical position of toks[:, 0] (== tokens already in the cache);
    lens [B] valid counts (1..S). Position q of row i lands its K/V at
    logical ``starts[i] + q`` — pages for positions past lens route to
    SCRATCH (clamped-column writes past a row's table would land in
    its last real page, which under prefix sharing may even be
    borrowed; the valid-mask makes junk structurally harmless instead
    of accidentally so). Per-query causal masking (`key_pos <=
    query_pos`) keeps every query's softmax support exactly the
    decode-step support, which is what lets the verify argmaxes be
    bit-identical to sequential decode in f32.

    all_tok is each position's greedy argmax (the verify receipt);
    picked is the sampled/argmax token at each row's LAST valid
    position (the next token a non-speculative boundary would emit).
    """

    def run(pools, tables, toks, starts, lens, params, key):
        b, s = toks.shape
        hd = head_dim or params["wte"].shape[1] // n_heads
        scale = 1.0 / math.sqrt(hd)
        offs = jnp.arange(s, dtype=jnp.int32)
        positions = starts[:, None] + offs[None, :]        # [B, S]
        valid = offs[None, :] < lens[:, None]              # [B, S]
        with _scope("embed"):
            wpe = params["wpe"]
            pos_emb = wpe[jnp.clip(positions, 0, wpe.shape[0] - 1)]
            x = params["wte"][toks] + pos_emb              # [B, S, H]
        bi = jnp.arange(b)[:, None]                        # [B, 1]
        w = tables.shape[1]
        col = jnp.clip(positions // block_size, 0, w - 1)
        blk = jnp.where(valid, tables[bi, col], 0)         # [B, S]
        off = positions % block_size
        new_pools = []
        for bp, (kp, vp) in zip(params["blocks"], pools):
            with _scope("attn"):
                xn = _ln(x, bp["ln1_w"], bp["ln1_b"], eps)
                qkv = _mm(xn, bp, "qkv") + bp["qkv_b"]
                if qkv_heads_major:
                    qkv = jnp.einsum("bsnch->bscnh", qkv.reshape(
                        b, s, n_heads, 3, hd))
                else:
                    qkv = qkv.reshape(b, s, 3, n_heads, hd)
                q = jnp.einsum("bsnh->bnsh", qkv[:, :, 0])  # [B,nh,S,hd]
                kp = kp.at[blk, off].set(qkv[:, :, 1].reshape(b, s, -1))
                vp = vp.at[blk, off].set(qkv[:, :, 2].reshape(b, s, -1))
                kc = _gathered(kp, tables, n_heads, hd)
                vc = _gathered(vp, tables, n_heads, hd)
                att = jnp.einsum("bnqh,bnkh->bnqk", q, kc) * scale
                kpos = jnp.arange(kc.shape[2])
                mask = (kpos[None, None, None, :]
                        <= positions[:, None, :, None])
                att = jnp.where(mask, att, -1e30)
                p = jax.nn.softmax(att.astype(jnp.float32),
                                   axis=-1).astype(x.dtype)
                ctx = jnp.einsum("bnqk,bnkh->bnqh", p, vc)
                ctx = jnp.einsum("bnsh->bsnh", ctx).reshape(b, s, -1)
                proj = _mm(ctx, bp, "proj")
                if tp_reduce is not None:
                    proj = tp_reduce(proj)
                x = x + proj + bp["proj_b"]
            with _scope("mlp"):
                ff = _ln(x, bp["ln2_w"], bp["ln2_b"], eps)
                ff = jax.nn.gelu(_mm(ff, bp, "fc1") + bp["fc1_b"],
                                 approximate=False)
                f2 = _mm(ff, bp, "fc2")
                if tp_reduce is not None:
                    f2 = tp_reduce(f2)
                x = x + f2 + bp["fc2_b"]
            new_pools.append((kp, vp))
        with _scope("lm_head"):
            h = _ln(x, params["lnf_w"], params["lnf_b"], eps)
            logits = h @ params["wte"].T                   # [B, S, V]
            all_tok = jnp.argmax(logits.astype(jnp.float32),
                                 axis=-1).astype(jnp.int32)
            idx = (lens - 1).astype(jnp.int32)
            last = jnp.take_along_axis(
                logits, idx[:, None, None], axis=1)[:, 0]  # [B, V]
            picked = _pick(last, key, temperature, top_k, top_p)
        return tuple(new_pools), all_tok, picked

    return run


def jit_with_donated_pools(fn):
    """The one jit policy for both programs: pools (arg 0) donated so
    cache pages update in place. Per-ENGINE jits (no module-level lru
    cache): `_cache_size()` then counts exactly this engine's
    executables, which is what the RecompileSentinel contract needs."""
    return jax.jit(fn, donate_argnums=(0,))


def jit_tp_with_donated_pools(fn, mesh, params_specs, n_plain: int,
                              n_out: int):
    """The tp twin of jit_with_donated_pools: the program body runs as
    a ``shard_map`` over the mesh's 'tp' axis, then jits with the SAME
    donation policy — pools stay arg 0 and donated, so the per-chip
    page shards update in place and ``_cache_size()`` keeps counting
    this engine's executables.

    Argument contract (all three serving programs share it):
    ``fn(pools, <n_plain host arrays>, params, key)``. Pools shard
    over heads per SERVING_POOL_SPEC; the host arrays (tables /
    positions / token windows) and the key replicate — the host block
    tables are the SAME numpy arrays a tp=1 engine dispatches, which
    is why admission/eviction/COW logic is untouched by tp. Outputs:
    pools first (sharded), then ``n_out - 1`` replicated token arrays
    (identical on every chip by construction — every divergent value
    is all-reduced before it reaches the sampler)."""
    from jax import shard_map
    from ..distributed.sharding import SERVING_POOL_SPEC
    sm = shard_map(
        fn, mesh=mesh,
        in_specs=(SERVING_POOL_SPEC,) + (P(),) * n_plain
        + (params_specs, P()),
        out_specs=(SERVING_POOL_SPEC,) + (P(),) * (n_out - 1),
        check_vma=False)
    return jax.jit(sm, donate_argnums=(0,))
