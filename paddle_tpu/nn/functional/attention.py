"""Attention functionals: SDPA + blockwise (flash) attention.

The reference has no fused attention for training (only the inference-side
multihead_matmul fuse, /root/reference/paddle/fluid/operators/fused/
multihead_matmul_op.cu) — attention is composed per-op in
python/paddle/nn/layer/transformer.py. Here attention is first-class:

- scaled_dot_product_attention: jnp composition; XLA fuses the softmax chain
  into the MXU matmuls on TPU.
- flash_attention: blockwise online-softmax over KV chunks via lax.scan —
  O(seq) memory, long-context ready, and the unit the ring-attention
  context-parallel strategy builds on (paddle_tpu.distributed.ring).
  A Pallas TPU kernel backs the hot path (paddle_tpu.ops.pallas_kernels)
  when running on TPU; this file is the portable reference implementation.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ...ops.registry import register_op

__all__ = ["scaled_dot_product_attention", "flash_attention"]


def _sdpa_impl(q, k, v, attn_mask, dropout_p, is_causal, scale,
               drop_key=None):
    # layouts: [batch, seq, heads, head_dim] (paddle convention)
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    qT = jnp.einsum("bsnh->bnsh", q)
    kT = jnp.einsum("bsnh->bnsh", k)
    vT = jnp.einsum("bsnh->bnsh", v)
    logits = jnp.einsum("bnqh,bnkh->bnqk", qT, kT) * s
    if is_causal:
        ql, kl = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((ql, kl), bool), k=kl - ql)
        logits = jnp.where(mask, logits, -jnp.inf)
    if attn_mask is not None:
        if attn_mask.dtype == jnp.bool_:
            logits = jnp.where(attn_mask, logits, -jnp.inf)
        else:
            logits = logits + attn_mask
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(
        q.dtype)
    if drop_key is not None and dropout_p > 0.0:
        # dropout on the NORMALIZED attention probs — the reference
        # composes softmax -> dropout_op -> matmul in its transformer
        # (python/paddle/nn/layer/transformer.py), so the fused form
        # must drop the same tensor
        keep = jax.random.bernoulli(drop_key, 1.0 - dropout_p,
                                    probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p),
                          0.0).astype(probs.dtype)
    out = jnp.einsum("bnqk,bnkh->bnqh", probs, vT)
    return jnp.einsum("bnsh->bsnh", out)


# NB: the "rng" tag keeps these off the eager jit fast path, matching
# every explicit-key rng op (dropout_nd etc.); the compiled TrainStep
# path is unaffected — dispatch cost there is zero by construction.
@register_op("sdpa_dropout", tags=("rng",))
def _sdpa_dropout(query, key, value, drop_key, attn_mask=None,
                  dropout_p=0.0, is_causal=False, scale=None):
    return _sdpa_impl(query, key, value, attn_mask, dropout_p, is_causal,
                      scale, drop_key=drop_key)


@register_op("scaled_dot_product_attention")
def _sdpa_op(query, key, value, attn_mask=None, dropout_p=0.0,
             is_causal=False, scale=None):
    return _sdpa_impl(query, key, value, attn_mask, dropout_p, is_causal,
                      scale)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, scale=None, name=None):
    """Plain-python dispatcher (ops must stay pure): training-mode
    dropout routes to the rng-tagged op with an explicit key."""
    if dropout_p and training:
        from ...core.generator import next_key
        return _sdpa_dropout(query, key, value, next_key(),
                             attn_mask=attn_mask, dropout_p=dropout_p,
                             is_causal=is_causal, scale=scale)
    return _sdpa_op(query, key, value, attn_mask=attn_mask,
                    dropout_p=dropout_p, is_causal=is_causal,
                    scale=scale)


def _flash_carry_init(b, n, sq, hd):
    """Fresh online-softmax carry (acc, m, l) for blockwise attention."""
    return (jnp.zeros((b, n, sq, hd), jnp.float32),
            jnp.full((b, n, sq), -jnp.inf, jnp.float32),
            jnp.zeros((b, n, sq), jnp.float32))


def _flash_carry_update(q32, k, v, carry, block_k, pos_q, pos_k0, sk,
                        is_causal, dropout=None, kv_lens=None):
    """Consume one KV shard [b, n, s_kv, h] in block_k chunks, updating
    the online-softmax carry (acc, m, l).

    Carry-in/carry-out so multiple shards can be consumed sequentially —
    the unit the ring-attention hop reuses: each hop's remote KV shard
    streams through here, so no s×s logits ever materialize (peak extra
    memory is one [.., sq, block_k] block). `pos_k0` is the shard's
    global key offset, `sk` its true (unpadded) length; `pos_q` carries
    the queries' global positions for causal masking across shards.

    dropout=(key, p) applies flash-style attention-probs dropout: the
    denominator l sums the UNDROPPED probs (dropout zeroes entries of
    the normalized matrix — same contract as the Pallas kernel,
    ops/pallas_kernels.py _fwd_kernel) while acc accumulates
    p·keep/(1-p)·V with a per-block mask from fold_in(key, block).
    The scan body is rematerialized (jax.checkpoint) so the backward
    REGENERATES each block's mask instead of saving O(s²) residuals —
    the pure-JAX form of the flash-dropout trick (varlen batches and
    PD_ATTN_DROPOUT_IMPL=blockwise run it).

    kv_lens [b] int (varlen): per-batch true key length — keys at
    pos_k >= kv_lens[i] are masked for batch row i (right-padded
    batches, the layout io/sampler.py's bucketing produces). Replaces
    the scalar `sk` bound per row; the reference's varlen flash
    (flash_attn_varlen) capability in blockwise form.
    """
    b, n, skl, hd = k.shape
    nblocks = (skl + block_k - 1) // block_k
    pad = nblocks * block_k - skl
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kb = k.reshape(b, n, nblocks, block_k, hd)
    vb = v.reshape(b, n, nblocks, block_k, hd)

    def body(carry, blk):
        acc, m, l = carry
        kj, vj, jidx = blk
        logits = jnp.einsum("bnqh,bnkh->bnqk", q32,
                            kj.astype(jnp.float32))
        pos_k = pos_k0 + jidx * block_k + jnp.arange(block_k)
        valid = pos_k < pos_k0 + sk            # [bk]
        if kv_lens is not None:
            # per-batch right-padding bound: [b, 1, 1, bk]
            valid = (valid[None, :]
                     & (pos_k[None, :] < kv_lens[:, None]))[:, None,
                                                            None, :]
        if is_causal:
            cmask = pos_q[:, None] >= pos_k[None, :]   # [sq, bk]
            if kv_lens is not None:
                valid = valid & cmask[None, None]
            else:
                valid = valid[None, :] & cmask
            logits = jnp.where(valid, logits, -jnp.inf)
        elif kv_lens is not None:
            logits = jnp.where(valid, logits, -jnp.inf)
        else:
            logits = jnp.where(valid[None, :], logits, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        # guard fully-masked rows
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(logits - m_safe[..., None])
        p = jnp.where(jnp.isfinite(logits), p, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * corr + jnp.sum(p, axis=-1)
        if dropout is not None:
            dkey, dp = dropout
            keep = jax.random.bernoulli(
                jax.random.fold_in(dkey, jidx), 1.0 - dp, p.shape)
            p = jnp.where(keep, p / (1.0 - dp), 0.0)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bnqk,bnkh->bnqh", p, vj.astype(jnp.float32))
        return (acc_new, m_new, l_new), None

    if dropout is not None:
        body = jax.checkpoint(body)
    carry, _ = jax.lax.scan(
        body, carry,
        (jnp.moveaxis(kb, 2, 0), jnp.moveaxis(vb, 2, 0),
         jnp.arange(nblocks)))
    return carry


def _flash_finish(carry, dtype):
    acc, _, l = carry
    return (acc / jnp.maximum(l[..., None], 1e-30)).astype(dtype)


def _flash_fwd(q, k, v, is_causal, scale, block_k, dropout=None,
               kv_lens=None):
    """Blockwise attention with online softmax, scanning KV chunks.

    q,k,v: [b, n, s, h] (head-major internally). dropout=(key, p)
    enables the rematerialized flash-dropout path; kv_lens [b] the
    varlen right-padding bound (see _flash_carry_update).
    """
    b, n, sq, hd = q.shape
    sk = k.shape[2]
    q32 = q.astype(jnp.float32) * scale
    carry = _flash_carry_init(b, n, sq, hd)
    carry = _flash_carry_update(q32, k, v, carry, block_k,
                                jnp.arange(sq), 0, sk, is_causal,
                                dropout=dropout, kv_lens=kv_lens)
    return _flash_finish(carry, q.dtype)


def _flash_headmajor(query, key, value, causal, block_size,
                     dropout=None, kv_lens=None):
    """Shared paddle-layout wrapper over _flash_fwd: [b,s,n,h] in/out,
    head-major inside, 1/sqrt(h) scaling, block clamped to sk. Both
    the no-dropout fallback and the blockwise dropout tier route here
    so layout/scaling fixes cannot diverge."""
    q = jnp.einsum("bsnh->bnsh", query)
    k = jnp.einsum("bsnh->bnsh", key)
    v = jnp.einsum("bsnh->bnsh", value)
    scale = 1.0 / math.sqrt(q.shape[-1])
    blk = min(block_size, k.shape[2])
    out = _flash_fwd(q, k, v, causal, scale, blk, dropout=dropout,
                     kv_lens=kv_lens)
    return jnp.einsum("bnsh->bsnh", out)


def _flash_dropout_blockwise(query, key, value, drop_key, causal,
                             dropout_p, block_k=512):
    """Pure-JAX blockwise flash attention WITH dropout: exact
    flash-dropout semantics at O(seq·block) forward memory (backward
    ≤ O(seq²·hd/block) carry residuals, still ~8× under materialized
    probs at hd=64/block=512) without any Mosaic-lowered RNG. What
    PD_ATTN_DROPOUT_IMPL=blockwise selects."""
    return _flash_headmajor(query, key, value, causal, block_k,
                            dropout=(drop_key, float(dropout_p)))


def _flash_kernel(query, key, value, causal, step_mesh, dropout_p=0.0,
                  seed=None):
    """The Pallas kernel, bare — or, inside a GSPMD-sharded step
    (`step_mesh`, a distributed.env.step_mesh), shard_mapped over that
    step's mesh: batch rows over its data axes, heads over 'tp'."""
    from ...distributed.env import TENSOR_AXIS
    from ...ops import pallas_kernels as _pk
    if step_mesh is None or step_mesh.mesh.size == 1:
        return _pk.flash_attention_mha(query, key, value, causal=causal,
                                       dropout_p=dropout_p, seed=seed)
    mesh = step_mesh.mesh
    head_axis = TENSOR_AXIS if TENSOR_AXIS in mesh.axis_names else None
    return _pk.flash_attention_mha_sharded(
        query, key, value, mesh, step_mesh.batch_axes, head_axis,
        causal=causal, dropout_p=dropout_p, seed=seed)


@register_op("flash_attention_op")
def _flash_attention_op(query, key, value, kv_lens=None, causal=False,
                        block_size=512, step_mesh=None):
    """No-dropout flash attention: Pallas kernel on TPU, lax.scan
    online-softmax elsewhere. kv_lens [b] (varlen right-padding) takes
    the blockwise path everywhere — the Pallas kernel's key bound is a
    compile-time scalar. `step_mesh` rides as an attribute (never
    ambient state read here) so the per-op jit caches, which key on
    attributes, cannot hand a mesh-less trace to a sharded step."""
    from ...ops import pallas_kernels as _pk
    if kv_lens is None and _pk.pallas_available():
        return _flash_kernel(query, key, value, causal, step_mesh)
    return _flash_headmajor(query, key, value, causal, block_size,
                            kv_lens=kv_lens)


def attention_dropout_impl() -> str:
    """Which implementation training-mode attention dropout dispatches
    to, from the platform alone: "kernel" (Pallas in-kernel RNG) on a
    TPU, "sdpa" (materialized probs — the CPU reference) elsewhere.
    PD_ATTN_DROPOUT_IMPL names a tier explicitly, "blockwise"
    (pure-JAX flash-dropout) included."""
    import os
    from ...ops import pallas_kernels as _pk
    forced = os.environ.get("PD_ATTN_DROPOUT_IMPL", "").strip().lower()
    if forced:
        if forced not in ("kernel", "blockwise", "sdpa"):
            # reject typos loudly — a silent auto-detect fallback would
            # turn a tier sweep data point into a duplicate measurement
            # (same convention as pallas_kernels._block_env)
            raise ValueError(
                f"PD_ATTN_DROPOUT_IMPL={forced!r}: must be kernel, "
                "blockwise, or sdpa")
        return forced
    return "kernel" if _pk.pallas_available() else "sdpa"


@register_op("flash_attention_dropout", tags=("rng",))
def _flash_attention_dropout_op(query, key, value, drop_key,
                                kv_lens=None, causal=False,
                                dropout_p=0.0, block_size=512,
                                step_mesh=None):
    """Training-mode flash attention with attention-probs dropout.
    Three tiers (attention_dropout_impl): Pallas in-kernel RNG
    (ops/pallas_kernels.py — backward regenerates each block's mask
    from the seed; O(seq·block) memory), pure-JAX blockwise
    flash-dropout (same math, rematerialized masks, no Mosaic RNG),
    or SDPA-with-dropout (exact reference semantics, O(seq²) memory —
    CPU/test sizes only). drop_key is a real PRNG key so static
    replay can refresh it per run like every other rng op."""
    impl = attention_dropout_impl()
    if impl == "kernel" and kv_lens is None:
        seed = jax.random.randint(drop_key, (1,), 0, 2 ** 31 - 1,
                                  dtype=jnp.int32)
        return _flash_kernel(query, key, value, causal, step_mesh,
                             dropout_p=dropout_p, seed=seed)
    if impl in ("kernel", "blockwise"):
        # varlen rides the blockwise tier (per-batch key bound is not
        # in the Mosaic kernel); plain kernel-tier calls never get here
        return _flash_headmajor(query, key, value, causal, block_size,
                                dropout=(drop_key, float(dropout_p)),
                                kv_lens=kv_lens)
    if kv_lens is not None:
        mask = (jnp.arange(key.shape[1])[None, :]
                < kv_lens[:, None])[:, None, None, :]
        return _sdpa_impl(query, key, value, mask, dropout_p, causal,
                          None, drop_key=drop_key)
    return _sdpa_impl(query, key, value, None, dropout_p, causal, None,
                      drop_key=drop_key)


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, block_size=512, training=True,
                    name=None, kv_lens=None):
    """paddle.nn.functional.flash_attention-compatible entry.

    Layout: [batch, seq, num_heads, head_dim]. Memory O(seq·block)
    instead of O(seq²). Training-mode attention dropout runs INSIDE the
    Pallas kernel on TPU (block-seeded mask, regenerated in the
    backward); eval or dropout=0 takes the deterministic kernel.

    kv_lens [b] int32 (TPU-native extension; the reference's
    flash_attn_varlen capability): per-batch true key length for
    right-padded batches — keys at positions >= kv_lens[i] are masked
    while keeping the blockwise O(seq·block) memory form. Right
    padding is exactly what io/sampler.py's bucketing produces, so
    masked batches need not fall back to materialized SDPA.
    """
    # kv_lens rides POSITIONALLY: static capture stores keyword tensors
    # as frozen constants (and rejects keyword Vars), so a traced
    # per-batch length must occupy an input slot
    from ...distributed.env import current_step_mesh
    ctx = current_step_mesh()
    # only a sharded step passes its mesh: everywhere else the ops keep
    # their attribute set (captured programs, cache keys) unchanged
    mesh_kw = {} if ctx is None else {"step_mesh": ctx}
    if dropout and training:
        # return_softmax is an API-parity flag (no path here has ever
        # returned the probs); training-mode dropout must still apply
        from ...core.generator import next_key
        return _flash_attention_dropout_op(query, key, value, next_key(),
                                           kv_lens,
                                           causal=causal,
                                           dropout_p=float(dropout),
                                           block_size=block_size,
                                           **mesh_kw)
    if not return_softmax:
        return _flash_attention_op(query, key, value, kv_lens,
                                   causal=causal,
                                   block_size=block_size, **mesh_kw)
    # return_softmax form: the blockwise reference path (pure jnp),
    # sharing the registered op's implementation
    return _flash_attention_op.__pure_fn__(query, key, value, kv_lens,
                                           causal=causal,
                                           block_size=block_size)
