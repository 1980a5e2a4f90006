"""Pallas TPU kernels for the hot ops.

The reference implements its hot paths as hand-written CUDA
(/root/reference/paddle/fluid/operators/fused/multihead_matmul_op.cu,
fused_elemwise_activation, the cuDNN bindings). The TPU-native equivalent is
a small set of Pallas/Mosaic kernels that own the MXU/VMEM schedule where XLA
fusion is not enough. This module provides flash attention (forward +
backward) as blocked online-softmax kernels:

- forward: grid (batch*heads, q_blocks, k_blocks); q/k/v tiles staged in
  VMEM, accumulator + running (m, l) stats in VMEM scratch that persists
  across the sequential k-block grid dimension; emits O and the per-row
  logsumexp needed by the backward.
- backward: the standard two-kernel split — a dq kernel iterating k-blocks
  innermost, and a dk/dv kernel iterating q-blocks innermost — each
  recomputing P = exp(QK^T·scale − lse) on the fly (no O(s²) residuals).

Everything is O(seq·block) memory, causal blocks above the diagonal are
skipped, and inputs are padded to MXU-friendly (128, 128) tiles. The
portable lax.scan reference lives in paddle_tpu.nn.functional.attention;
correctness of this kernel is tested against it (interpret mode on CPU,
compiled on TPU).
"""
from __future__ import annotations

import functools
import os
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_mha", "flash_attention_mha_sharded",
           "flash_prefill_attention", "paged_decode_attention",
           "paged_decode_work", "retention_decode", "expert_gmm",
           "pallas_available"]

# Max block sizes along the q/k sequence dims. Large blocks amortize the
# per-grid-step overhead (DMA setup + Mosaic loop) — with head_dim 64 a
# 128x128 block is only ~4 MFLOP, far too little to hide ~1us/step; 512-wide
# blocks put ~134 MFLOP per step while staying well under VMEM (~1.5 MB).
# Env-tunable (PD_FLASH_BQ / PD_FLASH_BK) so a hardware session can sweep
# per-generation VMEM sweet spots without code edits. Values must be
# 128-multiples (>= 128): _pick_block would otherwise silently round,
# turning a sweep data point into a duplicate measurement.


def _block_env(name: str, default: int) -> int:
    v = int(os.environ.get(name, default))
    if v < 128 or v % 128:
        raise ValueError(
            f"{name}={v} invalid: flash block sizes must be multiples "
            "of 128 (MXU tile), >= 128")
    return v


_BQ = _block_env("PD_FLASH_BQ", 512)
_BK = _block_env("PD_FLASH_BK", 512)
_NEG = -1e30


def pallas_available() -> bool:
    """True when the default backend is a TPU: there the Mosaic kernels
    are what runs, and a compiler refusal surfaces as its own error."""
    return jax.devices()[0].platform == "tpu"


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _pick_block(s: int, bmax: int) -> tuple:
    """Pad s to 128-row tiles and pick the largest block ≤ bmax that divides
    the padded length — so padding waste is bounded by one 128 tile, never
    a full 512 block (sq=520 pads to 640 with bq=128, not to 1024)."""
    s_p = _ceil_to(s, 128)
    nb = s_p // 128
    for kt in range(min(bmax // 128, nb), 0, -1):
        if nb % kt == 0:
            return s_p, 128 * kt
    return s_p, 128


def _dot(a, b, a_dim, b_dim):
    """MXU matmul contracting a[a_dim] with b[b_dim], f32 accumulate.
    bf16 tiles pin DEFAULT precision: under a process-wide
    jax_default_matmul_precision=highest the traced dot would ask
    Mosaic for an fp32 contraction of bf16 operands, which it refuses
    ("Bad lhs type"); f32 tiles follow the process setting."""
    precision = jax.lax.Precision.DEFAULT if a.dtype == jnp.bfloat16 \
        else None
    return jax.lax.dot_general(
        a, b, (((a_dim,), (b_dim,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32)


def _masked_probs(q, k, lse_row, i, j, *, scale, causal, bq, bk, sk):
    """Shared logits→probabilities block for the backward kernels:
    P = exp(QK^T·scale − lse) with key-padding and causal masks. The forward
    kernel computes its own online-softmax variant of the same masking —
    keep the mask logic here and there in sync."""
    s = _dot(q, k, 1, 1) * scale
    col = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = col < sk
    if causal:
        row = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        mask = mask & (row >= col)
    s = jnp.where(mask, s, _NEG)
    p = jnp.exp(s - lse_row[:, None])
    return jnp.where(mask, p, 0.0)


# ---------------------------------------------------------------- forward

def _global_row(bh, first_row, n_local, n_total):
    """Shard-local batch·heads row -> its row in the unsharded
    [batch, heads] grid (rows are batch-major; the shard holds n_local
    of n_total heads and starts at global row `first_row`)."""
    return first_row + bh + (bh // n_local) * (n_total - n_local)


def _drop_mask(seed_ref, bh, i, j, nq, nk, bq, bk, dropout_p, heads):
    """Deterministic per-(batch·head, q-block, k-block) keep mask: the
    backward kernels REGENERATE the forward's mask from the same seed
    tuple instead of storing an O(s²) mask (the flash-dropout trick).

    Mosaic on real TPU rejects prng_seed with >2 values ("Setting seed
    with more than 2 values is not supported", v5e libtpu 0.0.34), so
    the (bh, i, j) block coordinate folds into ONE collision-free
    linear index (nq/nk are static grid bounds) and we seed with
    exactly (user_seed, block_index).

    `heads` = (n_local, n_total) when the kernel runs on one shard of a
    mesh-sharded [batch, heads] grid (flash_attention_mha_sharded):
    `bh` is then shard-local, and the mask is keyed on the GLOBAL
    batch·heads row — seed_ref[1] carries the shard's first global row
    — so every shard drops its own links and the sharded step drops
    exactly the links the unsharded one does."""
    if heads is not None:
        bh = _global_row(bh, seed_ref[1], *heads)
    block_index = (bh * nq + i) * nk + j
    pltpu.prng_seed(seed_ref[0], block_index)
    bits = pltpu.bitcast(pltpu.prng_random_bits((bq, bk)), jnp.uint32)
    threshold = jnp.uint32(min(int(dropout_p * 4294967296.0),
                               4294967295))
    return bits >= threshold  # keep with prob 1 - p


def _online_softmax(s, mask, m_prev, l_prev):
    """One key block of the online softmax, the arithmetic every
    forward kernel here shares: scores `s` [bq, bk] in f32 with their
    `mask` (None: every link is live), the rows' running max and sum
    so far -> the block's unnormalised probabilities p (f32, 0 where
    masked), the new max and sum, and `corr`, what the accumulator
    built under the old max is worth under the new."""
    if mask is not None:
        s = jnp.where(mask, s, _NEG)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    return p, m_new, l_prev * corr + jnp.sum(p, axis=-1), corr


def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref,
                *, scale, causal, bq, bk, nq, nk, sk, dropout_p, heads):
    bh = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)

    # causal: skip blocks strictly above the diagonal
    run = True
    if causal:
        run = j * bk <= (i + 1) * bq - 1

    @pl.when(run)
    def _():
        q = q_ref[0]
        k = k_ref[0]
        s = _dot(q, k, 1, 1) * scale
        col = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = col < sk
        if causal:
            row = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            mask = mask & (row >= col)
        # the softmax denominator sums over ALL links (dropout zeroes
        # entries of the NORMALIZED probs), so l uses the undropped p
        p, m_new, l_new, corr = _online_softmax(
            s, mask, m_ref[:, 0], l_ref[:, 0])
        if dropout_p > 0.0:
            keep = _drop_mask(seed_ref, bh, i, j, nq, nk, bq, bk, dropout_p,
                              heads)
            p = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
        pv = _dot(p.astype(v_ref.dtype), v_ref[0], 1, 0)
        acc_ref[:] = acc_ref[:] * corr[:, None] + pv
        m_ref[:] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(j == nk - 1)
    def _():
        l = l_ref[:, 0]
        l_safe = jnp.maximum(l, 1e-30)
        o_ref[0] = (acc_ref[:] / l_safe[:, None]).astype(o_ref.dtype)
        # lse stored sublane-replicated (8, bq) to satisfy TPU tiling
        lse = m_ref[:, 0] + jnp.log(l_safe)
        lse_ref[0] = jnp.broadcast_to(lse[None, :], lse_ref.shape[1:])


def _kernel_name(base, dropout_p):
    """Stable kernel names: what a profiler trace and the lowered step
    show, so a reader can tell which kernels ran and whether the
    in-kernel dropout was compiled in."""
    return base + ("_dropout" if dropout_p > 0.0 else "")


def _flash_fwd_pallas(q, k, v, causal, scale, interpret, dropout_p=0.0,
                      seed=None, heads=None):
    """q,k,v: [bh, s, h] padded to (128,128) tiles. Returns (o, lse)."""
    bh, sq, h = q.shape
    sk = k.shape[1]
    sq_p, bq = _pick_block(sq, _BQ)
    sk_p, bk = _pick_block(sk, _BK)
    h_p = h
    q = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, 0)))
    k = jnp.pad(k, ((0, 0), (0, sk_p - sk), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, sk_p - sk), (0, 0)))
    nq, nk = sq_p // bq, sk_p // bk
    seed_arr = jnp.asarray(
        [0 if seed is None else seed], jnp.int32).reshape(-1)

    kern = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        bq=bq, bk=bk, nq=nq, nk=nk, sk=sk, dropout_p=float(dropout_p),
        heads=heads)
    o, lse = pl.pallas_call(
        kern,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bq, h_p), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, h_p), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, h_p), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, h_p), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 8, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq_p, h_p), q.dtype),
            jax.ShapeDtypeStruct((bh, 8, sq_p), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, h_p), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        interpret=interpret,
        name=_kernel_name("flash_fwd", dropout_p),
    )(seed_arr, q, k, v)
    return o[:, :sq, :h], lse[:, 0, :sq]


# --------------------------------------------------------------- backward

def _dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, acc_ref, *, scale, causal, bq, bk, nq, nk, sk,
               dropout_p, heads):
    bh = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    run = True
    if causal:
        run = j * bk <= (i + 1) * bq - 1

    @pl.when(run)
    def _():
        k = k_ref[0]
        p = _masked_probs(q_ref[0], k, lse_ref[0, 0], i, j, scale=scale,
                          causal=causal, bq=bq, bk=bk, sk=sk)
        dp = _dot(do_ref[0], v_ref[0], 1, 1)
        if dropout_p > 0.0:
            keep = _drop_mask(seed_ref, bh, i, j, nq, nk, bq, bk, dropout_p,
                              heads)
            dp = jnp.where(keep, dp / (1.0 - dropout_p), 0.0)
        ds = p * (dp - delta_ref[0, 0][:, None])
        acc_ref[:] += _dot(ds.astype(k.dtype), k, 1, 0) * scale

    @pl.when(j == nk - 1)
    def _():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _dkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc,
                *, scale, causal, bq, bk, nq, nk, sk, dropout_p, heads):
    bh = pl.program_id(0)
    j = pl.program_id(1)  # k block
    i = pl.program_id(2)  # q block (innermost)

    @pl.when(i == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = True
    if causal:
        run = (i + 1) * bq - 1 >= j * bk

    @pl.when(run)
    def _():
        q = q_ref[0]
        do = do_ref[0]
        p = _masked_probs(q, k_ref[0], lse_ref[0, 0], i, j, scale=scale,
                          causal=causal, bq=bq, bk=bk, sk=sk)
        if dropout_p > 0.0:
            # same seed tuple (bh, q-block i, k-block j) as the forward
            keep = _drop_mask(seed_ref, bh, i, j, nq, nk, bq, bk, dropout_p,
                              heads)
            pd = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
        else:
            pd = p
        # dv += (dropout(P))^T @ dO
        pt = pd.astype(do.dtype)
        dv_acc[:] += _dot(pt, do, 0, 0)
        dp = _dot(do, v_ref[0], 1, 1)
        if dropout_p > 0.0:
            dp = jnp.where(keep, dp / (1.0 - dropout_p), 0.0)
        ds = p * (dp - delta_ref[0, 0][:, None])
        # dk += dS^T @ Q * scale
        dk_acc[:] += _dot(ds.astype(q.dtype), q, 0, 0) * scale

    @pl.when(i == nq - 1)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_pallas(q, k, v, o, lse, do, causal, scale, interpret,
                      dropout_p=0.0, seed=None, heads=None):
    bh, sq, h = q.shape
    sk = k.shape[1]
    sq_p, bq = _pick_block(sq, _BQ)
    sk_p, bk = _pick_block(sk, _BK)
    h_p = h
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    qp = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, sk_p - sk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, sk_p - sk), (0, 0)))
    dop = jnp.pad(do, ((0, 0), (0, sq_p - sq), (0, 0)))
    # padded q rows: lse=0 → p=exp(-1e30)≈0 under mask anyway; keep 0.
    # lse/delta carried sublane-replicated (bh, 8, sq) for TPU tiling.
    lsep = jnp.broadcast_to(
        jnp.pad(lse, ((0, 0), (0, sq_p - sq)))[:, None, :], (bh, 8, sq_p))
    deltap = jnp.broadcast_to(
        jnp.pad(delta, ((0, 0), (0, sq_p - sq)))[:, None, :], (bh, 8, sq_p))
    nq, nk = sq_p // bq, sk_p // bk
    seed_arr = (jnp.zeros((1,), jnp.int32) if seed is None
                else jnp.asarray(seed, jnp.int32).reshape(-1))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nq=nq, nk=nk, sk=sk,
                          dropout_p=float(dropout_p), heads=heads),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bq, h_p), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, h_p), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, h_p), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bq, h_p), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 8, bq), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, 8, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, bq, h_p), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq_p, h_p), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, h_p), jnp.float32)],
        interpret=interpret,
        name=_kernel_name("flash_bwd_dq", dropout_p),
    )(seed_arr, qp, kp, vp, dop, lsep, deltap)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nq=nq, nk=nk, sk=sk,
                          dropout_p=float(dropout_p), heads=heads),
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bq, h_p), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bk, h_p), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, h_p), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bq, h_p), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, 8, bq), lambda b, j, i: (b, 0, i)),
            pl.BlockSpec((1, 8, bq), lambda b, j, i: (b, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, h_p), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, h_p), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk_p, h_p), k.dtype),
            jax.ShapeDtypeStruct((bh, sk_p, h_p), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, h_p), jnp.float32),
            pltpu.VMEM((bk, h_p), jnp.float32),
        ],
        interpret=interpret,
        name=_kernel_name("flash_bwd_dkv", dropout_p),
    )(seed_arr, qp, kp, vp, dop, lsep, deltap)

    return dq[:, :sq, :h], dk[:, :sk, :h], dv[:, :sk, :h]


# ------------------------------------------------------------- public API

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_mha(q, k, v, seed, causal, scale, interpret, dropout_p, heads):
    o, _ = _flash_fwd_pallas(q, k, v, causal, scale, interpret,
                             dropout_p=dropout_p, seed=seed, heads=heads)
    return o


def _flash_mha_fwd(q, k, v, seed, causal, scale, interpret, dropout_p,
                   heads):
    o, lse = _flash_fwd_pallas(q, k, v, causal, scale, interpret,
                               dropout_p=dropout_p, seed=seed, heads=heads)
    return o, (q, k, v, seed, o, lse)


def _flash_mha_bwd(causal, scale, interpret, dropout_p, heads, res, do):
    q, k, v, seed, o, lse = res
    dq, dk, dv = _flash_bwd_pallas(q, k, v, o, lse, do, causal, scale,
                                   interpret, dropout_p=dropout_p,
                                   seed=seed, heads=heads)
    import numpy as np
    dseed = np.zeros(np.shape(seed), jax.dtypes.float0)
    return dq, dk, dv, dseed


_flash_mha.defvjp(_flash_mha_fwd, _flash_mha_bwd)


def _seed_array(seed):
    return (jnp.zeros((1,), jnp.int32) if seed is None
            else jnp.asarray(seed, jnp.int32).reshape(1))


def flash_attention_mha(query, key, value, causal=False, scale=None,
                        interpret=False, dropout_p=0.0, seed=None,
                        shard_rows=None):
    """Flash attention over [batch, seq, num_heads, head_dim] inputs.

    Pallas TPU kernel (Mosaic) with custom VJP; O(seq·block) memory.
    dropout_p applies attention-probs dropout INSIDE the kernel (the
    backward regenerates each block's keep-mask from (seed, block)
    instead of storing it); `seed` is a traced int32 scalar — vary it
    per training step. `interpret=True` runs the same kernels under the
    Pallas interpreter (used by the CPU test suite).

    `shard_rows` = (first_row, total_heads) is set by
    flash_attention_mha_sharded only: the inputs are one shard of a
    larger [batch, heads] grid whose first global batch·heads row is
    the traced int32 `first_row`, so dropout masks key on global rows.
    """
    b, sq, n, h = query.shape
    sk = key.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(h)
    # pad head_dim up to a full 128-lane tile for Mosaic; zero columns are
    # exact no-ops for QK^T, PV, and all three gradients, sliced off below
    h_p = _ceil_to(h, 128)
    q = jnp.einsum("bsnh->bnsh", query).reshape(b * n, sq, h)
    k = jnp.einsum("bsnh->bnsh", key).reshape(b * n, sk, h)
    v = jnp.einsum("bsnh->bnsh", value).reshape(b * n, sk, h)
    if h_p != h:
        pad = ((0, 0), (0, 0), (0, h_p - h))
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
    seed_arr = _seed_array(seed)
    heads = None
    if shard_rows is not None and dropout_p > 0.0:
        first_row, total_heads = shard_rows
        seed_arr = jnp.concatenate(
            [seed_arr, jnp.asarray(first_row, jnp.int32).reshape(1)])
        heads = (n, int(total_heads))
    o = _flash_mha(q, k, v, seed_arr, bool(causal), float(scale),
                   bool(interpret), float(dropout_p), heads)
    return jnp.einsum("bnsh->bsnh", o.reshape(b, n, sq, h_p)[..., :h])


def flash_attention_mha_sharded(query, key, value, mesh, batch_axes,
                                head_axis, causal=False, dropout_p=0.0,
                                seed=None, interpret=False):
    """flash_attention_mha inside a jit whose arrays are GSPMD-sharded
    over `mesh`: Mosaic kernels cannot be partitioned automatically, so
    the call is shard_mapped — each chip attends over its own batch
    rows (`batch_axes`, the plan's data axes) and its own heads
    (`head_axis`, None when the mesh has no tensor axis). Attention
    never mixes batch rows or heads, so there is no collective inside;
    mesh axes named by neither see replicated inputs."""
    from jax.sharding import PartitionSpec as P
    batch_axes = tuple(batch_axes)
    b, _, n, _ = query.shape
    b_local = b // math.prod(mesh.shape[a] for a in batch_axes)
    n_local = n // (mesh.shape[head_axis] if head_axis else 1)
    spec = P(batch_axes or None, None, head_axis, None)

    def body(q, k, v, sd):
        first_row = jnp.zeros((), jnp.int32)
        if batch_axes:
            first_row += jax.lax.axis_index(batch_axes) * (b_local * n)
        if head_axis:
            first_row += jax.lax.axis_index(head_axis) * n_local
        return flash_attention_mha(
            q, k, v, causal=causal, interpret=interpret,
            dropout_p=dropout_p, seed=sd, shard_rows=(first_row, n))

    return jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec, P()),
        out_specs=spec, check_vma=False)(query, key, value,
                                         _seed_array(seed))


# ------------------------------------------------------ flash prefill

# The serving prefill's shapes are the bucket ladder's and nothing else
# tunes them (not the training kernels' PD_FLASH_BQ / PD_FLASH_BK):
# the largest query / key block; the most rows of scores a grid step
# holds over the heads that share its lanes; and the rows of them
# attended at a time (the chunk after feeds the MXU while this one's
# softmax runs). The chunks are unrolled, and a kernel's unrolled size
# is set-up time in every process, compile cache or not: five lane
# blocks a step were 9% faster a call and 22 s of set-up (PERF.md,
# PR 31).
_PREFILL_BLOCK = 512
_PREFILL_ROWS = 1024
_PREFILL_CHUNK = 128


def _segment_start(starts, row):
    """Where the prompt that holds `row` (a scalar, an array of rows)
    starts: the last of a packed row's ascending prompt starts at or
    before it (`starts` a list of scalars or an array)."""
    start = starts[0]
    for p in range(1, len(starts)):
        start = jnp.where(row >= starts[p], starts[p], start)
    return start


def _prefill_kernel(*refs, scale, bq, nk, hd, n_seg, window=None):
    """One grid step is one (query block, key block) of one row for
    the `g` heads that share a block of lanes: a row of q, k, v and o is
    a token with its heads side by side (the serving layout, read and
    written in place), and a step's window is [bq, g * hd] lanes of it
    (a pair of 64-wide heads). The MXU contracts over all the window's
    lanes whatever the head size, so the heads are stacked along the
    rows: g copies of the query block, copy h zeroed outside head h's
    lanes, are [g * bq, lanes]; their product with the key block over
    the lanes is every head's scores (the other heads' lanes meet
    zeros), and p times v weighs every lane, of which copy h's result
    is wanted in head h's lanes only: picked once, when the query block
    is done. The stacked rows go through in chunks; a chunk's update
    is `_fwd_kernel`'s (`_online_softmax`), with the scale folded into
    q (exact where it is a power of two, as 1/8 is for a head of 64;
    one more rounding of q elsewhere).

    The grid's last axis runs over a band of `nk` key blocks, i - nk + 1
    .. i, those before block 0 skipped. `lens` decides what is skipped,
    never what is masked: a step runs iff its query block starts before
    the row's end. A row is one prompt, or (`n_seg` > 0) one packed row
    of `n_seg` prompts laid end to end, whose query sees the keys of its
    own prompt only: there `blocks` holds, per query block, the first
    key block it reads (those before it end before the block's first
    prompt starts, and are skipped as wholly masked) and the first key
    block that no prompt of the query block starts after (a key block
    before it holds keys of a prompt before some query's own). Two
    bodies: the masked one runs on the diagonal and, in a packed row,
    on the key blocks before that bound; every other step takes all its
    keys unmasked. On the diagonal of a one-prompt row a chunk takes
    the keys up to its own last row and no further, the chunks
    unrolled (`step`). In a packed row every chunk takes the whole key
    block, a link live iff its key lies between the start of the
    query's prompt and the query, one more comparison than the causal
    one; the chunks then go through a loop (`packed_step`), as a body
    unrolled over whole blocks holds half again the code, which every
    process loads at every layer. The accumulator, zeroed at a query
    block's first step, over a floored sum at its last makes a block
    that never ran exactly 0.

    With a `window` a link is live iff 0 <= t - s < window besides (the
    edge blocks, and a diagonal block narrower than the window,
    masked)."""
    if n_seg:
        (lens_ref, starts_ref, blocks_ref, q_ref, k_ref, v_ref, o_ref,
         acc_ref, m_ref, l_ref, qs_ref) = refs
    else:
        lens_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
    a, i, step_of_i = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    j = i - (nk - 1) + step_of_i
    lb = q_ref.shape[-1]
    g = lb // hd
    chunk = min(_PREFILL_CHUNK, bq)
    head_of_lane = jax.lax.broadcasted_iota(jnp.int32, (1, lb), 1) // hd

    @pl.when(step_of_i == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)

    def update(rows, s, mask, v):
        """One chunk of stacked query rows through the online softmax
        against the key block's scores `s`."""
        p, m_new, l_new, corr = _online_softmax(
            s, mask, m_ref[rows, 0], l_ref[rows, 0])
        acc_ref[rows] = acc_ref[rows] * corr[:, None] + _dot(
            p.astype(v.dtype), v, 1, 0)
        m_ref[rows] = jnp.broadcast_to(m_new[:, None], (chunk, 128))
        l_ref[rows] = jnp.broadcast_to(l_new[:, None], (chunk, 128))

    def band(offset, shape):
        """Links within the window, at a chunk's row `offset` into the
        query block."""
        return ((i - j) * bq + offset
                + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
                - jax.lax.broadcasted_iota(jnp.int32, shape, 1) < window)

    def stacked_queries():
        q = q_ref[0].astype(jnp.float32) * scale
        return jnp.concatenate(
            [jnp.where(head_of_lane == h, q, 0.0) for h in range(g)],
            axis=0).astype(k_ref.dtype)                    # [g * bq, lb]

    def step(masked):
        """A one-prompt row: the chunks unrolled, a diagonal chunk
        taking the keys up to its own last row."""
        k, v = k_ref[0], v_ref[0]
        qs = stacked_queries()
        for r in range(0, g * bq, chunk):
            rows = slice(r, r + chunk)
            # on the diagonal i * bq == j * bk: the offsets cancel
            keys = r % bq + chunk if masked else bq
            s = _dot(qs[rows], k[:keys], 1, 1)             # [chunk, keys]
            mask = None
            if masked:
                mask = (r % bq + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 0)
                    >= jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
            if window is not None and (window < bq or not masked):
                mask = band(r % bq, s.shape) if mask is None \
                    else mask & band(r % bq, s.shape)
            update(rows, s, mask, v[:keys])

    def packed_step(masked):
        """A packed row: every chunk takes the whole key block, so a
        loop of one body goes down the query block, over the stacked
        queries in a scratch, two chunks an iteration (every head's
        copy of a chunk of rows, with one mask for the copies, or two
        chunks of rows of one head) so that one chunk's matmul can
        overlap the other's softmax. Unrolled over whole key blocks the
        body would be set-up in every process: a prompt's diagonal no
        longer stops at a chunk's last row."""
        k, v = k_ref[0], v_ref[0]
        qs_ref[:] = stacked_queries()
        bounds = [starts_ref[p] for p in range(n_seg)]
        per = min(max(1, 2 // g), bq // chunk)

        def rows_at(c, carry):
            for u in range(per):
                offset = pl.multiple_of((c * per + u) * chunk, chunk)
                shape = (chunk, bq)
                mask = None
                if masked:
                    # a query row sees the keys from its prompt's start
                    # to itself: the first and last live column
                    at = offset + jax.lax.broadcasted_iota(
                        jnp.int32, (chunk, 1), 0)
                    last = (i - j) * bq + at
                    first = _segment_start(bounds, i * bq + at) - j * bq
                    if window is not None:
                        first = jnp.maximum(first, last - (window - 1))
                    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
                    mask = (col >= first) & (col <= last)
                elif window is not None:
                    mask = band(offset, shape)
                for h in range(g):
                    rows = pl.ds(h * bq + offset, chunk)
                    update(rows, _dot(qs_ref[rows], k, 1, 1), mask, v)
            return carry

        jax.lax.fori_loop(0, bq // chunk // per, rows_at, 0)

    live = i * bq < lens_ref[a]
    if n_seg:
        run = live & (j >= blocks_ref[i])
        masked = (j == i) | (j < blocks_ref[pl.num_programs(2) + i])
    else:
        run = live & (j >= 0)
        masked = j == i
    body = packed_step if n_seg else step
    pl.when(run & masked)(functools.partial(body, True))
    if nk > 1:
        pl.when(run & jnp.logical_not(masked))(
            functools.partial(body, False))

    @pl.when(step_of_i == nk - 1)
    def _():
        o = acc_ref[:] / jnp.maximum(l_ref[:, :1], 1e-30)
        out = o[:bq]
        for h in range(1, g):
            out = jnp.where(head_of_lane == h, o[h * bq:(h + 1) * bq], out)
        o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "window",
                                             "interpret", "longest"))
def flash_prefill_attention(q, k, v, lengths, scale, interpret=False,
                            window=None, starts=None, longest=None):
    """Causal attention of right-padded rows over their own K/V, the
    scores never leaving the chip.

    q [A, S, nh, hd], k, v [A, S, n_kv, hd] (n_kv dividing nh: query
    head i reads key-value head i // (nh / n_kv), which needs whole
    128-lane heads where n_kv < nh); lengths [A] int32, each row's true
    length. A row is one prompt; or, with `starts` [n] int32 (A = 1),
    the one row packs n prompts end to end, prompt p taking rows
    starts[p] .. starts[p + 1] - 1 (the last up to the length), the
    starts ascending and starts[0] = 0, and query t of a prompt sees key
    s iff s is of the same prompt and s <= t. `longest` (static; None:
    S) is the most rows a prompt takes: key blocks further back from a
    query block get no grid step, nor do those that end before the
    query block's first prompt starts or lie past the length. With a
    `window`, query t sees key s iff 0 <= t - s < window besides, and
    key blocks wholly outside that band get no grid step.
    Returns [A, S, nh, hd]
    in q's dtype: query t < lengths[a] of row a attended its keys,
    exactly; a query past the length holds something finite that
    nothing may read (0 where its whole block lies past the length).
    Scores, the running max and sum and the accumulator are f32, the
    probabilities rounded to v's dtype for their matmul: what
    `decoder.masked_attention` does under `causal_mask(S, lengths)` (a
    packed row: under `decoder.packed_mask`), less its rounding of the
    scores to the storage dtype.

    Rows are read as [A, S, nh * hd], heads side by side, in blocks of
    lcm(hd, 128) lanes (two heads of 64), or the whole row where that
    does not divide it; S is padded to whole 128-row tiles, which no
    bucket of 128 or more needs. Jitted so that a program calling it
    once a layer traces and lowers it once."""
    a, s, nh, hd = q.shape
    d = nh * hd
    group = nh // k.shape[2]
    lb = math.lcm(hd, 128)
    if d % lb:
        lb = d
    g = lb // hd
    if group > 1 and g != 1:
        raise ValueError(
            f"flash_prefill_attention: {nh} query heads over "
            f"{k.shape[2]} key-value heads of {hd} lanes: a grouped "
            "read takes whole heads of a multiple of 128 lanes")
    if starts is not None and a != 1:
        raise ValueError(
            f"flash_prefill_attention: `starts` packs one row, not {a}")
    s_p, bq = _pick_block(s, max(128, min(_PREFILL_BLOCK,
                                          _PREFILL_ROWS // g // 128 * 128)))
    nq = s_p // bq
    # key blocks a query block reads: all up to the diagonal, under a
    # window the band's, ceil((window - 1) / bq) below the diagonal,
    # and no further back than a prompt reaches
    nk = nq
    for reach in (window, longest):
        if reach is not None:
            nk = min(nk, -(-(reach - 1) // bq) + 1)
    lengths = lengths.astype(jnp.int32)
    prefetch = (lengths,)
    n_seg = 0
    if starts is not None:
        starts = starts.astype(jnp.int32)
        n_seg = starts.shape[0]
        # per query block: the key block of its first row's prompt
        # start, and one past the last key block that a prompt starting
        # inside the block, or in a key block's later rows, cuts
        top = jnp.arange(nq, dtype=jnp.int32) * bq
        bottom = jnp.minimum(top + bq, lengths[0]) - 1
        prefetch += (starts, jnp.concatenate(
            [_segment_start(starts, top) // bq,
             -(-_segment_start(starts, bottom) // bq)]))

    def rows(x):
        x = x.reshape(a, s, x.shape[2] * hd)
        return jnp.pad(x, ((0, 0), (0, s_p - s), (0, 0))) if s_p != s else x

    def kv_block(r, c, i, j, lens, *packed):
        """The band's block j, clipped to the blocks the query block
        reads: where a step is skipped its key windows stay where they
        are and nothing is fetched for it."""
        first = packed[1][i] if packed else 0
        return (r, jnp.clip(i - (nk - 1) + j, first,
                            jnp.where(i * bq < lens[r], i, 0)),
                c if group == 1 else c // group)

    q_spec = pl.BlockSpec((1, bq, lb), lambda r, c, i, j, *_: (r, i, c))
    kv_spec = pl.BlockSpec((1, bq, lb), kv_block)
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, scale=float(scale), bq=bq,
                          nk=nk, hd=hd, n_seg=n_seg, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(a, d // lb, nq, nk),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((g * bq, lb), jnp.float32),
                pltpu.VMEM((g * bq, 128), jnp.float32),
                pltpu.VMEM((g * bq, 128), jnp.float32),
            ] + ([pltpu.VMEM((g * bq, lb), k.dtype)] if n_seg else [])),
        out_shape=jax.ShapeDtypeStruct((a, s_p, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_prefill_attention",
    )(*prefetch, rows(q), rows(k), rows(v))
    return out[:, :s].reshape(a, s, nh, hd)


# ------------------------------------------------------- paged decode

# K/V pages a grid step: each is one operand window of its pool, so a
# step's pages are fetched by the pipeline's own page-sized copies
# while the step before is attended. A slot's last step is padded to
# this many windows; an inactive lane has no step. On the v5e a step
# costs about 0.8 us whatever it holds (two small matmuls and the
# accumulator's update) and a window about 0.02 us, so more pages a
# step means fewer steps for long slots and a dearer last step: 4, 8
# and 16 read 303, 175 and 123 us a call with every table full; 8
# reads 2.2, 2.8, 7.4, 28.8 and 49.6 us with 0, 1, 4, 18 and 32 of 32
# lanes live at 200 tokens (PERF.md).
_PAGES_PER_STEP = 8


def _dot_f32(a, b, dims, exact=False):
    """f32 `a` times `b` on the MXU, accumulated in f32, without
    rounding `a` to b's precision: f32 pages take the f32 matmul
    (`HIGHEST`); 16-bit pages take `a` as two 16-bit terms, high and
    remainder, stacked into one matmul (one pass over `b`), or as one
    term where the caller knows `a` to be `exact` in b's dtype."""
    dims = (dims, ((), ()))
    if b.dtype == jnp.float32:
        return jax.lax.dot_general(
            a, b, dims, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
    hi = a.astype(b.dtype)
    lhs = hi if exact else jnp.concatenate(
        [hi, (a - hi.astype(jnp.float32)).astype(b.dtype)], axis=0)
    out = jax.lax.dot_general(lhs, b, dims,
                              precision=jax.lax.Precision.DEFAULT,
                              preferred_element_type=jnp.float32)
    n = a.shape[0]
    return out if exact else out[:n] + out[n:]


def _paged_decode_kernel(slot_ref, round_ref, fetch_ref, lengths_ref,
                         q_ref, sel_ref, *refs, scale, bs, pps, q_exact,
                         hd, group=1, window=None):
    """One grid step is one round of one slot: table entries
    round*pps .. round*pps+pps-1 in the K and V windows. A page is
    [bs, nh*hd], one row a token with the heads side by side in the
    lanes, so a step's pages stack into [T, nh*hd] (T = pps*bs) with
    full tiles, and the per-head sums inside a row are matmuls on the
    otherwise idle MXU. `sel` [nhp, nh*hd] holds a 1 where lane d
    belongs to head h (heads padded to nhp rows): sel * q puts each
    head's query in its own row, its product with K over the lanes is
    the scores [nhp, T] (heads in sublanes, tokens in lanes), and
    p [nhp, T] times V is every head's weighting of every lane,
    [nhp, nh*hd], of which row h is wanted in head h's lanes only: the
    accumulator keeps that form over a slot's rounds and `sel` picks
    the diagonal once, at the slot's end. Scores, the running max and
    sum, and the accumulator are f32 (`_dot_f32`; `q_exact`: q came in
    the pages' dtype, so sel * q is exact in it). A window past the
    length still holds an older page (no copy was made for it): its
    positions are masked, so it weighs exactly 0. q and the output are
    whole in VMEM, f32 so that one row can be read and written at a
    dynamic sublane. The output is zeroed at the first step: the row of
    an inactive lane (length 0, no round of its own) stays 0, and the
    one step of a call with no live lane writes nothing.

    Where a page holds fewer key-value heads than there are query heads
    (`group` query heads a K/V head, whole heads of a multiple of 128
    lanes), `sel` [nhp, D] puts query head h on its K/V head h // group
    and q is [B, nhp, hd]: tiled across the K/V heads' lanes, sel keeps
    each head's own; the output is [B, nhp, hd], the picked lanes
    summed over the K/V heads. With a `window`, a slot's rounds start
    at the page of its position length - window, and positions under
    it are masked."""
    del fetch_ref                               # the index maps read it
    k_refs, v_refs = refs[:pps], refs[pps:2 * pps]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * pps:]
    t = pl.program_id(0)
    slot, rnd = slot_ref[t], round_ref[t]
    length = lengths_ref[slot]
    sel = sel_ref[...]                                         # [nhp, D]
    nhp, t_step = sel.shape[0], pps * bs

    @pl.when(t == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(rnd == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k = jnp.concatenate([r[0] for r in k_refs], axis=0)        # [T, D]
    v = jnp.concatenate([r[0] for r in v_refs], axis=0)
    if group == 1:
        qh = sel * q_ref[pl.ds(slot, 1), :]                    # [nhp, D]
    else:
        qh = sel * jnp.concatenate([q_ref[slot]] * (sel.shape[1] // hd),
                                   axis=1)
    s = _dot_f32(qh, k, ((1,), (1,)), q_exact) * scale         # [nhp, T]
    pos = rnd * t_step + jax.lax.broadcasted_iota(
        jnp.int32, (nhp, t_step), 1)
    end = (rnd + 1) * t_step            # past this round's last position
    if window is None:
        s = jnp.where(pos < length, s, _NEG)
    else:
        first = length - window
        base = jnp.maximum(first, 0) // bs * bs
        pos, end = pos + base, end + base
        s = jnp.where((pos < length) & (pos >= first), s, _NEG)
    m = m_ref[...]
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))  # [nhp, 1]
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    m_ref[...] = m_new
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + _dot_f32(p, v, ((1,), (0,)))

    @pl.when((length > 0) & (end >= length))
    def _():
        if group == 1:
            # one head's row is non-zero in a lane, so the sums over
            # the heads are that row's value: acc / l, element by element
            ctx = jnp.sum(acc_ref[...] * sel, axis=0, keepdims=True)
            norm = jnp.sum(l_ref[...] * sel, axis=0, keepdims=True)
            o_ref[pl.ds(slot, 1), :] = ctx / norm
        else:
            picked = acc_ref[...] * sel
            ctx = picked[:, :hd]
            for kv in range(1, sel.shape[1] // hd):
                ctx = ctx + picked[:, kv * hd:(kv + 1) * hd]
            o_ref[slot] = ctx / l_ref[...]


def _decode_rounds(tables, lengths, bs, pps, window=None):
    """The kernel's work list, from the tables and lengths alone (the
    same for every layer of a token-step). A lane whose first table
    entry is the scratch page 0 is inactive: it is taken to hold
    nothing (length 0) and has no round. A live slot has
    ceil(pages held / pps) rounds. Returns the lengths so taken, the
    number of rounds in all (at least one, so that a call with no live
    lane still zeroes its output), and per round (padded to the most
    there can be) its slot, its index within the slot, and the page
    each of its `pps` windows holds: the table entry where that is
    live (it holds a position under the slot's length), elsewhere the
    page the same window held the round before, so the pipeline, which
    copies only when a window's index changes, fetches live pages and
    nothing else (the first round may fetch pages it does not need).
    With a `window` a slot's rounds start at the page that holds its
    position length - window."""
    b, w = tables.shape
    most = b * (w // pps)
    # each row's first entry, by a reduction along the row: a column
    # slice has XLA copy the tables to column-major order first
    first = jnp.max(jnp.where(jnp.arange(w) == 0, tables, 0), axis=1)
    lengths = jnp.where(first == 0, 0, lengths)
    pages = -(-lengths // bs)
    start = 0
    if window is not None:
        start = jnp.maximum(lengths - window, 0) // bs
    rounds = -(-(pages - start) // pps)
    ends = jnp.cumsum(rounds)
    at = jnp.arange(most, dtype=jnp.int32)
    slot = jnp.minimum(jnp.sum(at[:, None] >= ends[None, :], axis=1),
                       b - 1).astype(jnp.int32)
    rnd = at - (ends - rounds)[slot]
    col = rnd[:, None] * pps + jnp.arange(pps, dtype=jnp.int32)[None, :]
    if window is not None:
        col = col + start[slot][:, None]
    live = (col < pages[slot][:, None]) & (at < ends[-1])[:, None]
    last = jax.lax.cummax(jnp.where(live, at[:, None], 0), axis=0)
    entry = tables[slot[:, None], jnp.minimum(col, w - 1)]
    fetch = jnp.take_along_axis(entry, last, axis=0)
    return (lengths, jnp.maximum(ends[-1], 1), slot, rnd.astype(jnp.int32),
            fetch.reshape(-1))


def paged_decode_work(tables, lengths, block_size, window=None):
    """The decode kernel's work list (`_decode_rounds`) for one token-step
    and one kind of layer: every layer of that kind reads the same
    pages, so a program makes it once a kind and hands it to each call
    of `paged_decode_attention` as `work`."""
    pps = _PAGES_PER_STEP
    tables = tables.astype(jnp.int32)
    pad = -tables.shape[1] % pps
    if pad:
        tables = jnp.pad(tables, ((0, 0), (0, pad)))
    return _decode_rounds(tables, lengths.astype(jnp.int32), block_size,
                          pps, window)


@functools.partial(jax.jit, static_argnames=("scale", "window",
                                             "interpret"))
def paged_decode_attention(q, k_pool, v_pool, tables, lengths, scale,
                           interpret=False, window=None, work=None):
    """Single-token attention over a paged K/V cache, read in place.

    q [B, nh, hd]; k_pool, v_pool [n_blocks, bs, n_kv*hd] (n_kv = nh,
    or fewer key-value heads, each read by nh / n_kv query heads; one
    row a token, heads side by side: the shape whose default device layout
    is the row-major one that the serving programs write, so nothing
    converts a pool around the call); tables [B, W] int32 (table order
    is logical order); lengths [B] int32: slot b attends positions
    0..lengths[b]-1, exactly, each at least 1 where the slot is live. A
    lane whose first table entry is the scratch page 0 is inactive: it
    has no round and its output row is 0, whatever its length. Returns
    [B, nh, hd] in the pools' dtype. The pools stay in HBM: only pages
    that hold a live position are read, and the grid has as many steps
    as the live slots hold rounds of pages (one, if none is live), so
    the work grows with the tokens held and not with the table's width
    or the lanes. With a `window`, slot b attends positions
    lengths[b]-window .. lengths[b]-1 and reads their pages only.
    `work` is `paged_decode_work(tables, lengths, bs, window)` where
    the caller made it (None: made here). f32 scores, softmax
    statistics and accumulation; the result is rounded once, here.
    `interpret=True`
    runs the kernel under the Pallas interpreter (the CPU suite).
    Jitted, so that a program that calls it once a layer traces and
    lowers it once: 36 separate calls added 4-6 s to every process's
    set-up."""
    b, nh, hd = q.shape
    bs, d = k_pool.shape[1:]
    group = nh * hd // d
    pps = _PAGES_PER_STEP
    if work is None:
        work = paged_decode_work(tables, lengths, bs, window)
    lengths, n_rounds, slot, rnd, fetch = work
    # heads padded to whole 16-bit sublane tiles; the padding's rows of
    # `sel` are zero, so they score 0 everywhere and are never picked
    nhp = _ceil_to(nh, 16)
    heads = jnp.arange(nhp)[:, None]
    if group > 1:
        heads = heads // group
    sel = (jnp.arange(d)[None, :] // hd == heads).astype(jnp.float32)
    if group == 1:
        q_in, out_rows = q.astype(jnp.float32).reshape(b, d), (b, d)
    else:
        q_in = jnp.pad(q.astype(jnp.float32), ((0, 0), (0, nhp - nh), (0, 0)))
        out_rows = (b, nhp, hd)

    def page(i):
        return pl.BlockSpec(
            (1, bs, d),
            lambda t, slot, rnd, fetch, lens: (fetch[t * pps + i], 0, 0))

    def whole(shape):
        return pl.BlockSpec(shape, lambda t, *_: (0,) * len(shape))

    kern = functools.partial(_paged_decode_kernel, scale=float(scale),
                             bs=bs, pps=pps,
                             q_exact=q.dtype == k_pool.dtype, hd=hd,
                             group=group, window=window)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_rounds,),
            in_specs=[whole(out_rows), whole((nhp, d))]
            + [page(i) for i in range(pps)] * 2,
            out_specs=whole(out_rows),
            scratch_shapes=[
                pltpu.VMEM((nhp, 1), jnp.float32),
                pltpu.VMEM((nhp, 1), jnp.float32),
                pltpu.VMEM((nhp, d), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct(out_rows, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(slot, rnd, fetch, lengths, q_in, sel,
      *([k_pool] * pps), *([v_pool] * pps))
    if group == 1:
        return out.astype(k_pool.dtype).reshape(b, nh, hd)
    return out[:, :nh].astype(k_pool.dtype)


# -- retention decode: one pass over a lane's state row -----------------------

# rows of the state (values, e) a loop iteration holds: 32 rows of 128
# lanes are 4 registers of state and 4 of each query head's running
# sum, which with 5 query heads stay in registers over the whole loop
_RETENTION_ROWS = 32
# token-steps one write of a row can cover: the chunk's keys, their
# weighted values and their weights ride one 8-sublane tile each
RETENTION_CHUNK = 8


def _retention_decode_kernel(rows_ref, write_ref, x_ref, s_ref, z_ref,
                             y_ref, so_ref, zo_ref, p_ref, a_ref, vt_ref,
                             *, grp, hd, et, n_keys):
    """One grid step is one lane's state of one key-value head:
    S [F, hd, hd] (`S[d, e, a]`: feature offset d, value e in the
    sublanes, feature position a in the lanes) and z [F rounded up to
    8, hd] (the rows past F zero), read once, and written only where
    the call writes (`write_ref`).

    `x` [40, hd] holds the step's vectors one a sublane, in tiles of 8
    rows: the `grp` query heads of this key-value head; the chunk's
    keys k_i; its weighted values w_i v_i; each weight w_i in every
    lane; the decay G of the row in every lane (decoder.py, "the decode
    chunk").

    The read: each query head's running sum `acc_i[e, a] += S[d, e, a]
    phi(q_i)[d, a]` over the planes d while a plane is in registers;
    the sum over a is taken once at the end (a transpose and a sublane
    sum, so that e comes out in the lanes). Row i < grp of `y` is
    phi(q_i)^T S, lane i of its row 7 phi(q_i).z.

    The write: plane d of `so` becomes `G S[d] + sum_i (w_i v_i) (x)
    phi(k_i)[d]` (values down the sublanes, phi(k_i)[d] along the
    lanes), z alike, whole. A call that does not write leaves `so`
    alone, and its index map holds it on one block of the scratch row
    for the whole call, so the pipeline stores nothing but that block,
    once, at the end; z is given back as it was found."""
    del rows_ref                                # the index maps read it
    nf, nfz = hd // 2 + 1, z_ref.shape[2]
    x = x_ref[0, 0]                                            # [40, hd]
    # row d of the features of the queries and keys at once is
    # `c_d x roll(x, d)` (models/decoder.py, "the retention mixer"): two
    # registers, kept in `p` (row 16 d + i is query i's, 16 d + 8 + i key
    # i's; the rows of z's padding are zero)
    qk = x[0:16]
    for d in range(nf):
        c = 1.0 if d in (0, hd // 2) else math.sqrt(2.0)
        p_ref[pl.ds(16 * d, 16), :] = qk * pltpu.roll(qk, d, 1) * c
    p_ref[pl.ds(16 * nf, 16 * (nfz - nf)), :] = jnp.zeros(
        (16 * (nfz - nf), hd), jnp.float32)
    z0 = z_ref[0, 0]
    den = [jnp.sum(p_ref[pl.ds(i, nfz, stride=16), :] * z0,
                   keepdims=True) for i in range(grp)]         # [1, 1]
    for e0 in range(0, hd, et):
        def plane(d, accs, e0=e0):
            p = p_ref[pl.ds(pl.multiple_of(d * 16, 16), 8), :]
            s = s_ref[0, 0, d, e0:e0 + et, :]
            return tuple(acc + s * p[i:i + 1, :]
                         for i, acc in enumerate(accs))

        accs = jax.lax.fori_loop(
            0, nf, plane,
            tuple(jnp.zeros((et, hd), jnp.float32) for _ in range(grp)))
        for i, acc in enumerate(accs):
            a_ref[i, e0:e0 + et, :] = acc
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, hd), 1)
    den_row = jnp.zeros((1, hd), jnp.float32)
    for i in range(grp):
        den_row = jnp.where(lanes == i, den[i], den_row)
    y_ref[0, 0] = jnp.concatenate(
        [jnp.sum(a_ref[i].T, axis=0, keepdims=True) for i in range(grp)]
        + [jnp.zeros((7 - grp, hd), jnp.float32), den_row], axis=0)
    zo_ref[0, 0] = z0

    @pl.when(write_ref[0] != 0)
    def _write():
        decay = x[32:33, :]                                    # [1, hd]
        z_new = decay * z0
        for i in range(n_keys):
            z_new = z_new + x[24 + i:25 + i, :] \
                * p_ref[pl.ds(8 + i, nfz, stride=16), :]
        zo_ref[0, 0] = z_new
        for i in range(n_keys):
            # w_i v_i down the sublanes, the same in every lane
            vt_ref[i] = jnp.broadcast_to(x[16 + i:17 + i, :], (hd, hd)).T
        decay_t = jnp.broadcast_to(decay, (et, hd))
        for e0 in range(0, hd, et):
            vts = [vt_ref[i, e0:e0 + et, :] for i in range(n_keys)]

            def plane_out(d, carry, e0=e0, vts=vts):
                p = p_ref[pl.ds(pl.multiple_of(d * 16 + 8, 8), 8), :]
                m = decay_t * s_ref[0, 0, d, e0:e0 + et, :]
                for i, vt in enumerate(vts):
                    m = m + vt * p[i:i + 1, :]
                so_ref[0, 0, d, e0:e0 + et, :] = m
                return carry

            jax.lax.fori_loop(0, nf, plane_out, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def retention_decode(state, rows, q, keys, vals, decay, weights, write,
                     interpret=False):
    """`decoder.retention_pass` as one Mosaic call: each lane's state
    row read once, and written only where `write`.

    state = (S [R, n_kv, F, hd, hd], z [R, n_kv, F rounded up to 8,
    hd]) in f32, F = hd/2 + 1; rows [B] int32, the state row of each
    lane (dead lanes name the scratch row 0); q [B, N, hd]; the chunk's
    keys and vals [B, n_kv, C, hd], C <= RETENTION_CHUNK, with their
    weights [B, n_kv, C] and the row's decay [B, n_kv] (f32:
    `decoder.retention_chunk_weights`); `write` a bool, traced or not.
    Returns (num0 = phi(q)^T S [B, n_kv, N / n_kv, hd], den0 = phi(q).z
    [B, n_kv, N / n_kv], state'), all arithmetic in f32. Where `write`,
    lane b's row becomes decay S + sum_i weights_i vals_i phi(keys_i)^T
    (z alike), in place: the state operands are aliased to the outputs
    (donate them); a call that does not write stores one block of
    junk in the scratch row 0, which no live lane reads. `interpret=True` runs the kernel under the Pallas
    interpreter (the CPU suite). Jitted, so that a program that calls
    it once a layer and token-step traces and lowers it once."""
    s_all, z_all = state
    b, nh, hd = q.shape
    n_kv, n_keys = keys.shape[1], keys.shape[2]
    grp = nh // n_kv
    nf, nfz = hd // 2 + 1, z_all.shape[2]
    if s_all.dtype != jnp.float32 or z_all.dtype != jnp.float32:
        raise ValueError("retention_decode keeps its state in float32, "
                         f"got {s_all.dtype}")
    if grp > 7:
        raise ValueError(
            f"{grp} query heads a key-value head: the queries and their "
            "normalisers ride one 8-sublane tile")
    if n_keys > RETENTION_CHUNK:
        raise ValueError(
            f"a write of {n_keys} token-steps: at most {RETENTION_CHUNK}, "
            "the chunk's keys ride one 8-sublane tile")
    et = min(_RETENTION_ROWS, hd)
    f32 = jnp.float32

    def tile(a):
        """[b, n_kv, n, hd] -> [b, n_kv, 8, hd] in f32, zeros below."""
        return jnp.pad(a.astype(f32),
                       ((0, 0), (0, 0), (0, 8 - a.shape[2]), (0, 0)))

    x = jnp.concatenate([
        tile(q.reshape(b, n_kv, grp, hd)), tile(keys),
        tile(vals * weights[..., None]),
        tile(jnp.broadcast_to(weights[..., None], keys.shape)),
        tile(jnp.broadcast_to(decay[..., None, None], (b, n_kv, 1, hd)))],
        axis=2)

    def lane(*tail):
        return lambda i, j, rows, write: (i, j) + tail

    def row(*tail):
        return lambda i, j, rows, write: (rows[i], j) + tail

    def written(i, j, rows, write):
        """Where a call writes: lane i's row, else the scratch row's
        first block throughout (stored once, never read for a lane)."""
        on = write[0] != 0
        return (jnp.where(on, rows[i], 0), jnp.where(on, j, 0), 0, 0, 0)

    kern = functools.partial(_retention_decode_kernel, grp=grp, hd=hd,
                             et=et, n_keys=n_keys)
    y, s_all, z_all = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_kv),
            in_specs=[pl.BlockSpec((1, 1, 40, hd), lane(0, 0)),
                      pl.BlockSpec((1, 1, nf, hd, hd), row(0, 0, 0)),
                      pl.BlockSpec((1, 1, nfz, hd), row(0, 0))],
            out_specs=[pl.BlockSpec((1, 1, 8, hd), lane(0, 0)),
                       pl.BlockSpec((1, 1, nf, hd, hd), written),
                       pl.BlockSpec((1, 1, nfz, hd), row(0, 0))],
            scratch_shapes=[pltpu.VMEM((nfz * 16, hd), f32),
                            pltpu.VMEM((grp, hd, hd), f32),
                            pltpu.VMEM((n_keys, hd, hd), f32)]),
        out_shape=[jax.ShapeDtypeStruct((b, n_kv, 8, hd), f32),
                   jax.ShapeDtypeStruct(s_all.shape, f32),
                   jax.ShapeDtypeStruct(z_all.shape, f32)],
        # operands count the prefetched rows and flag: S is 3, z is 4
        input_output_aliases={3: 1, 4: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # a plane set is 4.3 MB at hd 128, in and out, each
            # double-buffered
            vmem_limit_bytes=48 * 1024 * 1024),
        interpret=interpret,
        name="retention_decode",
    )(rows.astype(jnp.int32), jnp.asarray(write, jnp.int32).reshape(1),
      x, s_all, z_all)
    return y[:, :, :grp], y[:, :, 7, :grp], (s_all, z_all)


# -- the expert layer's grouped matmul -----------------------------------------

def _gmm_tile(n: int, most: int) -> int:
    """The largest multiple of 128 that divides n and is at most
    `most` (n itself where no such multiple exists)."""
    for t in range(most // 128 * 128, 0, -128):
        if n % t == 0:
            return t
    return n


def expert_gmm(lhs, rhs, sizes, out_dtype, interpret=False):
    """Rows of lhs [M, K] in groups sorted by expert, group g's
    `sizes[g]` rows times rhs[g] [K, N], f32 accumulation, out_dtype
    out; rows past the groups are not computed. JAX's megablox kernel
    (`jax.experimental.pallas.ops.tpu.megablox.gmm`, `gmm_*` in a
    device trace): a grid step is one row tile of one group, so an
    expert's weights are read once a row tile it has rows in, and a
    group's rows ride the tiles of their neighbours. Row tiles of 512
    for a prefill's many rows (each expert sees hundreds), of 32 for a
    decode step's few."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    m, k = lhs.shape
    n = rhs.shape[2]
    tm = 512 if m >= 8192 and m % 512 == 0 else 32 if m % 32 == 0 else m
    tiling = (tm, _gmm_tile(k, 1024), _gmm_tile(n, 1024))
    return gmm(lhs, rhs, sizes.astype(jnp.int32), out_dtype, tiling,
               interpret=interpret)
