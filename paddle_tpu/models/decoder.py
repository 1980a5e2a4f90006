"""The GPT decoder block as one pure function over the parameter tree.

Generation (models/generation.py: a jitted prefill + decode scan over a
dense cache) and serving (serving/programs.py: bucketed prefill, paged
decode step, paged chunk) all run `block`. What differs between them is
how the cache is addressed, and that is `block`'s one parameter: an
`attend(q, k, v)` that writes this call's K/V where its cache keeps
them and returns what the queries attended. The rest — norms, the fused
qkv and its column layout, the tp all-reduces, the MLP — stands here
once, described by a `DecoderSpec`, so f32 greedy through any cache is
the same arithmetic in the same order (tests/test_decoder_block.py).

The parameter tree is `generation._gpt_params`'s: `wte`, `wpe`,
`lnf_w`, `lnf_b` and per block `{ln1,ln2,qkv,proj,fc1,fc2}_{w,b}`.
Training's copy of the block (models/gpt.py::GPTBlock.forward, on the
framework's Tensor ops) is held to this one by the same test file.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from ..observability.anatomy import scope as _scope

__all__ = ["DecoderSpec", "block", "blocks", "embed", "final_logits",
           "masked_attention", "prefix_mask", "causal_mask"]


@dataclasses.dataclass(frozen=True)
class DecoderSpec:
    """What a block body closes over. Hashable: the dense builders key
    their compiled programs on it.

    Under a tp plan (serving/engine.py::serving_decoder_spec) `n_heads`
    is the LOCAL head count, the fused-qkv columns are heads-major
    `(heads, 3, head_dim)` so a chip's contiguous shard carries whole
    heads with their q, k, v, and `reduce` all-reduces the proj / fc2
    partial contractions before their biases."""
    eps: float
    n_heads: int
    head_dim: int
    qkv_heads_major: bool = False
    reduce: Optional[Callable] = None

    @classmethod
    def of(cls, config) -> "DecoderSpec":
        """The unsharded block of a GPTConfig."""
        nh = int(config.num_heads)
        return cls(eps=float(config.layer_norm_eps), n_heads=nh,
                   head_dim=int(config.hidden_size) // nh)

    @property
    def scale(self) -> float:
        return 1.0 / math.sqrt(self.head_dim)


def _ln(x, w, b, eps):
    # moments in f32 regardless of storage dtype: bf16 serving would
    # otherwise lose layernorm precision
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return (((xf - mu) / jnp.sqrt(var + eps)).astype(x.dtype) * w + b)


def _gelu(x):
    """The erf GELU in f32, rounded once to x's dtype. Written with
    `lax.erf`, not `jax.nn.gelu(approximate=False)`: that one is
    `0.5 x erfc(-x sqrt(1/2))` in the storage dtype, and on a TPU
    `erfc` expands to 84 elementwise instructions (both branches, an
    exponential, two divides) that XLA fuses into fc2's OPERAND, where
    the matrix unit waits for them; `erf` stays one instruction and the
    activation rides in fc1's epilogue, once an element.
    `erfc(-z) = 1 + erf(z)` exactly: the same function, 1e-6 from it
    in f32."""
    xf = x.astype(jnp.float32)
    return (0.5 * xf * (1.0 + jax.lax.erf(xf * 0.7071067811865476))
            ).astype(x.dtype)


def _mm(x, bp, name):
    """One block matmul through either the float weight
    (``<name>_w``: the training/bf16 serving path, unchanged HLO) or
    the serving int8 snapshot (a ``{"q8", "s"}`` leaf from
    quant/int8_serving — per-channel PTQ codes + dequant scales riding
    the params pytree as traced arguments). The branch is a trace-time
    isinstance on the pytree structure, so the float path compiles to
    exactly the ``x @ w`` it always was — the f32 greedy parity
    contract is untouched."""
    w = bp[name + "_w"]
    if isinstance(w, dict):
        from ..quant.int8_serving import int8_matmul
        return int8_matmul(x, w["q8"], w["s"])
    return x @ w


def embed(params, ids, positions):
    """Token plus learned position embeddings; `positions` broadcasts
    against `ids`."""
    return params["wte"][ids] + params["wpe"][positions]


def final_logits(spec, params, x):
    """`ln_f`, then the weight-tied head."""
    h = _ln(x, params["lnf_w"], params["lnf_b"], spec.eps)
    return h @ params["wte"].T


def prefix_mask(n_keys, n_valid):
    """[B or 1, 1, 1, n_keys]: key j is live iff j < n_valid (a scalar,
    or [B] where every row has its own live prefix)."""
    return (jnp.arange(n_keys)[None, None, None, :]
            < jnp.reshape(n_valid, (-1, 1, 1, 1)))


def causal_mask(s, prompt_lens=None):
    """[S, S] lower triangle; with prompt_lens [B] (right-padded rows)
    [B, 1, S, S], keys past each row's true length masked too."""
    cm = jnp.tril(jnp.ones((s, s), bool))
    if prompt_lens is None:
        return cm
    live = jnp.arange(s)[None, :] < prompt_lens[:, None]
    return cm[None, None] & live[:, None, None, :]


def masked_attention(q, kc, vc, mask, scale):
    """Queries [B, S, N, hd] (the block's view) over keys and values
    [B, N, T, hd] (a cache's), `mask` broadcastable to [B, N, S, T];
    the softmax in f32 whatever the storage dtype. Returns
    [B, S, N, hd]."""
    q = jnp.einsum("bsnh->bnsh", q)
    att = jnp.einsum("bnqh,bnkh->bnqk", q, kc) * scale
    att = jnp.where(mask, att, -1e30)
    p = jax.nn.softmax(att.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bnsh->bsnh", jnp.einsum("bnqk,bnkh->bnqh", p, vc))


def block(spec, bp, x, attend):
    """One decoder block over x [B, S, H] -> (x', cache').

    `attend(q, k, v) -> (ctx, cache')` gets [B, S, n_heads, head_dim]
    views of the one qkv result and owns all that differs between the
    callers: where K/V are written and what the queries attend over.
    `ctx` comes back in q's shape; `cache'` is passed through."""
    b, s, _ = x.shape
    nh, hd = spec.n_heads, spec.head_dim
    with _scope("attn"):
        xn = _ln(x, bp["ln1_w"], bp["ln1_b"], spec.eps)
        qkv = _mm(xn, bp, "qkv") + bp["qkv_b"]
        if spec.qkv_heads_major:
            qkv = jnp.einsum("bsnch->bscnh", qkv.reshape(b, s, nh, 3, hd))
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        else:
            # thirds of the lanes, then heads: slicing a [.., 3, nh, hd]
            # view made XLA lay a prefill's whole qkv result out
            # sequence-minor and copy it back for every reader
            q, k, v = (t.reshape(b, s, nh, hd)
                       for t in jnp.split(qkv, 3, axis=-1))
        ctx, cache = attend(q, k, v)
        proj = _mm(ctx.reshape(b, s, nh * hd), bp, "proj")
        if spec.reduce is not None:
            proj = spec.reduce(proj)
        x = x + proj + bp["proj_b"]
    with _scope("mlp"):
        ff = _ln(x, bp["ln2_w"], bp["ln2_b"], spec.eps)
        ff = _gelu(_mm(ff, bp, "fc1") + bp["fc1_b"])
        ff = _mm(ff, bp, "fc2")
        if spec.reduce is not None:
            ff = spec.reduce(ff)
        x = x + ff + bp["fc2_b"]
    return x, cache


def blocks(spec, params, x, caches, attend):
    """Every block in turn. `attend(cache, q, k, v)` is `block`'s with
    that layer's cache in front (None throughout for a prefill, which
    has none to read)."""
    new = []
    if caches is None:
        caches = [None] * len(params["blocks"])
    for bp, cache in zip(params["blocks"], caches):
        x, cache = block(spec, bp, x, functools.partial(attend, cache))
        new.append(cache)
    return x, tuple(new)
