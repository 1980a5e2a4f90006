"""The decoder block as one pure function over the parameter tree.

Generation (models/generation.py: a jitted prefill + decode scan over a
dense cache) and serving (serving/programs.py: bucketed prefill, decode
step, paged chunk) all run `block`. What differs between them is how
the cache is addressed, and that is `block`'s one parameter: an
`attend(q, k, v)` that writes this call's K/V where its cache keeps
them and returns what the queries attended. The rest — norms, the
projections and their column layout, the tp all-reduces, the MLP —
stands here once, described by a `DecoderSpec`, so f32 greedy through
any cache is the same arithmetic in the same order
(tests/test_decoder_block.py).

A `DecoderSpec` describes two blocks today. The first (its defaults):
LayerNorm with bias, learned positions, one fused qkv of equal heads,
softmax attention, an erf-GELU 4h MLP, a head tied to the embedding;
its tree is `generation._gpt_params`'s: `wte`, `wpe`, `lnf_w`, `lnf_b`
and per block `{ln1,ln2,qkv,proj,fc1,fc2}_{w,b}`. The second: RMSNorm,
no biases, separate `q`, `k`, `v` of `n_heads` and `n_kv_heads`, a
per-head RMSNorm on q and k, rotary positions, a SwiGLU MLP
(`gate`, `up`, `down`), an untied head (`head_w`), and the mixer
`retention`: a gated linear attention with the kernel `(q.k)^2`
(`retained_attention`, the functions below it, and DESIGN.md "How a
retention layer is served"), whose `attend` also gets the block's
log-decay `gate` (`g_w`, `g_b`). Training's copy of the first block
(models/gpt.py::GPTBlock.forward, on the framework's Tensor ops) is
held to this one by the same test file.
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
           "masked_attention", "prefix_mask", "causal_mask",
           "retention_features", "retained_attention", "retention_state",
           "retention_step", "retention_chunk_push",
           "retention_chunk_weights", "retention_pass",
           "retention_chunk_step"]


@dataclasses.dataclass(frozen=True)
class DecoderSpec:
    """What a block body closes over. Hashable: the dense builders key
    their compiled programs on it.

    Under a tp plan (serving/engine.py::serving_decoder_spec) `n_heads`
    is the LOCAL head count, the fused-qkv columns are heads-major
    `(heads, 3, head_dim)` so a chip's contiguous shard carries whole
    heads with their q, k, v, and `reduce` all-reduces the proj / fc2
    partial contractions before their biases.

    The fields after `reduce` are what distinguishes the second block
    (the module's header); their defaults are the first."""
    eps: float
    n_heads: int
    head_dim: int
    qkv_heads_major: bool = False
    reduce: Optional[Callable] = None
    norm: str = "layer"                 # | "rms": a gain, no bias
    bias: bool = True                   # on the projections and the MLP
    n_kv_heads: Optional[int] = None    # None: one fused qkv of n_heads
    qk_norm: bool = False               # RMSNorm of each q and k head
    rope_theta: Optional[float] = None  # None: learned positions (wpe)
    mlp: str = "gelu"                   # | "swiglu"
    tied_head: bool = True              # False: `head_w`
    mixer: str = "softmax"              # | "retention": attend gets a gate

    def __post_init__(self):
        # `block` knows the two trees of the module's header. A mix it
        # has no leaves for is refused here, by name, not as a KeyError
        # inside a trace
        fused = self.n_kv_heads is None
        for field, known in (("norm", ("layer", "rms")),
                             ("mlp", ("gelu", "swiglu")),
                             ("mixer", ("softmax", "retention"))):
            if getattr(self, field) not in known:
                raise ValueError(f"DecoderSpec.{field}="
                                 f"{getattr(self, field)!r}: one of {known}")
        if self.bias != fused or self.bias != (self.mlp == "gelu"):
            raise ValueError(
                "DecoderSpec: biases come with the fused qkv "
                "(n_kv_heads=None) and the GELU MLP, and only with them; "
                f"got bias={self.bias}, n_kv_heads={self.n_kv_heads}, "
                f"mlp={self.mlp!r}")
        if not fused and (self.qkv_heads_major or self.reduce is not None):
            raise ValueError(
                "DecoderSpec: the tp layout (qkv_heads_major, reduce) is "
                "the fused qkv's; separate q, k, v have no sharded form")
        if fused and (self.mixer == "retention" or self.qk_norm):
            raise ValueError(
                "DecoderSpec: the retention mixer's gate and the q/k norm "
                "are leaves of the tree with separate q, k, v: set "
                "n_kv_heads")
    @classmethod
    def of(cls, config) -> "DecoderSpec":
        """The unsharded block of a model's config: a GPTConfig's, or
        what a config that describes its own block says."""
        if hasattr(config, "decoder_spec"):
            return config.decoder_spec()
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


def _rms(x, w, eps):
    """RMSNorm over the last axis, the mean square in f32."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps)).astype(x.dtype) * w


def _norm(spec, x, p, name):
    if spec.norm == "rms":
        return _rms(x, p[name + "_w"], spec.eps)
    return _ln(x, p[name + "_w"], p[name + "_b"], spec.eps)


def _rope_table(spec, positions):
    """(cos, sin), each `positions.shape + (1, head_dim)` in f32: the
    rotate-half form, both halves of a head turning by the same
    angles `position * theta^(-2i / head_dim)`."""
    hd = spec.head_dim
    inv = 1.0 / (spec.rope_theta
                 ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[..., None, None] * inv
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang), jnp.sin(ang)


def _rope(x, table):
    cos, sin = table
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    turned = jnp.concatenate([-x2, x1], axis=-1)
    return (xf * cos + turned * sin).astype(x.dtype)


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
    against `ids`. A tree without `wpe` (rotary positions, applied in
    the block) is a lookup."""
    if "wpe" not in params:
        return params["wte"][ids]
    return params["wte"][ids] + params["wpe"][positions]


def final_logits(spec, params, x):
    """The final norm, then the head: tied to the embedding, or its
    own `head_w`."""
    h = _norm(spec, x, params, "lnf")
    if spec.tied_head:
        return h @ params["wte"].T
    return h @ params["head_w"]


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


def block(spec, bp, x, attend, rope=None):
    """One decoder block over x [B, S, H] -> (x', cache').

    `attend(q, k, v) -> (ctx, cache')` gets [B, S, heads, head_dim]
    views of the projections (`n_kv_heads` of them for k and v where
    the spec has fewer) and owns all that differs between the callers:
    where K/V are written and what the queries attend over. Under the
    mixer `retention` it is `attend(q, k, v, gate)`, `gate`
    [B, S, n_kv_heads] the f32 log of each token's decay. `ctx` comes
    back in q's shape; `cache'` is passed through. `rope` is
    `_rope_table`'s pair for x's positions (`blocks` makes it)."""
    b, s, _ = x.shape
    nh, hd = spec.n_heads, spec.head_dim
    with _scope("attn"):
        xn = _norm(spec, x, bp, "ln1")
        if spec.n_kv_heads is not None:
            q = _mm(xn, bp, "q").reshape(b, s, nh, hd)
            k = _mm(xn, bp, "k").reshape(b, s, spec.n_kv_heads, hd)
            v = _mm(xn, bp, "v").reshape(b, s, spec.n_kv_heads, hd)
        else:
            qkv = _mm(xn, bp, "qkv") + bp["qkv_b"]
            if spec.qkv_heads_major:
                qkv = jnp.einsum("bsnch->bscnh",
                                 qkv.reshape(b, s, nh, 3, hd))
                q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            else:
                # thirds of the lanes, then heads: slicing a
                # [.., 3, nh, hd] view made XLA lay a prefill's whole
                # qkv result out sequence-minor and copy it back for
                # every reader
                q, k, v = (t.reshape(b, s, nh, hd)
                           for t in jnp.split(qkv, 3, axis=-1))
        if spec.qk_norm:
            q = _rms(q, bp["qn_w"], spec.eps)
            k = _rms(k, bp["kn_w"], spec.eps)
        if rope is not None:
            q, k = _rope(q, rope), _rope(k, rope)
        if spec.mixer == "retention":
            # the gate's sum runs over a whole request: f32 from the
            # matmul's accumulator on, never rounded to the storage type
            gate = jax.nn.log_sigmoid(
                jnp.matmul(xn, bp["g_w"],
                           preferred_element_type=jnp.float32)
                + bp["g_b"].astype(jnp.float32))
            ctx, cache = attend(q, k, v, gate)
        else:
            ctx, cache = attend(q, k, v)
        proj = _mm(ctx.reshape(b, s, nh * hd), bp, "proj")
        if spec.reduce is not None:
            proj = spec.reduce(proj)
        x = x + proj
        if spec.bias:
            x = x + bp["proj_b"]
    with _scope("mlp"):
        ff = _norm(spec, x, bp, "ln2")
        if spec.mlp == "swiglu":
            ff = jax.nn.silu(_mm(ff, bp, "gate")) * _mm(ff, bp, "up")
            ff = _mm(ff, bp, "down")
        else:
            ff = _gelu(_mm(ff, bp, "fc1") + bp["fc1_b"])
            ff = _mm(ff, bp, "fc2")
        if spec.reduce is not None:
            ff = spec.reduce(ff)
        x = x + ff
        if spec.bias:
            x = x + bp["fc2_b"]
    return x, cache


def blocks(spec, params, x, caches, attend, positions=None):
    """Every block in turn. `attend(cache, q, k, v)` is `block`'s with
    that layer's cache in front (None throughout for a prefill, which
    has none to read). `positions` (broadcastable to x's [B, S]) are
    what a spec with rotary positions turns q and k by."""
    new = []
    if caches is None:
        caches = [None] * len(params["blocks"])
    rope = None if spec.rope_theta is None \
        else _rope_table(spec, positions)
    for bp, cache in zip(params["blocks"], caches):
        x, cache = block(spec, bp, x, functools.partial(attend, cache),
                         rope)
        new.append(cache)
    return x, tuple(new)


# -- the retention mixer ------------------------------------------------------
#
# A gated linear attention whose kernel is the even power (q.k)^2:
#   a_ts = (q_t . k_s)^2 exp(Gam_t - Gam_s), s <= t;   y_t = sum_s a_ts v_s
#                                                           / sum_s a_ts
# with Gam the running sum of the gate. (x.y)^2 = phi(x).phi(y) for the
# symmetric second tensor power phi, so a request's whole past is a
# fixed-size state S = sum_s w_s phi(k_s) v_s^T, z = sum_s w_s phi(k_s).
# phi lists each unordered pair of components once, by circular offset:
#   phi(x)[d, a] = c_d x_a x_{(a - d) mod n},   d = 0 .. n/2,
#   c_0 = c_{n/2} = 1, c_d = sqrt(2) otherwise
# (offset n/2 meets each of its pairs twice at weight 1, where the
# textbook order lists it once at sqrt(2): n/2 entries more, 0.8% at
# n = 128). So every row of phi is x times a lane rotation of x, and the
# state of one key-value head is [n/2 + 1, n, n] in whole tiles:
#   S[d, e, a] = sum_s w_s v_s[e] phi(k_s)[d, a],    z[d, a] alike.
# z's rows are padded with zeros to a multiple of 8 (`_z_rows`): with 65
# rows the device's default layout of [rows, n_kv, 65, n] puts n_kv
# second-minor to save the padding, and every program would convert
# the pool at entry and exit to address a head's [65, n] (the lesson
# of the K/V pools' layout, paged_cache.py).

def _feature_weights(n):
    c = [math.sqrt(2.0)] * (n // 2 + 1)
    c[0] = c[n // 2] = 1.0
    return c


def retention_features(x):
    """phi(x): [..., n] -> [..., n/2 + 1, n] in f32."""
    xf = x.astype(jnp.float32)
    return jnp.stack([c * xf * jnp.roll(xf, d, axis=-1) for d, c in
                      enumerate(_feature_weights(x.shape[-1]))], axis=-2)


def _z_rows(z_feats):
    """[..., F, n] -> [..., F rounded up to 8, n], zeros below."""
    f = z_feats.shape[-2]
    pad = [(0, 0)] * z_feats.ndim
    pad[-2] = (0, -f % 8)
    return jnp.pad(z_feats, pad)


def _grouped(q, n_kv):
    """[..., n_heads, hd] -> [..., n_kv, group, hd]: query head i
    reads key-value head i // group."""
    return q.reshape(q.shape[:-2] + (n_kv, q.shape[-2] // n_kv,
                                     q.shape[-1]))


def _decay(gam_to, gam_from, live):
    """exp(gam_to - gam_from) where `live`, else 0 (and no overflow
    where it is not)."""
    return jnp.where(live, jnp.exp(jnp.where(live, gam_to - gam_from,
                                             0.0)), 0.0)


def retained_attention(q, k, v, gate, lengths=None):
    """The quadratic form over one call's own rows: q [B, S, N, hd],
    k and v [B, S, n_kv, hd], gate [B, S, n_kv] (f32 log decays).
    Causal; with `lengths` [B] (right-padded rows) keys at or past a
    row's true length weigh 0 (the rows' junk queries come out finite).
    Scores, squares, decays and the division in f32; the weights meet v
    in v's dtype. Returns q's shape."""
    b, s, nh, hd = q.shape
    n_kv = k.shape[2]
    gam = jnp.cumsum(gate, axis=1)                          # [B, S, n_kv]
    sc = jnp.einsum("bqjgh,bkjh->bjgqk", _grouped(q, n_kv), k,
                    preferred_element_type=jnp.float32)
    live = jnp.tril(jnp.ones((s, s), bool))
    if lengths is not None:
        live = live & (jnp.arange(s)[None, :]
                       < lengths[:, None])[:, None, None, None, :]
    gam = jnp.einsum("bsj->bjs", gam)
    w = sc * sc * _decay(gam[:, :, None, :, None],
                         gam[:, :, None, None, :], live)
    num = jnp.einsum("bjgqk,bkjh->bqjgh", w.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    den = jnp.einsum("bjgq->bqjg", jnp.sum(w, axis=-1))
    # a true query always weighs its own key ((q.k)^2 at decay 1); a
    # padded row's junk query far past the row's length can weigh
    # nothing at all (every live key's decay underflows). It gets 0,
    # not 0/0: the next layer would multiply that NaN, as a value at
    # weight 0, into the row's true queries and into its state
    den = jnp.where(den > 0.0, den, 1.0)
    return (num / den[..., None]).reshape(b, s, nh, hd).astype(q.dtype)


def retention_state(k, v, gate, lengths):
    """What a prompt leaves behind, as of each row's LAST TRUE token:
    S [B, n_kv, F, hd, hd] and z [B, n_kv, F padded, hd] in f32, from
    k, v [B, S, n_kv, hd], gate [B, S, n_kv], lengths [B]. Keys at or
    past a row's length weigh 0: a bucketed prefill's junk rows never
    reach a state, from which nothing could take them out again."""
    gam = jnp.cumsum(gate, axis=1)
    last = jnp.take_along_axis(
        gam, (lengths - 1).astype(jnp.int32)[:, None, None], axis=1)
    live = (jnp.arange(k.shape[1])[None, :] < lengths[:, None])[..., None]
    w = _decay(last, gam, live)                             # [B, S, n_kv]
    feats = retention_features(k)                           # [B,S,j,F,hd]
    vw = v.astype(jnp.float32) * w[..., None]
    return (jnp.einsum("bsje,bsjda->bjdea", vw, feats),
            _z_rows(jnp.einsum("bsj,bsjda->bjda", w, feats)))


def retention_step(state, rows, q, k, v, gate):
    """One token a lane, in jax.numpy (the form
    `ops/pallas_kernels.retention_decode` is held to): lane i's state
    row `rows[i]` is scaled by its decay, gains phi(k) v^T, is written
    back, and is read by the lane's queries. state = (S [R, n_kv, F,
    hd, hd], z [R, n_kv, F padded, hd]); q [B, N, hd], k and v [B, n_kv, hd],
    gate [B, n_kv]. Arithmetic in f32; the rows keep the state's
    dtype. Returns (ctx [B, N, hd] in q's dtype, state')."""
    s_all, z_all = state
    g = jnp.exp(gate.astype(jnp.float32))[..., None, None]  # [B,j,1,1]
    fk = retention_features(k)                              # [B, j, F, hd]
    fq = retention_features(_grouped(q, k.shape[1]))        # [B,j,g,F,hd]
    s_new = (g[..., None] * s_all[rows].astype(jnp.float32)
             + v.astype(jnp.float32)[:, :, None, :, None]
             * fk[:, :, :, None, :]).astype(s_all.dtype)
    z_new = (g * z_all[rows].astype(jnp.float32)
             + _z_rows(fk)).astype(z_all.dtype)
    num = jnp.einsum("bjgda,bjdea->bjge", fq, s_new.astype(jnp.float32))
    den = jnp.einsum("bjgda,bjda->bjg", fq,
                     z_new[..., :fk.shape[-2], :].astype(jnp.float32))
    ctx = (num / den[..., None]).reshape(q.shape).astype(q.dtype)
    return ctx, (s_all.at[rows].set(s_new), z_all.at[rows].set(z_new))


# -- the decode chunk: rows read every token-step, written once ---------------
#
# Over the token-steps t = 0 .. n-1 of a chunk, with S_0 the row as the
# chunk found it, G_t = prod_{i<=t} g_i and w_{t,i} = prod_{i<j<=t} g_j:
#   phi(q_t)^T S_t = G_t phi(q_t)^T S_0 + sum_{i<=t} w_{t,i} (q_t.k_i)^2 v_i
# and phi(q_t).z_t alike. So a token-step READS its row (`num0`, `den0`)
# and adds the chunk's own keys outside it; only the last token-step
# WRITES, S_n = G_n S_0 + sum_i w_{n,i} v_i phi(k_i)^T (z alike). The
# chunk's k, v and log decays ride in buffers [B, n_kv, C, hd] (gates
# [B, n_kv, C]), one entry a token-step, in f32.

def retention_chunk_push(chunk, t, k, v, gate):
    """The chunk's buffers (keys, vals, gates) with token-step t's k, v
    [B, n_kv, hd] and gate [B, n_kv] at index t."""
    keys, vals, gates = chunk
    f32 = jnp.float32
    return (keys.at[:, :, t].set(k.astype(f32)),
            vals.at[:, :, t].set(v.astype(f32)),
            gates.at[:, :, t].set(gate.astype(f32)))


def retention_chunk_weights(gates, t):
    """At token-step t of a chunk whose log decays are gates
    [B, n_kv, C] (entries past t ignored): (G_t [B, n_kv], the decay
    of the row as the chunk found it; w [B, n_kv, C], w_{t,i}, 0 past
    t)."""
    live = jnp.arange(gates.shape[-1]) <= t
    gam = jnp.cumsum(jnp.where(live, gates, 0.0), axis=-1)
    total = gam[..., -1:]
    return jnp.exp(total[..., 0]), _decay(total, gam, live)


def retention_pass(state, rows, q, keys, vals, decay, weights, write):
    """One pass over each lane's state row, in jax.numpy (the form
    `ops/pallas_kernels.retention_decode` is held to). Reads the row
    `rows[i]` as it stands: num0 = phi(q)^T S_0 [B, n_kv, group, hd]
    and den0 = phi(q).z_0 [B, n_kv, group], f32. Where `write` (a bool,
    traced or not), the row becomes decay S_0 + sum_i weights_i vals_i
    phi(keys_i)^T, z alike (keys, vals [B, n_kv, C, hd], decay
    [B, n_kv], weights [B, n_kv, C]); otherwise nothing is written.
    Returns (num0, den0, state')."""
    s_all, z_all = state
    n_kv = keys.shape[1]
    fq = retention_features(_grouped(q, n_kv))              # [B,j,g,F,hd]
    nf = fq.shape[-2]
    s0 = s_all[rows].astype(jnp.float32)
    z0 = z_all[rows].astype(jnp.float32)
    num0 = jnp.einsum("bjgda,bjdea->bjge", fq, s0)
    den0 = jnp.einsum("bjgda,bjda->bjg", fq, z0[..., :nf, :])

    def flush(state):
        s_all, z_all = state
        fk = retention_features(keys)                       # [B,j,C,F,hd]
        wv = vals * weights[..., None]
        s_new = (decay[..., None, None, None] * s0
                 + jnp.einsum("bjce,bjcda->bjdea", wv, fk))
        z_new = (decay[..., None, None] * z0
                 + _z_rows(jnp.einsum("bjc,bjcda->bjda", weights, fk)))
        return (s_all.at[rows].set(s_new.astype(s_all.dtype)),
                z_all.at[rows].set(z_new.astype(z_all.dtype)))

    return num0, den0, jax.lax.cond(write, flush, lambda st: st, state)


def retention_chunk_step(state, rows, q, chunk, t, write,
                         one_pass=retention_pass):
    """Token-step t of a decode chunk whose buffers `chunk` already
    hold this token-step's k, v and gate (`retention_chunk_push`):
    `one_pass` (`retention_pass`, or the kernel) reads each lane's row
    and, where `write`, writes its state as of this token-step; the
    chunk's own tokens are added here, (q.k_i)^2 over at most C keys,
    all in f32. Equal to t + 1 `retention_step`s from the row as the
    chunk found it. Returns (ctx [B, N, hd] in q's dtype, state')."""
    keys, vals, gates = chunk
    decay, w = retention_chunk_weights(gates, t)
    num0, den0, state = one_pass(state, rows, q, keys, vals, decay, w,
                                 write)
    qg = _grouped(q, keys.shape[1]).astype(jnp.float32)     # [B,j,g,hd]
    sc = jnp.sum(qg[:, :, :, None, :] * keys[:, :, None], axis=-1)
    a = sc * sc * w[:, :, None, :]                          # [B,j,g,C]
    num = (decay[..., None, None] * num0
           + jnp.sum(a[..., None] * vals[:, :, None], axis=3))
    den = decay[..., None] * den0 + jnp.sum(a, axis=-1)
    return (num / den[..., None]).reshape(q.shape).astype(q.dtype), state
