"""Plain reference for `brumby-14b-base`: the full forward pass of a
stack of power-retention layers over a prompt and its served tokens, in
straightforward float32 jax.numpy at `highest` matmul precision. The
QUADRATIC form over the whole sequence: no state, no cache, no kernel,
no buckets; it imports nothing of the program.

The stack is Qwen3-14B's (the published config's keys are that
model's): RMSNorm, no biases, 40 query and 8 key-value heads of 128, a
per-head RMSNorm on q and k, rotary positions (rotate-half, theta 1e6),
SwiGLU, an untied head; every softmax attention is replaced by a power
retention layer (Manifest AI, "Scaling Context Requires Rethinking
Attention", arXiv:2507.04239, and the Brumby-14B-Base release notes).
The published config has no key for the power, the gate or the
normaliser: they are the configuration file's `assumed` items. For one
layer, query head i reading key-value head j = i // 5:

    H    = rmsnorm(X)
    Q, K = rope(rmsnorm_128(H Wq)), rope(rmsnorm_128(H Wk));  V = H Wv
    gam  = log_sigmoid(H Wg + bg)  [T, 8];   Gam_t = sum_{r<=t} gam_r
    a_ts = (Q_t,i . K_s,j)^2 exp(Gam_t,j - Gam_s,j)   for s <= t, else 0
    Y_t,i = sum_s a_ts V_s,j / sum_s a_ts
    X'   = X + concat_i(Y) Wo
    X''  = X' + (silu(N Wgate) * (N Wup)) Wdown,   N = rmsnorm(X')

(the usual 1/sqrt(128) cancels between numerator and denominator).
`retention_recurrent` is the same layer as the recurrence a server
runs, over the textbook feature map phi(x) = (c_ab x_a x_b)_{a<=b},
D = n(n+1)/2, c_aa = 1, c_ab = sqrt(2): tests hold it to the quadratic
form, which ties phi and the gate's direction to (q.k)^2.

`forward_flops` counts the model's forward FLOPs from the
configuration's shapes: 2 FLOPs a multiply-add of every matmul, the
retention layer's reads of its state (2 (40 + 8) D 128 a token and
layer, D = 8,256), the vocabulary as published. Padding (prefill
buckets, the state's layout) and recomputation are not counted.

`precision` is "float32" (the reference), or a lower one for the
control: "bfloat16", or "int8" (weights per output channel, activations
per token, symmetric, round to nearest) on both operands of every
matmul of the layers and of the head, and on the operands of the
retention's two contractions; or "state-bfloat16", the control of the
one precision that is this layer's own (the configuration's `cache`):
everything in float32 as the reference has it, but the layer run as its
recurrence with S and z rounded to bfloat16 after every token.

Parameters are a flat dict under the program's state_dict names, which
is the only thing the two sides share.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST

_LAYER_LEAVES = (
    "input_layernorm.weight", "self_attn.q_proj.weight",
    "self_attn.k_proj.weight", "self_attn.v_proj.weight",
    "self_attn.o_proj.weight", "self_attn.g_proj.weight",
    "self_attn.g_proj.bias", "self_attn.q_norm.weight",
    "self_attn.k_norm.weight", "post_attention_layernorm.weight",
    "mlp.gate_proj.weight", "mlp.up_proj.weight", "mlp.down_proj.weight")


def gate_bias(cfg: dict):
    """The gate's bias: sigmoid(bias) spread evenly over the assumed
    range of decays across the key-value heads (the same in every
    layer). With a zero bias random weights give a decay of 0.5 a
    token and nothing older than a few tokens is ever read."""
    lo, hi = cfg["assumed"]["gate_decay_range"]
    n = cfg["num_key_value_heads"]
    decay = [lo + (hi - lo) * j / (n - 1) for j in range(n)]
    return [math.log(d / (1.0 - d)) for d in decay]


def param_table(cfg: dict) -> dict:
    """name -> (shape, init, scale) with init in normal/ones/gate."""
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    ffn, v = cfg["intermediate_size"], cfg["vocab_size"]
    # the residual writers, scaled by the PUBLISHED depth: a layer here
    # is a layer of the 40-layer model
    resid = 1.0 / math.sqrt(2.0 * cfg["assumed"]["init_residual_layers"])
    t = {"model.embed_tokens.weight": ((v, h), "normal", 1.0)}
    for l in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{l}."
        t.update({
            p + "input_layernorm.weight": ((h,), "ones", 1.0),
            p + "self_attn.q_proj.weight": ((h, nq * hd), "normal", 1.0),
            p + "self_attn.k_proj.weight": ((h, nkv * hd), "normal", 1.0),
            p + "self_attn.v_proj.weight": ((h, nkv * hd), "normal", 1.0),
            p + "self_attn.o_proj.weight": ((nq * hd, h), "normal", resid),
            p + "self_attn.g_proj.weight": ((h, nkv), "normal", 1.0),
            p + "self_attn.g_proj.bias": ((nkv,), "gate", 1.0),
            p + "self_attn.q_norm.weight": ((hd,), "ones", 1.0),
            p + "self_attn.k_norm.weight": ((hd,), "ones", 1.0),
            p + "post_attention_layernorm.weight": ((h,), "ones", 1.0),
            p + "mlp.gate_proj.weight": ((h, ffn), "normal", 1.0),
            p + "mlp.up_proj.weight": ((h, ffn), "normal", 1.0),
            p + "mlp.down_proj.weight": ((ffn, h), "normal", resid),
        })
    t.update({"model.norm.weight": ((h,), "ones", 1.0),
              "lm_head.weight": ((h, v), "normal", 1.0)})
    return t


def make_params(cfg: dict, key, dtype="bfloat16") -> dict:
    """All weights on the device in one jitted call from one key, in
    the type they are served in."""
    table = param_table(cfg)
    std = float(cfg["assumed"]["initializer_range"])
    bias = gate_bias(cfg)
    dt = jnp.dtype(dtype)

    @jax.jit
    def build(key):
        out = {}
        for n, (name, (shape, init, scale)) in enumerate(table.items()):
            if init == "normal":
                out[name] = (std * scale * jax.random.normal(
                    jax.random.fold_in(key, n), shape, jnp.float32)
                ).astype(dt)
            elif init == "ones":
                out[name] = jnp.ones(shape, dt)
            else:
                out[name] = jnp.asarray(bias, jnp.float32).astype(dt)
        return out

    return build(key)


def state_features(cfg: dict) -> int:
    """D, the size of the symmetric second tensor power of a head."""
    n = cfg["head_dim"]
    return n * (n + 1) // 2


def forward_flops(cfg: dict, first_pos: int, n_tokens: int,
                  n_heads_out: int) -> float:
    """Forward FLOPs of `n_tokens` tokens fed at positions first_pos..,
    with the LM head taken at `n_heads_out` of them (a prefill takes it
    once). A retention layer's cost a token does not depend on the
    position: the token's query heads read the state of their
    key-value head (2 * 40 * D * 128) and its key and value are added
    to it (2 * 8 * D * 128)."""
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    matmuls = (h * nq * hd + 2 * h * nkv * hd + nq * hd * h
               + 3 * h * cfg["intermediate_size"])
    state = (nq + nkv) * state_features(cfg) * hd
    return (2.0 * (matmuls + state) * cfg["num_hidden_layers"] * n_tokens
            + 2.0 * h * cfg["vocab_size"] * n_heads_out)


def _round_act(x, precision):
    if precision == "float32":
        return x
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "int8":
        s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True),
                        1e-30) / 127.0
        return jnp.round(x / s) * s
    raise ValueError(f"unknown precision {precision!r}")


def _round_weight(w, precision):
    if precision == "int8":
        s = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True),
                        1e-30) / 127.0
        return jnp.round(w / s) * s
    return _round_act(w, precision)


def _mm(x, w, precision):
    return jnp.matmul(_round_act(x, precision),
                      _round_weight(w, precision), precision=HIGHEST)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [R, T, heads, n], positions 0..T-1, rotate-half."""
    t, n = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    x1, x2 = x[..., :n // 2], x[..., n // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def retention_quadratic(q, k, v, gam, precision="float32"):
    """The layer's mixer as written above. q [R, T, nq, n];
    k, v [R, T, nkv, n]; gam [R, T, nkv] (log decays). -> [R, T, nq, n]"""
    r, t, nq, n = q.shape
    nkv = k.shape[2]
    qg = q.reshape(r, t, nkv, nq // nkv, n)
    dots = jnp.einsum("rtjgn,rsjn->rjgts", _round_act(qg, precision),
                      _round_act(k, precision), precision=HIGHEST)
    cum = jnp.cumsum(gam, axis=1)                          # Gam [R, T, nkv]
    diff = cum.transpose(0, 2, 1)[:, :, :, None] \
        - cum.transpose(0, 2, 1)[:, :, None, :]            # [R, nkv, t, s]
    causal = jnp.tril(jnp.ones((t, t), bool))
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, diff, 0.0)), 0.0)
    a = jnp.square(dots) * decay[:, :, None]               # [R,nkv,g,t,s]
    num = jnp.einsum("rjgts,rsjn->rtjgn", _round_act(a, precision),
                     _round_act(v, precision), precision=HIGHEST)
    den = jnp.sum(a, axis=-1).transpose(0, 3, 1, 2)        # [R, t, nkv, g]
    return (num / den[..., None]).reshape(r, t, nq, n)


def phi(x):
    """The symmetric second tensor power, textbook order: all pairs
    a <= b, the diagonal at weight 1 and the rest at sqrt(2), so that
    phi(x) . phi(y) = (x . y)^2. [..., n] -> [..., n (n + 1) / 2]."""
    n = x.shape[-1]
    a, b = jnp.triu_indices(n)
    c = jnp.where(a == b, 1.0, math.sqrt(2.0))
    return c * x[..., a] * x[..., b]


def phi_full(x):
    """The whole second tensor power, x x^T flattened: n^2 features,
    each pair a != b twice at weight 1 where `phi` has it once at
    sqrt(2), so phi_full(x) . phi_full(y) = (x . y)^2 as well. The same
    state element for element (the twin of an element holds the same
    value and rounds alike), with no gather: the form the
    "state-bfloat16" control runs at the cell's size."""
    return (x[..., :, None] * x[..., None, :]).reshape(
        x.shape[:-1] + (x.shape[-1] ** 2,))


def retention_recurrent(q, k, v, gam, precision="float32", features=phi,
                        state_dtype=jnp.float32):
    """The same mixer as the recurrence over the state
    S_t = e^{gam_t} S_{t-1} + phi(k_t) v_t^T, z_t alike,
    y_t = phi(q_t)^T S_t / phi(q_t)^T z_t. Shapes as
    `retention_quadratic`; float32 arithmetic only, S and z kept in
    `state_dtype` between tokens; for the tests and the state's control
    (it is slow)."""
    assert precision == "float32", precision
    r, t, nq, n = q.shape
    nkv = k.shape[2]
    f32 = jnp.float32

    def step(carry, xs):
        s, z = carry
        q_t, k_t, v_t, g_t = xs
        fq_t, fk_t = features(q_t), features(k_t)          # [R,j,g,D] [R,j,D]
        g = jnp.exp(g_t)
        s = (g[..., None, None] * s.astype(f32)
             + fk_t[..., :, None] * v_t[..., None, :]).astype(state_dtype)
        z = (g[..., None] * z.astype(f32) + fk_t).astype(state_dtype)
        num = jnp.einsum("rjgd,rjdn->rjgn", fq_t, s.astype(f32),
                         precision=HIGHEST)
        den = jnp.einsum("rjgd,rjd->rjg", fq_t, z.astype(f32),
                         precision=HIGHEST)
        return (s, z), num / den[..., None]

    d = features(k[:1, :1]).shape[-1]
    init = (jnp.zeros((r, nkv, d, n), state_dtype),
            jnp.zeros((r, nkv, d), state_dtype))
    seq = tuple(jnp.moveaxis(x, 1, 0) for x in
                (q.reshape(r, t, nkv, nq // nkv, n), k, v, gam))
    _, y = jax.lax.scan(step, init, seq)
    return jnp.moveaxis(y, 0, 1).reshape(r, t, nq, n)


STATE_BF16 = "state-bfloat16"
_STATE_BF16_MIXER = functools.partial(
    retention_recurrent, features=phi_full, state_dtype=jnp.bfloat16)


def layer(x, lp, cfg_static, precision, mixer=retention_quadratic):
    """One layer over x [R, T, H]; `lp` the layer's leaves by their
    short names."""
    nq, nkv, hd, eps, theta = cfg_static
    r, t, _ = x.shape
    mm = functools.partial(_mm, precision=precision)
    h = _rms_norm(x, lp["input_layernorm.weight"], eps)
    q = mm(h, lp["self_attn.q_proj.weight"]).reshape(r, t, nq, hd)
    k = mm(h, lp["self_attn.k_proj.weight"]).reshape(r, t, nkv, hd)
    v = mm(h, lp["self_attn.v_proj.weight"]).reshape(r, t, nkv, hd)
    q = _rope(_rms_norm(q, lp["self_attn.q_norm.weight"], eps), theta)
    k = _rope(_rms_norm(k, lp["self_attn.k_norm.weight"], eps), theta)
    gam = jax.nn.log_sigmoid(mm(h, lp["self_attn.g_proj.weight"])
                             + lp["self_attn.g_proj.bias"])
    y = mixer(q, k, v, gam, precision)
    x = x + mm(y.reshape(r, t, nq * hd), lp["self_attn.o_proj.weight"])
    n = _rms_norm(x, lp["post_attention_layernorm.weight"], eps)
    f = jax.nn.silu(mm(n, lp["mlp.gate_proj.weight"])) \
        * mm(n, lp["mlp.up_proj.weight"])
    return x + mm(f, lp["mlp.down_proj.weight"])


def _static(cfg):
    return (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], float(cfg["rms_norm_eps"]),
            float(cfg["rope_theta"]))


@functools.partial(jax.jit, static_argnames=("cfg_static", "precision",
                                             "recurrent"))
def _layer(x, lp, cfg_static, precision, recurrent=False):
    """One jitted layer: the weights of ONE layer are made float32 at a
    time (all of them at once would be 11.5 GB beside the 5.75 GB they
    are served in)."""
    lp = {k: v.astype(jnp.float32) for k, v in lp.items()}
    if precision == STATE_BF16:
        return layer(x, lp, cfg_static, "float32", _STATE_BF16_MIXER)
    return layer(x, lp, cfg_static, precision,
                 retention_recurrent if recurrent else retention_quadratic)


def hidden(params, cfg, tokens, precision="float32", recurrent=False):
    """tokens [R, T] -> the last layer's output [R, T, H], float32."""
    x = params["model.embed_tokens.weight"][tokens].astype(jnp.float32)
    for l in range(cfg["num_hidden_layers"]):
        lp = {n: params[f"model.layers.{l}.{n}"] for n in _LAYER_LEAVES}
        x = _layer(x, lp, _static(cfg), precision, recurrent)
    return x


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, w_norm, w_head, eps, precision):
    h = _rms_norm(x, w_norm.astype(jnp.float32), eps)
    return _mm(h, w_head.astype(jnp.float32), precision)


def logits(params, cfg, tokens, precision="float32", recurrent=False):
    """tokens [R, T] -> logits [R, T, V] in float32."""
    return _head(hidden(params, cfg, tokens, precision, recurrent),
                 params["model.norm.weight"], params["lm_head.weight"],
                 float(cfg["rms_norm_eps"]),
                 "float32" if precision == STATE_BF16 else precision)


@jax.jit
def _read(lg, picks):
    """logits [R, T, V] -> per position: the best logit, its index, the
    logits at `picks` [K, R, T], and the margin of the best logit over
    the second."""
    best = jnp.max(lg, axis=-1)
    first = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    ids = jax.lax.broadcasted_iota(jnp.int32, lg.shape, 2)
    second = jnp.max(jnp.where(ids == first[..., None], -jnp.inf, lg),
                     axis=-1)
    picked = jnp.take_along_axis(
        lg[None], picks[..., None].astype(jnp.int32), axis=-1)[..., 0]
    return best, first, picked, best - second


def score(params, cfg, tokens, picks, precision="float32", block_rows=4):
    """`_read` of the logits over blocks of rows, so that a block's
    [rows, T, vocab] logits fit; numpy in, numpy out."""
    import numpy as np
    outs = []
    for i in range(0, tokens.shape[0], block_rows):
        lg = logits(params, cfg, jnp.asarray(tokens[i:i + block_rows]),
                    precision)
        outs.append(jax.device_get(
            _read(lg, jnp.asarray(picks[:, i:i + block_rows]))))
    return (np.concatenate([o[0] for o in outs], axis=0),
            np.concatenate([o[1] for o in outs], axis=0),
            np.concatenate([o[2] for o in outs], axis=1),
            np.concatenate([o[3] for o in outs], axis=0))
