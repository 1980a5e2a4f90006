"""Plain reference for `mellum2-12b-a2.5b`: the full forward pass of a
stack of grouped-query attention layers and routed-expert layers over a
prompt and its served tokens, in straightforward float32 jax.numpy at
`highest` matmul precision. No cache, no kernel, no buckets, no batch of
requests: attention is computed in blocks of queries and each token's
experts for that token alone; it imports nothing of the program.

The stack is JetBrains' Mellum2-12B-A2.5B-Instruct (its published
config.json), the Qwen3-MoE form: RMSNorm (eps 1e-6), no biases, 32
query and 4 key-value heads of 128 (query head i reads key-value head
i // 8), a per-head RMSNorm on q and k, rotary positions (rotate-half,
theta 500,000), 64 routed experts of width 896 a layer, 8 a token, no
shared expert, an untied head. For one layer, with its kind from
`layer_types`:

    H    = rmsnorm(X)
    Q, K = rope(rmsnorm_128(H Wq)), rope(rmsnorm_128(H Wk));  V = H Wv
    Y_t  = softmax_s(Q_t . K_s / sqrt(128)) V_s over the keys s it sees:
           sliding_attention  0 <= t - s < 1024, plain rotary angles
           full_attention     s <= t, YaRN's angles (factor 16,
                              original 8,192 positions, beta 32 / 1),
                              cos and sin times 1.2773
    X'   = X + concat(Y) Wo
    N    = rmsnorm(X');  p = softmax(N Wr) over all 64 experts
    T    = the 8 largest of p (ties to the lower id), w_e = p_e / sum_T p
    X''  = X' + sum_{e in T} w_e (silu(N Wgate_e) * (N Wup_e)) Wdown_e

What the config has no key for is the configuration file's `assumed`.
The experts are computed over per-expert lists of the tokens routed to
each, in chunks of `EXPERT_ROWS` rows: a token's row meets only its own
8 experts.

`forward_flops` counts the model's forward FLOPs from the
configuration's shapes: 2 FLOPs a multiply-add of every matmul, 8 of
the 64 experts a token and the router, attention over the keys a query
sees (all before it on a full layer, at most 1,024 on a sliding one:
4 x 32 x 128 a key), the vocabulary as published. Padding and
recomputation are not counted.

`precision` is "float32" (the reference), or a lower one for the
control: "bfloat16", or "int8" (weights per output channel,
activations per token, symmetric, round to nearest) on both operands of
every matmul: the projections, the router, the experts, the attention's
two contractions and the head.

Parameters are a flat dict under the program's state_dict names, which
is the only thing the two sides share.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
# query rows a block of attention takes; token rows an expert takes at
# a time (the last chunk of an expert's list is padded)
QUERY_BLOCK = 512
EXPERT_ROWS = 256
# the reference pads a sequence to a multiple of this, so that a few
# shapes compile (the tail's tokens come after every served position,
# and nothing attends forward)
PAD_TO = 1024
# the positions at the end of a scored row whose logits are taken
HEAD_TAIL = 256

_LAYER_LEAVES = (
    "input_layernorm.weight", "self_attn.q_proj.weight",
    "self_attn.k_proj.weight", "self_attn.v_proj.weight",
    "self_attn.o_proj.weight", "self_attn.q_norm.weight",
    "self_attn.k_norm.weight", "post_attention_layernorm.weight",
    "mlp.gate.weight", "mlp.experts.gate_up_proj",
    "mlp.experts.down_proj")


def param_table(cfg: dict) -> dict:
    """name -> (shape, init, scale) with init in normal/ones."""
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    e, f, v = cfg["num_experts"], cfg["moe_intermediate_size"], \
        cfg["vocab_size"]
    # the residual writers, scaled by the PUBLISHED depth: a layer here
    # is a layer of the 28-layer model
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
            p + "self_attn.q_norm.weight": ((hd,), "ones", 1.0),
            p + "self_attn.k_norm.weight": ((hd,), "ones", 1.0),
            p + "post_attention_layernorm.weight": ((h,), "ones", 1.0),
            p + "mlp.gate.weight": ((h, e), "normal", 1.0),
            p + "mlp.experts.gate_up_proj": ((e, h, 2 * f), "normal", 1.0),
            p + "mlp.experts.down_proj": ((e, f, h), "normal", resid),
        })
    t.update({"model.norm.weight": ((h,), "ones", 1.0),
              "lm_head.weight": ((h, v), "normal", 1.0)})
    return t


def make_params(cfg: dict, key, dtype="bfloat16") -> dict:
    """All weights on the device in one jitted call from one key, in
    the type they are served in."""
    table = param_table(cfg)
    std = float(cfg["assumed"]["initializer_range"])
    dt = jnp.dtype(dtype)

    @jax.jit
    def build(key):
        out = {}
        for n, (name, (shape, init, scale)) in enumerate(table.items()):
            if init == "normal":
                out[name] = (std * scale * jax.random.normal(
                    jax.random.fold_in(key, n), shape, jnp.float32)
                ).astype(dt)
            else:
                out[name] = jnp.ones(shape, dt)
        return out

    return build(key)


def _window_of(cfg: dict, layer: int):
    kind = cfg["layer_types"][layer]
    return cfg["sliding_window"] if kind == "sliding_attention" else None


def forward_flops(cfg: dict, first_pos: int, n_tokens: int,
                  n_heads_out: int) -> float:
    """Forward FLOPs of `n_tokens` tokens fed at positions first_pos..,
    with the LM head taken at `n_heads_out` of them (a prefill takes it
    once)."""
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    matmuls = (2 * h * nq * hd + 2 * h * nkv * hd + h * cfg["num_experts"]
               + cfg["num_experts_per_tok"] * 3 * h
               * cfg["moe_intermediate_size"])
    last = first_pos + n_tokens
    keys = 0.0
    for layer in range(cfg["num_hidden_layers"]):
        w = _window_of(cfg, layer)
        if w is None:
            keys += sum_over(first_pos, last)
        else:
            # t + 1 keys while t < w, then w
            keys += sum_over(first_pos, min(last, w)) \
                + w * max(0, last - max(first_pos, w))
    return (2.0 * matmuls * cfg["num_hidden_layers"] * n_tokens
            + 4.0 * nq * hd * keys
            + 2.0 * h * cfg["vocab_size"] * n_heads_out)


def sum_over(a: int, b: int) -> float:
    """The sum of t + 1 over t in [a, b): the keys of queries a..b-1
    that each see every key before them."""
    if b <= a:
        return 0.0
    return (b * (b + 1) - a * (a + 1)) / 2.0


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
        s = jnp.maximum(jnp.max(jnp.abs(w), axis=-2, keepdims=True),
                        1e-30) / 127.0
        return jnp.round(w / s) * s
    return _round_act(w, precision)


def _mm(x, w, precision):
    return jnp.matmul(_round_act(x, precision),
                      _round_weight(w, precision), precision=HIGHEST)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def rope_angles(n: int, theta: float, t: int, yarn=None):
    """[t, n] angles of positions 0..t-1, both halves alike, and the
    factor cos and sin are multiplied by. `yarn`: the rope_parameters
    entry of a YaRN layer (transformers' `_compute_yarn_parameters`)."""
    freqs = theta ** (jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    inv = 1.0 / freqs
    scale = 1.0
    if yarn is not None:
        factor, orig = yarn["factor"], yarn["original_max_position_embeddings"]

        def dim(rotations):
            return n * math.log(orig / (rotations * 2 * math.pi)) \
                / (2 * math.log(theta))

        low = max(math.floor(dim(yarn["beta_fast"])), 0)
        high = min(math.ceil(dim(yarn["beta_slow"])), n - 1)
        if low == high:
            high += 0.001
        ramp = jnp.clip((jnp.arange(n // 2, dtype=jnp.float32) - low)
                        / (high - low), 0.0, 1.0)
        extrapolate = 1.0 - ramp
        inv = inv / factor * (1.0 - extrapolate) + inv * extrapolate
        scale = yarn["attention_factor"]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.concatenate([ang, ang], axis=-1), scale


def _rope(x, ang, scale):
    """x [R, T, heads, n]."""
    n = x.shape[-1]
    ang = ang[None, :, None, :]
    x1, x2 = x[..., :n // 2], x[..., n // 2:]
    return (x * jnp.cos(ang)
            + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)) * scale


def attention(q, k, v, window, precision):
    """q [R, T, nq, n], k, v [R, T, nkv, n] -> [R, T, nq, n]: each block
    of QUERY_BLOCK queries over the keys it can see (on a sliding layer
    the band of `window` below the block, on a full one every key),
    masked to s <= t (and t - s < window)."""
    r, t0, nq, n = q.shape
    nkv = k.shape[2]
    # whole blocks of queries: the padding comes after every real token
    t = -(-t0 // QUERY_BLOCK) * QUERY_BLOCK
    q, k, v = (jnp.pad(a, ((0, 0), (0, t - t0), (0, 0), (0, 0)))
               for a in (q, k, v))
    qg = _round_act(q, precision).reshape(r, t, nkv, nq // nkv, n)
    k, v = _round_act(k, precision), _round_act(v, precision)
    span = t if window is None else min(t, window + QUERY_BLOCK)
    # keys from `first`: sliding blocks read a band of `span` keys
    pad = span - QUERY_BLOCK if window is not None else 0
    kp = jnp.pad(k, ((0, 0), (pad, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (pad, 0), (0, 0), (0, 0)))

    def one(start):
        qb = jax.lax.dynamic_slice_in_dim(qg, start, QUERY_BLOCK, axis=1)
        first = 0 if window is None else start + QUERY_BLOCK - span
        kb = jax.lax.dynamic_slice_in_dim(kp, first + pad, span, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(vp, first + pad, span, axis=1)
        sc = jnp.einsum("rtjgn,rsjn->rjgts", qb, kb,
                        precision=HIGHEST) / math.sqrt(n)
        tq = start + jnp.arange(QUERY_BLOCK)[:, None]
        sk = first + jnp.arange(span)[None, :]
        live = (sk <= tq) & (sk >= 0)
        if window is not None:
            live = live & (tq - sk < window)
        p = jax.nn.softmax(jnp.where(live, sc, -jnp.inf), axis=-1)
        return jnp.einsum("rjgts,rsjn->rtjgn", _round_act(p, precision),
                          vb, precision=HIGHEST)

    out = jax.lax.map(one, jnp.arange(0, t, QUERY_BLOCK))   # [nb, R, ...]
    return jnp.moveaxis(out, 0, 1).reshape(r, t, nq, n)[:, :t0]


def experts(x, lp, cs, precision):
    """x [N, H] (post-attention norm's output) -> the routed experts'
    weighted sum [N, H]. Router probabilities over all experts in f32,
    each token's top k (ties to the lower id), renormalised; then for
    each expert the list of tokens routed to it, in chunks of
    EXPERT_ROWS rows, through that expert alone."""
    n_exp, k, f, _ = cs
    n, hid = x.shape
    p = jax.nn.softmax(_mm(x, lp["mlp.gate.weight"], precision), axis=-1)
    w, e = jax.lax.top_k(p, k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    e, w = e.reshape(-1), w.reshape(-1)
    tok = jnp.arange(n * k) // k
    # each row's place in the rows listed by expert (the order they come
    # in within one): a running count, no sort
    running = jnp.cumsum((e[:, None] == jnp.arange(n_exp)[None, :])
                         .astype(jnp.int32), axis=0)
    counts = running[-1]
    starts = jnp.cumsum(counts) - counts
    place = starts[e] + jnp.take_along_axis(running, e[:, None], axis=1)[:, 0]
    order = jnp.zeros((n * k,), jnp.int32).at[place - 1].set(
        jnp.arange(n * k, dtype=jnp.int32))
    chunks = -(-counts // EXPERT_ROWS)
    ends = jnp.cumsum(chunks)
    order = jnp.pad(order, (0, EXPERT_ROWS))
    gu_all, down_all = lp["mlp.experts.gate_up_proj"], \
        lp["mlp.experts.down_proj"]

    def body(c, out):
        ex = jnp.sum(c >= ends)                         # this chunk's expert
        exc = jnp.minimum(ex, n_exp - 1)
        within = c - (ends[exc] - chunks[exc])
        lo = starts[exc] + within * EXPERT_ROWS
        rows = jax.lax.dynamic_slice_in_dim(order, lo, EXPERT_ROWS)
        live = (jnp.arange(EXPERT_ROWS) < counts[exc] - within * EXPERT_ROWS) \
            & (ex < n_exp)
        xs = x[tok[rows]]
        gu = _mm(xs, gu_all[exc], precision)
        a = jax.nn.silu(gu[:, :f]) * gu[:, f:]
        y = _mm(a, down_all[exc], precision)
        return out.at[tok[rows]].add(
            jnp.where(live, w[rows], 0.0)[:, None] * y)

    most = n * k // EXPERT_ROWS + n_exp
    return jax.lax.fori_loop(0, most, body, jnp.zeros((n, hid), jnp.float32))


def layer(x, lp, cs, precision, window, rope):
    """One layer over x [R, T, H]; `lp` the layer's leaves by their
    short names, `rope` (angles, factor)."""
    nq, nkv, hd, eps = cs[0]
    r, t, _ = x.shape
    mm = functools.partial(_mm, precision=precision)
    h = _rms_norm(x, lp["input_layernorm.weight"], eps)
    q = mm(h, lp["self_attn.q_proj.weight"]).reshape(r, t, nq, hd)
    k = mm(h, lp["self_attn.k_proj.weight"]).reshape(r, t, nkv, hd)
    v = mm(h, lp["self_attn.v_proj.weight"]).reshape(r, t, nkv, hd)
    q = _rope(_rms_norm(q, lp["self_attn.q_norm.weight"], eps), *rope)
    k = _rope(_rms_norm(k, lp["self_attn.k_norm.weight"], eps), *rope)
    y = attention(q, k, v, window, precision)
    x = x + mm(y.reshape(r, t, nq * hd), lp["self_attn.o_proj.weight"])
    n = _rms_norm(x, lp["post_attention_layernorm.weight"], eps)
    return x + experts(n.reshape(r * t, -1), lp, cs[1],
                       precision).reshape(x.shape)


def _static(cfg):
    return ((cfg["num_attention_heads"], cfg["num_key_value_heads"],
             cfg["head_dim"], float(cfg["rms_norm_eps"])),
            (cfg["num_experts"], cfg["num_experts_per_tok"],
             cfg["moe_intermediate_size"], cfg["hidden_size"]))


def _rope_key(cfg, layer):
    """A hashable description of a layer's rotary form."""
    kind = cfg["layer_types"][layer]
    p = cfg["rope_parameters"][kind]
    yarn = None if p["rope_type"] == "default" else tuple(sorted(
        (k, v) for k, v in p.items() if k != "rope_type"))
    return float(p["rope_theta"]), yarn


@functools.partial(jax.jit, static_argnames=("cs", "precision", "window",
                                             "rope_key"))
def _layer(x, lp, cs, precision, window, rope_key):
    """One jitted layer: the weights of ONE layer are made float32 at a
    time (all of them at once would be 15 GB beside the 7.6 GB they are
    served in)."""
    lp = {k: v.astype(jnp.float32) for k, v in lp.items()}
    theta, yarn = rope_key
    rope = rope_angles(cs[0][2], theta, x.shape[1],
                       None if yarn is None else dict(yarn))
    return layer(x, lp, cs, precision, window, rope)


def hidden(params, cfg, tokens, precision="float32"):
    """tokens [R, T] -> the last layer's output [R, T, H], float32."""
    x = params["model.embed_tokens.weight"][tokens].astype(jnp.float32)
    for l in range(cfg["num_hidden_layers"]):
        lp = {n: params[f"model.layers.{l}.{n}"] for n in _LAYER_LEAVES}
        x = _layer(x, lp, _static(cfg), precision, _window_of(cfg, l),
                   _rope_key(cfg, l))
    return x


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, w_norm, w_head, eps, precision):
    h = _rms_norm(x, w_norm.astype(jnp.float32), eps)
    return _mm(h, w_head.astype(jnp.float32), precision)


def logits(params, cfg, tokens, precision="float32"):
    """tokens [R, T] -> logits [R, T, V] in float32."""
    return _head(hidden(params, cfg, tokens, precision),
                 params["model.norm.weight"], params["lm_head.weight"],
                 float(cfg["rms_norm_eps"]), precision)


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


def score(params, cfg, tokens, picks, precision="float32", block_rows=1):
    """`_read` of the logits over blocks of rows, each row padded to a
    multiple of PAD_TO tokens (the padding after every real one, so it
    changes no real position); numpy in, numpy out. The head is taken
    at the last HEAD_TAIL positions of the T given only: the benchmark
    groups requests by their length rounded up to 128 (perfbench/lib/
    correct.py, `by_length`), so a request's served positions lie in
    the last 128 + its new tokens (at most 96 in this configuration's
    traffic). Every position before them reads NaN, which no
    comparison passes."""
    import numpy as np
    t = tokens.shape[1]
    tail = min(t, HEAD_TAIL)
    tp = -(-t // PAD_TO) * PAD_TO
    tokens = np.pad(tokens, ((0, 0), (0, tp - t)))
    outs = []
    for i in range(0, tokens.shape[0], block_rows):
        x = hidden(params, cfg, jnp.asarray(tokens[i:i + block_rows]),
                   precision)[:, t - tail:t]
        lg = _head(x, params["model.norm.weight"], params["lm_head.weight"],
                   float(cfg["rms_norm_eps"]), precision)
        outs.append(jax.device_get(
            _read(lg, jnp.asarray(picks[:, i:i + block_rows, t - tail:]))))

    def whole(a, axis):
        pad = [(0, 0)] * a.ndim
        pad[axis] = (t - tail, 0)
        fill = -1 if a.dtype.kind == "i" else np.nan
        return np.pad(a, pad, constant_values=fill)

    return (whole(np.concatenate([o[0] for o in outs], axis=0), 1),
            whole(np.concatenate([o[1] for o in outs], axis=0), 1),
            whole(np.concatenate([o[2] for o in outs], axis=1), 2),
            whole(np.concatenate([o[3] for o in outs], axis=0), 1))
