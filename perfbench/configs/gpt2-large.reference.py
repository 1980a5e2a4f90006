"""Plain reference for `gpt2-large`: the GPT-2 decoder's full forward
pass over a prompt and its served tokens, in straightforward float32
jax.numpy at `highest` matmul precision. No cache, no pages, no
batching by a scheduler, no buckets; it imports nothing of the program.

Follows Radford et al. 2019 and the released model code: learned
position embeddings, pre-LayerNorm blocks, fused qkv, causal softmax
attention, a 4x MLP, a final LayerNorm and a head tied to the token
embeddings. The GELU is the one the configuration's file states: the
erf form, which is what the program computes, where the release has
the tanh form (`activation_function` is under `reduced`).

`forward_flops` counts the model's forward FLOPs from the
configuration's shapes, for the whole step's share of peak: 2 FLOPs per
multiply-add of every matmul, attention at the true head size and true
context, the vocabulary as published. Padding (vocabulary 50,257 ->
50,304, prefill buckets) and recomputation are not counted.

`precision` is "float32" (the reference), or a lower one for the
control: "bfloat16", or "int8" (weights per output channel, activations
per token, symmetric, round to nearest) on both operands of every
matmul of the blocks and of the head.

Parameters are a flat dict under the program's state_dict names, which
is the only thing the two sides share.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def param_table(cfg: dict) -> dict:
    """name -> (shape, init, scale) with init in normal/zeros/ones."""
    d = cfg["n_embd"]
    v = cfg["assumed"]["padded_vocab_size"]
    resid = 1.0 / math.sqrt(2.0 * cfg["n_layer"])
    t = {"gpt.wte.weight": ((v, d), "normal", 1.0),
         "gpt.wpe.weight": ((cfg["n_positions"], d), "normal", 1.0)}
    for l in range(cfg["n_layer"]):
        p = f"gpt.blocks.{l}."
        t.update({
            p + "ln1.weight": ((d,), "ones", 1.0),
            p + "ln1.bias": ((d,), "zeros", 1.0),
            p + "ln2.weight": ((d,), "ones", 1.0),
            p + "ln2.bias": ((d,), "zeros", 1.0),
            p + "qkv.weight": ((d, 3 * d), "normal", 1.0),
            p + "qkv.bias": ((3 * d,), "zeros", 1.0),
            p + "proj.weight": ((d, d), "normal", resid),
            p + "proj.bias": ((d,), "zeros", 1.0),
            p + "fc1.weight": ((d, 4 * d), "normal", 1.0),
            p + "fc1.bias": ((4 * d,), "zeros", 1.0),
            p + "fc2.weight": ((4 * d, d), "normal", resid),
            p + "fc2.bias": ((d,), "zeros", 1.0),
        })
    t.update({"gpt.ln_f.weight": ((d,), "ones", 1.0),
              "gpt.ln_f.bias": ((d,), "zeros", 1.0)})
    return t


def make_params(cfg: dict, key, dtype="bfloat16") -> dict:
    """All weights on the device in one jitted call from one key, in
    the type they are served in."""
    table = param_table(cfg)
    std = float(cfg["initializer_range"])
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
                out[name] = jnp.zeros(shape, dt)
        return out

    return build(key)


def forward_flops(cfg: dict, first_pos: int, n_tokens: int,
                  n_heads_out: int) -> float:
    """Forward FLOPs of `n_tokens` tokens fed at positions first_pos..,
    each attending to itself and everything before it, with the LM head
    taken at `n_heads_out` of them (a prefill takes it once)."""
    d, layers = cfg["n_embd"], cfg["n_layer"]
    last = first_pos + n_tokens
    # sum over t in [first_pos, last) of (t + 1) keys
    keys = (last * (last + 1) - first_pos * (first_pos + 1)) / 2.0
    return (2.0 * 12 * d * d * layers * n_tokens
            + 4.0 * d * layers * keys
            + 2.0 * d * cfg["vocab_size"] * n_heads_out)


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


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


_BLOCK_LEAVES = ("ln1.weight", "ln1.bias", "ln2.weight", "ln2.bias",
                 "qkv.weight", "qkv.bias", "proj.weight", "proj.bias",
                 "fc1.weight", "fc1.bias", "fc2.weight", "fc2.bias")


@functools.partial(jax.jit, static_argnames=("n_layer", "n_head", "eps",
                                             "precision"))
def _score(params, tokens, picks, n_layer, n_head, eps, precision):
    """tokens [R, T] -> per position: the best logit, its index, the
    logits at `picks` [K, R, T], and the margin of the best logit over
    the second."""
    f32 = {k: v.astype(jnp.float32) for k, v in params.items()}
    r, t = tokens.shape
    d = f32["gpt.wte.weight"].shape[1]
    hd = d // n_head
    mm = functools.partial(_mm, precision=precision)
    x = f32["gpt.wte.weight"][tokens] + f32["gpt.wpe.weight"][:t][None]
    causal = jnp.tril(jnp.ones((t, t), bool))
    stack = {n: jnp.stack([f32[f"gpt.blocks.{l}.{n}"]
                           for l in range(n_layer)])
             for n in _BLOCK_LEAVES}

    def block(x, lp):
        a = _layer_norm(x, lp["ln1.weight"], lp["ln1.bias"], eps)
        qkv = (mm(a, lp["qkv.weight"]) + lp["qkv.bias"]).reshape(
            r, t, 3, n_head, hd)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        att = jnp.einsum("bqnd,bknd->bnqk", _round_act(q, precision),
                         _round_act(k, precision),
                         precision=HIGHEST) / math.sqrt(hd)
        att = jnp.where(causal[None, None], att, -jnp.inf)
        pr = jax.nn.softmax(att, axis=-1)
        ctx = jnp.einsum("bnqk,bknd->bqnd", _round_act(pr, precision),
                         _round_act(v, precision), precision=HIGHEST)
        x = x + mm(ctx.reshape(r, t, d), lp["proj.weight"]) \
            + lp["proj.bias"]
        f = _layer_norm(x, lp["ln2.weight"], lp["ln2.bias"], eps)
        f = jax.nn.gelu(mm(f, lp["fc1.weight"]) + lp["fc1.bias"],
                        approximate=False)
        return x + mm(f, lp["fc2.weight"]) + lp["fc2.bias"], None

    x, _ = jax.lax.scan(block, x, stack)
    h = _layer_norm(x, f32["gpt.ln_f.weight"], f32["gpt.ln_f.bias"], eps)
    logits = mm(h, f32["gpt.wte.weight"].T)              # [R, T, V]
    best = jnp.max(logits, axis=-1)
    first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    ids = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
    second = jnp.max(jnp.where(ids == first[..., None], -jnp.inf, logits),
                     axis=-1)
    margin = best - second
    picked = jnp.take_along_axis(
        logits[None], picks[..., None].astype(jnp.int32), axis=-1)[..., 0]
    return best, first, picked, margin


def score(params, cfg, tokens, picks, precision="float32", block_rows=4):
    """Run `_score` over blocks of rows; numpy in, numpy out."""
    import numpy as np
    outs = []
    for i in range(0, tokens.shape[0], block_rows):
        outs.append(jax.device_get(_score(
            params, jnp.asarray(tokens[i:i + block_rows]),
            jnp.asarray(picks[:, i:i + block_rows]),
            n_layer=cfg["n_layer"], n_head=cfg["n_head"],
            eps=float(cfg["layer_norm_epsilon"]), precision=precision)))
    return (np.concatenate([o[0] for o in outs], axis=0),
            np.concatenate([o[1] for o in outs], axis=0),
            np.concatenate([o[2] for o in outs], axis=1),
            np.concatenate([o[3] for o in outs], axis=0))
