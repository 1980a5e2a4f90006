"""Autoregressive generation with a KV cache — one compiled decode loop.

Reference decoding surface: beam_search ops
(/root/reference/paddle/fluid/operators/beam_search_op.cc, exposed via
layers/rnn.py dynamic_decode) driven one step at a time from Python —
every step is an executor round-trip. The TPU-native form is ONE jitted
program: prefill computes the prompt's per-layer K/V into a
statically-shaped cache, then a `lax.scan` over decode steps updates the
cache in place (`dynamic_update_slice`) and attends over the valid
prefix with an iota mask. Static shapes throughout: the cache is sized
to prompt_len + max_new_tokens, finished rows keep emitting pad — XLA
compiles the whole generation once per (batch, prompt_len,
max_new_tokens) signature.

Supports greedy and temperature/top-k sampling over GPTForCausalLM
(weight-tied head). Correctness contract: greedy decode through the
cache equals argmax over full re-forward logits at every step
(tests/test_generation.py).

This module is ALSO the numerical reference for the continuous-batching
serving engine: paddle_tpu/serving/programs.py imports `_ln`, `_attend`,
`_prefill`, `_pick` (and the engine `_gpt_params`/`_cast_params`) so the
paged-cache decode is the same ops in the same order with only the cache
addressing changed — that reuse is what makes the paged-vs-dense greedy
parity contract bit-exact in f32 (tests/test_serving_engine.py). That
holds of the portable path; on a TPU the engine's decode attention is
the paged Pallas kernel, for which `_attend` over the gathered pages is
the reference (tests/test_paged_decode_attention.py). A change to these
helpers must keep all three suites green.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..framework import Tensor

__all__ = ["generate_gpt"]


def _ln(x, w, b, eps):
    # moments in f32 regardless of storage dtype: bf16 serving (the
    # dtype= cast below) would otherwise lose layernorm precision
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return (((xf - mu) / jnp.sqrt(var + eps)).astype(x.dtype) * w + b)


def _block_params(blk):
    return {
        "ln1_w": blk.ln1.weight._data, "ln1_b": blk.ln1.bias._data,
        "ln2_w": blk.ln2.weight._data, "ln2_b": blk.ln2.bias._data,
        "qkv_w": blk.qkv.weight._data, "qkv_b": blk.qkv.bias._data,
        "proj_w": blk.proj.weight._data, "proj_b": blk.proj.bias._data,
        "fc1_w": blk.fc1.weight._data, "fc1_b": blk.fc1.bias._data,
        "fc2_w": blk.fc2.weight._data, "fc2_b": blk.fc2.bias._data,
    }


# decode-key -> state_dict-name, derived from the one layout table in
# _block_params so a GPTBlock param rename can't go stale here
_SCAN_BLOCK_KEYS = {
    k: k[:-2] + (".weight" if k.endswith("_w") else ".bias")
    for k in ("ln1_w", "ln1_b", "ln2_w", "ln2_b", "qkv_w", "qkv_b",
              "proj_w", "proj_b", "fc1_w", "fc1_b", "fc2_w", "fc2_b")
}


def _gpt_params(model):
    gpt = model.gpt
    from ..nn.layer.scanned import ScannedStack
    if isinstance(gpt.blocks, ScannedStack):
        # scan_layers: slice the [L, ...] stacks into per-layer dicts —
        # the decode loop is already per-layer, so generation works
        # identically off either parameter layout
        stk = gpt.blocks
        get = {k: getattr(stk, stk._mangled[n])._data
               for k, n in _SCAN_BLOCK_KEYS.items()}
        blocks = [{k: v[i] for k, v in get.items()}
                  for i in range(stk.L)]
    else:
        blocks = [_block_params(b) for b in gpt.blocks]
    return {
        "wte": gpt.wte.weight._data,
        "wpe": gpt.wpe.weight._data,
        "lnf_w": gpt.ln_f.weight._data, "lnf_b": gpt.ln_f.bias._data,
        "blocks": blocks,
    }


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


def _attend(q, kc, vc, n_valid, scale):
    """q [B,N,1,hd] over cache kc/vc [B,N,T,hd], masked to n_valid
    (scalar, or [B] for ragged per-row prompt lengths)."""
    s = jnp.einsum("bnqh,bnkh->bnqk", q, kc) * scale
    pos = jnp.arange(kc.shape[2])
    if getattr(n_valid, "ndim", 0):
        mask = pos[None, None, None, :] < n_valid[:, None, None, None]
    else:
        mask = pos[None, None, None, :] < n_valid
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bnqk,bnkh->bnqh", p, vc)


def _step_hidden(params, eps, n_heads, x, caches, pos):
    """One token's hidden state through all blocks, updating caches.

    x: [B, 1, H]; caches: list of (k [B,N,T,hd], v [B,N,T,hd]);
    pos: index where this token's K/V land — a scalar (uniform
    prompts) or [B] (ragged prompts: each row writes at its own next
    position and attends over its own valid prefix)."""
    new_caches = []
    hd = x.shape[-1] // n_heads
    scale = 1.0 / math.sqrt(hd)
    ragged = bool(getattr(pos, "ndim", 0))
    for bp, (kc, vc) in zip(params["blocks"], caches):
        b = x.shape[0]
        xn = _ln(x, bp["ln1_w"], bp["ln1_b"], eps)
        qkv = (_mm(xn, bp, "qkv") + bp["qkv_b"]).reshape(
            b, 1, 3, n_heads, hd)
        q = jnp.einsum("bsnh->bnsh", qkv[:, :, 0])
        k = jnp.einsum("bsnh->bnsh", qkv[:, :, 1])
        v = jnp.einsum("bsnh->bnsh", qkv[:, :, 2])
        if ragged:
            # per-row scatter: row i writes its K/V at pos[i]
            bi = jnp.arange(b)
            kc = kc.at[bi, :, pos].set(k[:, :, 0])
            vc = vc.at[bi, :, pos].set(v[:, :, 0])
        else:
            kc = jax.lax.dynamic_update_slice_in_dim(kc, k, pos,
                                                     axis=2)
            vc = jax.lax.dynamic_update_slice_in_dim(vc, v, pos,
                                                     axis=2)
        ctx = _attend(q, kc, vc, pos + 1, scale)
        ctx = jnp.einsum("bnsh->bsnh", ctx).reshape(b, 1, -1)
        x = x + _mm(ctx, bp, "proj") + bp["proj_b"]
        ff = _ln(x, bp["ln2_w"], bp["ln2_b"], eps)
        ff = jax.nn.gelu(_mm(ff, bp, "fc1") + bp["fc1_b"],
                         approximate=False)
        x = x + _mm(ff, bp, "fc2") + bp["fc2_b"]
        new_caches.append((kc, vc))
    return x, new_caches


def _prefill(params, eps, n_heads, ids, total_len, prompt_lens=None,
             qkv_heads_major=False, tp_reduce=None, head_dim=None):
    """Full forward over the prompt, returning per-layer caches sized to
    total_len and the last hidden state. Uses the same big-matmul form
    as training (the MXU-efficient path) — only decode is token-wise.

    prompt_lens [B] (ragged, right-padded prompts): keys beyond each
    row's true length are masked; their junk cache slots are
    progressively OVERWRITTEN by the decode loop's per-row scatter, so
    they are never attended to.

    qkv_heads_major / tp_reduce: the tensor-parallel hooks. Inside a
    tp shard_map the qkv columns are laid out (heads, 3, hd) — so each
    chip's contiguous shard carries WHOLE heads with their q,k,v —
    and the proj/fc2 partial contractions need an all-reduce before
    the bias. Both default off; the tp=1 graph is byte-for-byte the
    one this function always built (the parity contract). head_dim
    must be given explicitly under tp (n_heads is then the LOCAL head
    count while the replicated hidden stays global)."""
    b, s = ids.shape
    hd = head_dim or params["wte"].shape[1] // n_heads
    scale = 1.0 / math.sqrt(hd)
    x = params["wte"][ids] + params["wpe"][jnp.arange(s)][None]
    cm = jnp.tril(jnp.ones((s, s), bool))
    if prompt_lens is not None:
        cm = (cm[None, None]
              & (jnp.arange(s)[None, :]
                 < prompt_lens[:, None])[:, None, None, :])
    caches = []
    for bp in params["blocks"]:
        xn = _ln(x, bp["ln1_w"], bp["ln1_b"], eps)
        qkv = _mm(xn, bp, "qkv") + bp["qkv_b"]
        if qkv_heads_major:
            qkv = jnp.einsum("bsnch->bscnh", qkv.reshape(
                b, s, n_heads, 3, hd))
        else:
            qkv = qkv.reshape(b, s, 3, n_heads, hd)
        q = jnp.einsum("bsnh->bnsh", qkv[:, :, 0])
        k = jnp.einsum("bsnh->bnsh", qkv[:, :, 1])
        v = jnp.einsum("bsnh->bnsh", qkv[:, :, 2])
        att = jnp.einsum("bnqh,bnkh->bnqk", q, k) * scale
        att = jnp.where(cm, att, -1e30)
        p = jax.nn.softmax(att.astype(jnp.float32), axis=-1).astype(
            x.dtype)
        ctx = jnp.einsum("bnqk,bnkh->bnqh", p, v)
        ctx = jnp.einsum("bnsh->bsnh", ctx).reshape(b, s, -1)
        proj = _mm(ctx, bp, "proj")
        if tp_reduce is not None:
            proj = tp_reduce(proj)
        x = x + proj + bp["proj_b"]
        ff = _ln(x, bp["ln2_w"], bp["ln2_b"], eps)
        ff = jax.nn.gelu(_mm(ff, bp, "fc1") + bp["fc1_b"],
                         approximate=False)
        f2 = _mm(ff, bp, "fc2")
        if tp_reduce is not None:
            f2 = tp_reduce(f2)
        x = x + f2 + bp["fc2_b"]
        kc = jnp.zeros((b, n_heads, total_len, hd), k.dtype)
        vc = jnp.zeros((b, n_heads, total_len, hd), v.dtype)
        kc = jax.lax.dynamic_update_slice_in_dim(kc, k, 0, axis=2)
        vc = jax.lax.dynamic_update_slice_in_dim(vc, v, 0, axis=2)
        caches.append((kc, vc))
    return x, caches


def _pick(logits, key, temperature, top_k, top_p=None):
    logits = logits.astype(jnp.float32)  # sampling math in f32 even
    # when the matmuls ran in bf16 (argmax is cast-invariant)
    if temperature == 0.0:  # greedy (static python branch)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    need_p = top_p is not None and float(top_p) < 1.0
    if top_k is not None or need_p:
        # ONE descending sort serves both filters (vocab-size sort is
        # the dominant sampling cost per decode step)
        sorted_l = jnp.sort(logits, axis=-1)[:, ::-1]
    if top_k is not None:
        k = min(int(top_k), logits.shape[-1])  # HF-style clamp
        kth = sorted_l[:, k - 1][:, None]
        logits = jnp.where(logits >= kth, logits, -1e30)
    if need_p:
        # nucleus sampling: keep the smallest prefix of the
        # descending-probability order whose mass reaches top_p (the
        # first token past the threshold stays in — HF semantics; the
        # top token's EXCLUSIVE mass is 0, so it always survives).
        # Sequential-filter semantics: when top_k is also set, the
        # nucleus mass is computed over the top_k-masked distribution
        # (HF warper order). Static-shape: sort + cumsum + where.
        base = sorted_l
        if top_k is not None:
            base = jnp.where(
                jnp.arange(base.shape[-1])[None, :] < k, base, -1e30)
        probs = jax.nn.softmax(base, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = (cum - probs) < float(top_p)
        kth = jnp.min(jnp.where(keep, base, jnp.inf), axis=-1,
                      keepdims=True)
        logits = jnp.where(logits >= kth, logits, -1e30)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def _cast_params(params, dtype):
    """Serving-dtype cast INSIDE the jitted program: one streamed
    f32→bf16 pass over the weights per call (vs per decode step), no
    host-side cached copy that could go stale after a weight update."""
    if dtype is None:
        return params
    dt = jnp.dtype(dtype)
    return jax.tree_util.tree_map(
        lambda a: (a.astype(dt)
                   if jnp.issubdtype(a.dtype, jnp.floating) else a),
        params)


@functools.lru_cache(maxsize=64)
def _build_run(eps, n_heads, temperature, top_k, eos_token_id,
               pad_token_id, max_new_tokens, prompt, total, dtype,
               ragged=False, top_p=None):
    """One jitted decode program per static signature — repeated
    generate() calls with the same shapes/sampling config reuse the
    compiled executable (params/ids/key[/prompt_lens] are traced
    arguments). ragged=True compiles the per-row-position form: each
    batch row prefills over its own prompt_lens[i]-long prefix, then
    decodes writing K/V at its own next position."""

    def run(params, ids, key, prompt_lens=None):
        params = _cast_params(params, dtype)
        b = ids.shape[0]
        pl = prompt_lens if ragged else None
        x, caches = _prefill(params, eps, n_heads, ids, total,
                             prompt_lens=pl)
        if ragged:
            idx = (prompt_lens - 1).astype(jnp.int32)
            last = jnp.take_along_axis(
                x, idx[:, None, None], axis=1)          # [B, 1, H]
            h_last = _ln(last, params["lnf_w"], params["lnf_b"], eps)
            pos0 = prompt_lens.astype(jnp.int32)
        else:
            h_last = _ln(x[:, -1:], params["lnf_w"], params["lnf_b"],
                         eps)
            pos0 = jnp.int32(prompt)
        logits = (h_last[:, 0] @ params["wte"].T)

        def body(carry, step_key):
            caches, logits, pos, done = carry
            tok = _pick(logits, step_key, temperature, top_k,
                        top_p)
            if eos_token_id is not None:
                tok = jnp.where(done, pad_token_id, tok)
                done = done | (tok == eos_token_id)
            emb_pos = (params["wpe"][pos] if ragged
                       else params["wpe"][pos][None])
            x = (params["wte"][tok] + emb_pos)[:, None, :]
            x, caches = _step_hidden(params, eps, n_heads, x, caches,
                                     pos)
            h = _ln(x, params["lnf_w"], params["lnf_b"], eps)
            logits = h[:, 0] @ params["wte"].T
            return (caches, logits, pos + 1, done), tok

        keys = jax.random.split(key, max_new_tokens)
        done0 = jnp.zeros((b,), bool)
        (_, _, _, _), toks = jax.lax.scan(
            body, (caches, logits, pos0, done0), keys)
        return jnp.concatenate([ids, toks.T], axis=1)

    return jax.jit(run)


@functools.lru_cache(maxsize=64)
def _build_beam_run(eps, n_heads, num_beams, eos_token_id, pad_token_id,
                    max_new_tokens, prompt, total, dtype):
    """Beam-search decode sharing the KV-cache machinery: beams live as
    batch rows [B*W], each step expands with the beam_search_step op's
    semantics (ops/extras.py, ref beam_search_op.cc), reorders the
    caches by parent beam, and the token/parent trail is walked back
    with gather_tree (ref gather_tree_op.cc)."""
    from ..ops.extras import beam_search_step, gather_tree
    bs_step = beam_search_step.__pure_fn__
    tree = gather_tree.__pure_fn__
    w = num_beams

    def run(params, ids, key):
        del key
        params = _cast_params(params, dtype)
        b = ids.shape[0]
        # prefill ONCE over the B prompts, then repeat the caches and
        # final logits across beams (duplicate rows would recompute the
        # identical prompt forward W times)
        x, caches = _prefill(params, eps, n_heads, ids, total)
        caches = jax.tree_util.tree_map(
            lambda c: jnp.repeat(c, w, axis=0), caches)
        h_last = _ln(x[:, -1:], params["lnf_w"], params["lnf_b"], eps)
        logits = jnp.repeat(h_last[:, 0] @ params["wte"].T, w,
                            axis=0)                         # [B*W, V]
        scores0 = jnp.tile(
            jnp.asarray([0.0] + [-1e30] * (w - 1), jnp.float32), (b, 1))
        done0 = jnp.zeros((b, w), bool)

        def body(carry, _):
            caches, logits, pos, scores, done = carry
            logp = jax.nn.log_softmax(
                logits.astype(jnp.float32), axis=-1).reshape(b, w, -1)
            if eos_token_id is not None:
                # finished beams only extend with pad at zero cost
                v = logp.shape[-1]
                frozen = jnp.full((v,), -1e30).at[pad_token_id].set(0.0)
                logp = jnp.where(done[:, :, None], frozen[None, None],
                                 logp)
            scores, toks, parents = bs_step(logp, scores, beam_size=w)
            if eos_token_id is not None:
                done = jnp.take_along_axis(done, parents, axis=1)
                done = done | (toks == eos_token_id)
            # reorder beam rows (KV caches + emitted state) by parent
            gidx = (jnp.arange(b)[:, None] * w + parents).reshape(-1)
            caches = jax.tree_util.tree_map(
                lambda c: jnp.take(c, gidx, axis=0), caches)
            flat_toks = toks.reshape(-1)
            x = (params["wte"][flat_toks]
                 + params["wpe"][pos][None])[:, None, :]
            x, caches = _step_hidden(params, eps, n_heads, x, caches,
                                     pos)
            h = _ln(x, params["lnf_w"], params["lnf_b"], eps)
            logits = h[:, 0] @ params["wte"].T
            return (caches, logits, pos + 1, scores, done), (toks,
                                                             parents)

        (_, _, _, scores, _), (toks, parents) = jax.lax.scan(
            body, (caches, logits, jnp.int32(prompt), scores0, done0),
            jnp.arange(max_new_tokens))
        seqs = tree(toks, parents)                         # [T, B, W]
        best = jnp.argmax(scores, axis=1)                  # [B]
        best_toks = jnp.take_along_axis(
            seqs, best[None, :, None], axis=2)[:, :, 0]    # [T, B]
        return (jnp.concatenate([ids, best_toks.T.astype(jnp.int32)],
                                axis=1),
                jnp.take_along_axis(scores, best[:, None], 1)[:, 0])

    return jax.jit(run)


def generate_gpt(model, input_ids, max_new_tokens=32, temperature=0.0,
                 top_k: Optional[int] = None,
                 eos_token_id: Optional[int] = None, pad_token_id=0,
                 num_beams=1, seed=0, dtype=None, prompt_lens=None,
                 top_p: Optional[float] = None):
    """KV-cache decode for GPTForCausalLM. temperature=0 -> greedy;
    num_beams>1 -> beam search (temperature/top_k/top_p ignored —
    beams expand by log-prob, not sampling).

    prompt_lens [B] int (ragged batching — the reference's LoD-driven
    dynamic_decode capability, TPU-style): input_ids is right-padded
    to a common length with any valid token id (pad_token_id by
    convention); row i's true prompt is its first prompt_lens[i] ids.
    Each row prefill-masks its padding, then decode writes K/V at its
    OWN next position, so rows of different lengths batch in one
    compiled program. Generated tokens still land in out[:, P:] for
    every row (out[i, prompt_lens[i]:P] keeps the pad filler).

    dtype="bfloat16" casts the float params (and with them the KV
    cache) for the decode — single-token decode is HBM-bound on
    weight reads, so bf16 serving roughly halves step latency on TPU.
    Layernorm moments and sampling stay in f32. Default None keeps
    the training dtype (exact greedy-equals-full-forward contract).

    Returns int32 [B, prompt_len + max_new_tokens]; rows that hit
    eos_token_id keep emitting pad_token_id afterwards.
    """
    cfg = model.gpt.config
    params = _gpt_params(model)
    dtype = None if dtype is None else str(jnp.dtype(dtype))
    ids = jnp.asarray(input_ids._data if isinstance(input_ids, Tensor)
                      else input_ids, jnp.int32)
    b, prompt = ids.shape
    if top_p is not None and not (0.0 < float(top_p) <= 1.0):
        # fail loudly host-side: top_p<=0 would mask EVERY token and
        # degenerate to uniform sampling over the whole vocab
        raise ValueError(f"top_p must be in (0, 1]; got {top_p}")
    total = prompt + int(max_new_tokens)
    if total > cfg.max_seq_len:
        raise ValueError(
            f"prompt+max_new_tokens={total} exceeds max_seq_len="
            f"{cfg.max_seq_len}")
    if num_beams > 1:
        if prompt_lens is not None:
            raise ValueError("prompt_lens is not supported with beam "
                             "search yet — pad to a common length")
        run = _build_beam_run(
            float(cfg.layer_norm_eps), int(cfg.num_heads),
            int(num_beams),
            None if eos_token_id is None else int(eos_token_id),
            int(pad_token_id), int(max_new_tokens), prompt, total,
            dtype)
        out, _scores = run(params, ids, jax.random.key(seed))
        return Tensor(out)
    ragged = prompt_lens is not None
    if ragged:
        import numpy as _np
        pl_host = _np.asarray(prompt_lens._data
                              if isinstance(prompt_lens, Tensor)
                              else prompt_lens)
        # fail loudly host-side: under jit, out-of-range lengths clamp
        # silently and the decode attends junk cache slots
        if pl_host.shape != (b,):
            raise ValueError(
                f"prompt_lens shape {pl_host.shape} != ({b},)")
        if pl_host.min() < 1 or pl_host.max() > prompt:
            raise ValueError(
                f"prompt_lens must be in [1, {prompt}] (padded prompt "
                f"width); got min={pl_host.min()} max={pl_host.max()}")
    run = _build_run(
        float(cfg.layer_norm_eps), int(cfg.num_heads),
        float(temperature), None if top_k is None else int(top_k),
        None if eos_token_id is None else int(eos_token_id),
        int(pad_token_id), int(max_new_tokens), prompt, total, dtype,
        ragged, None if top_p is None else float(top_p))
    if ragged:
        pl = jnp.asarray(prompt_lens._data
                         if isinstance(prompt_lens, Tensor)
                         else prompt_lens, jnp.int32)
        out = run(params, ids, jax.random.key(seed), pl)
    else:
        out = run(params, ids, jax.random.key(seed))
    return Tensor(out)
