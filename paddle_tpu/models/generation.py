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

The block itself is models/decoder.py's `block`; this module holds the
two DENSE addressings of the cache (`_prefill`: causal over the
prompt's own K/V, kept as a [B, N, total, hd] cache; `_step_hidden`:
write one token at `pos`, attend over `pos + 1`), the parameter table
(`_gpt_params`), the sampler (`_pick`) and the two jitted builders. The
serving engine's paged addressings (serving/programs.py) run the same
`block`, which is what makes paged-vs-dense greedy bit-exact in f32
(tests/test_serving_engine.py, tests/test_decoder_block.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..framework import Tensor
from . import decoder
from .decoder import DecoderSpec

__all__ = ["generate_gpt"]

# the one table of a block's leaves: <layer>_w / <layer>_b for each
_BLOCK_LAYERS = ("ln1", "ln2", "qkv", "proj", "fc1", "fc2")


def _block_params(get):
    """`get(layer, "weight" | "bias")` -> that leaf of one block (or of
    a whole [L, ...] stack)."""
    return {f"{n}_{kind[0]}": get(n, kind)
            for n in _BLOCK_LAYERS for kind in ("weight", "bias")}


def _gpt_params(model):
    gpt = model.gpt
    from ..nn.layer.scanned import ScannedStack
    if isinstance(gpt.blocks, ScannedStack):
        # scan_layers: slice the [L, ...] stacks into per-layer dicts —
        # the decode loop is already per-layer, so generation works
        # identically off either parameter layout
        stk = gpt.blocks
        stacks = _block_params(lambda n, kind: getattr(
            stk, stk._mangled[f"{n}.{kind}"])._data)
        blocks = [{k: v[i] for k, v in stacks.items()}
                  for i in range(stk.L)]
    else:
        blocks = [_block_params(lambda n, kind: getattr(
            getattr(b, n), kind)._data) for b in gpt.blocks]
    return {
        "wte": gpt.wte.weight._data,
        "wpe": gpt.wpe.weight._data,
        "lnf_w": gpt.ln_f.weight._data, "lnf_b": gpt.ln_f.bias._data,
        "blocks": blocks,
    }


def _step_hidden(spec, params, x, caches, pos):
    """One token's hidden state through all blocks, updating caches.

    x: [B, 1, H]; caches: list of (k [B,N,T,hd], v [B,N,T,hd]);
    pos: index where this token's K/V land — a scalar (uniform
    prompts) or [B] (ragged prompts: each row writes at its own next
    position and attends over its own valid prefix)."""

    def attend(cache, q, k, v):
        kc, vc = cache
        k = jnp.einsum("bsnh->bnsh", k)
        v = jnp.einsum("bsnh->bnsh", v)
        if getattr(pos, "ndim", 0):
            # per-row scatter: row i writes its K/V at pos[i]
            bi = jnp.arange(k.shape[0])
            kc = kc.at[bi, :, pos].set(k[:, :, 0])
            vc = vc.at[bi, :, pos].set(v[:, :, 0])
        else:
            kc = jax.lax.dynamic_update_slice_in_dim(kc, k, pos, axis=2)
            vc = jax.lax.dynamic_update_slice_in_dim(vc, v, pos, axis=2)
        mask = decoder.prefix_mask(kc.shape[2], pos + 1)
        return (decoder.masked_attention(q, kc, vc, mask, spec.scale),
                (kc, vc))

    return decoder.blocks(spec, params, x, caches, attend)


def _prefill(spec, params, ids, total_len, prompt_lens=None):
    """Full forward over the prompt, returning its hidden states and
    per-layer caches sized to total_len. Uses the same big-matmul form
    as training (the MXU-efficient path) — only decode is token-wise.

    prompt_lens [B] (ragged, right-padded prompts): keys beyond each
    row's true length are masked; their junk cache slots are
    progressively OVERWRITTEN by the decode loop's per-row scatter, so
    they are never attended to."""
    s = ids.shape[1]
    mask = decoder.causal_mask(s, prompt_lens)
    pad = ((0, 0), (0, 0), (0, total_len - s), (0, 0))

    def attend(_, q, k, v):
        kc = jnp.einsum("bsnh->bnsh", k)
        vc = jnp.einsum("bsnh->bnsh", v)
        return (decoder.masked_attention(q, kc, vc, mask, spec.scale),
                (jnp.pad(kc, pad), jnp.pad(vc, pad)))

    x = decoder.embed(params, ids, jnp.arange(s))
    return decoder.blocks(spec, params, x, None, attend)


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
def _build_run(spec, temperature, top_k, eos_token_id,
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
        x, caches = _prefill(spec, params, ids, total, prompt_lens=pl)
        if ragged:
            idx = (prompt_lens - 1).astype(jnp.int32)
            last = jnp.take_along_axis(
                x, idx[:, None, None], axis=1)[:, 0]    # [B, H]
            pos0 = prompt_lens.astype(jnp.int32)
        else:
            last = x[:, -1]
            pos0 = jnp.int32(prompt)
        logits = decoder.final_logits(spec, params, last)

        def body(carry, step_key):
            caches, logits, pos, done = carry
            tok = _pick(logits, step_key, temperature, top_k,
                        top_p)
            if eos_token_id is not None:
                tok = jnp.where(done, pad_token_id, tok)
                done = done | (tok == eos_token_id)
            x = decoder.embed(params, tok[:, None],
                              jnp.reshape(pos, (-1, 1)))
            x, caches = _step_hidden(spec, params, x, caches, pos)
            logits = decoder.final_logits(spec, params, x[:, 0])
            return (caches, logits, pos + 1, done), tok

        keys = jax.random.split(key, max_new_tokens)
        done0 = jnp.zeros((b,), bool)
        (_, _, _, _), toks = jax.lax.scan(
            body, (caches, logits, pos0, done0), keys)
        return jnp.concatenate([ids, toks.T], axis=1)

    return jax.jit(run)


@functools.lru_cache(maxsize=64)
def _build_beam_run(spec, num_beams, eos_token_id, pad_token_id,
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
        x, caches = _prefill(spec, params, ids, total)
        caches = jax.tree_util.tree_map(
            lambda c: jnp.repeat(c, w, axis=0), caches)
        logits = jnp.repeat(decoder.final_logits(spec, params, x[:, -1]),
                            w, axis=0)                      # [B*W, V]
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
            x = decoder.embed(params, toks.reshape(-1, 1), pos)
            x, caches = _step_hidden(spec, params, x, caches, pos)
            logits = decoder.final_logits(spec, params, x[:, 0])
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
            DecoderSpec.of(cfg), int(num_beams),
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
        DecoderSpec.of(cfg), float(temperature),
        None if top_k is None else int(top_k),
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
