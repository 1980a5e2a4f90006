"""The serving engine's three compiled programs: bucketed prefill, the
decode step and the paged chunk, for whichever decoder the engine
serves.

TVM's lesson (PAPERS.md) dictates the TPU shape: a SMALL, FIXED set of
pre-compiled executables over static shapes, never a recompile per
request. The steady-state serving loop is exactly

  n_prefill_buckets   prefill executables   (admit width x bucket len)
  n_decode_buckets    decode executables    (slot-count buckets)

(plus the chunk program's shapes where speculation or prefix sharing is
on) and the RecompileSentinel pins that count every step. Every program
takes the page pools FIRST and donates them (``donate_argnums=(0,)``),
so XLA writes K/V pages in place — the graph_lint donation rule proves
the aliasing on the lowered module.

The block is models/decoder.py's `block`, the one generation.py's dense
path runs; a program here is an ADDRESSING of the paged cache — an
`attend(pools, q, k, v)` that scatters this call's K/V rows into their
pages and says what the queries attend over — between the embedding and
the head. One body, so paged-vs-dense greedy is token-for-token in f32.
On a TPU the decode step's attention is
`ops/pallas_kernels.paged_decode_attention`, which reads each slot's
live pages in place through the block table instead of gathering every
table whole: chosen by the platform as the flash kernel is, and held to
the gather and `masked_attention` by tests/test_paged_decode_attention.py
(f32 within 1e-5, the greedy token the same). The chunk program
(several queries a slot) keeps the gather. The prefill's is
`flash_prefill_attention` on a TPU, a flash forward over the prompt's
own K/V rows in place (no `[A, heads, S, S]` scores, mask or
probabilities in HBM), and `masked_attention` under the dense causal
mask elsewhere (tests/test_flash_prefill_attention.py).

A pool is ``[n_blocks, block_size, n_heads * head_dim]``, one row a
token with the heads side by side (the shape whose default TPU layout
is the row-major one the scatters and the kernel address:
paged_cache.py); logical position ``p`` of a request lives in page
``table[p // block_size]`` at offset ``p % block_size``. The programs
write whole rows and split heads only after a gather. Masked or padded
lanes carry an all-zeros table row — their writes land in the reserved
scratch page 0 and their reads are iota-masked, so inactive lanes cost
no conditional scatter. Junk K/V (pad positions a bucketed prefill
computes past a row's true length) is routed to scratch by table
padding or overwritten by the decode scatter, and never attended:
every attention masks to the row's live prefix.

Tensor parallelism (``ServingConfig(plan=MeshPlan(tp=N))``) runs these
programs inside a ``shard_map`` over the 'tp' axis. Nothing here knows:
the `DecoderSpec` the engine hands the makers carries the local head
count, the heads-major qkv layout and the all-reduce
(engine.py::serving_decoder_spec).

A decoder whose mixer is `retention` (models/decoder.py) has no page
and no table: its cache is a state ROW a request (state_cache.py), and
the same two makers build its prefill and its decode step around the
retention addressings below. What is `tables [B, W]` above is then
`rows [B]`, one state row a lane, row 0 the scratch row of padded and
dead lanes. The prefill attends through the quadratic form over the
bucket and writes each row's state as of its LAST TRUE token: junk
rows past a prompt's length, which a paged cache lets the decode step
overwrite, could never be taken out of a state again, so they never
enter one. The decode program reads each live row once a layer and
token-step and writes it once a layer and chunk (decoder.py, "the
decode chunk"); the pass over the rows is
`ops/pallas_kernels.retention_decode` on a TPU and
`decoder.retention_pass` elsewhere, both held to `decoder.retention_step`
by tests/test_retention_serving.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..models import decoder
from ..models.generation import _pick
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


def _written(pools, index, shape, k, v):
    """This call's K/V [B, S, nh, hd], reshaped to whole rows `shape`
    (heads side by side), scattered into the pool pair at `index`."""
    kp, vp = pools
    return (kp.at[index].set(k.reshape(shape)),
            vp.at[index].set(v.reshape(shape)))


def _gather_attention(spec, pools, tables, q, mask):
    """The portable paged attention: every slot's whole table gathered
    into a contiguous cache, then `masked_attention`."""
    kc, vc = (_gathered(p, tables, spec.n_heads, spec.head_dim)
              for p in pools)
    return decoder.masked_attention(q, kc, vc, mask, spec.scale)


def _decode_addressing(spec, block_size, tables, positions):
    """One token a slot: its K/V row goes to logical `positions[i]` of
    slot i's table and its query attends over the `positions[i] + 1`
    rows the slot then holds — on a TPU through the paged kernel,
    elsewhere through the gather, the reference the kernel is tested
    against."""
    from ..ops import pallas_kernels as _pk
    on_tpu = _pk.pallas_available()
    bi = jnp.arange(tables.shape[0])
    at = (tables[bi, positions // block_size],           # page, [B]
          positions % block_size)                        # row, [B]
    mask = decoder.prefix_mask(tables.shape[1] * block_size,
                               positions + 1)

    def attend(pools, q, k, v):
        pools = _written(pools, at, at[0].shape + (-1,), k, v)
        if on_tpu:
            return _pk.paged_decode_attention(
                q[:, 0], *pools, tables, positions + 1,
                spec.scale)[:, None], pools
        return _gather_attention(spec, pools, tables, q, mask), pools

    return attend


def _prefill_addressing(spec, block_size, tables, s, prompt_lens):
    """A whole prompt a row, right-padded to `s`: the queries attend
    over this call's own K/V, causally — on a TPU through the flash
    kernel, which keeps the scores on the chip and takes `prompt_lens`
    only to skip the blocks past a row's end; elsewhere through
    `masked_attention` under the dense mask, the reference the kernel
    is tested against — and the K/V rows go page-wise into the first
    S / block_size pages of each row's table."""
    from ..ops import pallas_kernels as _pk
    on_tpu = _pk.pallas_available()
    mask = None if on_tpu else decoder.causal_mask(s, prompt_lens)

    def attend(pools, q, k, v):
        if on_tpu:
            ctx = _pk.flash_prefill_attention(q, k, v, prompt_lens,
                                              spec.scale)
        else:
            kc = jnp.einsum("bsnh->bnsh", k)
            vc = jnp.einsum("bsnh->bnsh", v)
            ctx = decoder.masked_attention(q, kc, vc, mask, spec.scale)
        nblk = s // block_size
        return ctx, _written(pools, tables[:, :nblk],
                             (k.shape[0], nblk, block_size, -1), k, v)

    return attend


def _chunk_addressing(spec, block_size, tables, positions, valid):
    """S tokens a slot, mid-stream: row q of slot i lands its K/V at
    logical `positions[i, q]` — rows past the slot's valid count route
    to SCRATCH (clamped-column writes past a row's table would land in
    its last real page, which under prefix sharing may even be
    borrowed; the valid-mask makes junk structurally harmless instead
    of accidentally so). Per-query causal masking (`key_pos <=
    query_pos`) keeps every query's softmax support exactly the
    decode-step support, which is what lets the verify argmaxes be
    bit-identical to sequential decode in f32."""
    bi = jnp.arange(tables.shape[0])[:, None]              # [B, 1]
    col = jnp.clip(positions // block_size, 0, tables.shape[1] - 1)
    at = (jnp.where(valid, tables[bi, col], 0),            # page, [B, S]
          positions % block_size)
    keys = jnp.arange(tables.shape[1] * block_size)
    mask = keys[None, None, None, :] <= positions[:, None, :, None]

    def attend(pools, q, k, v):
        pools = _written(pools, at, at[0].shape + (-1,), k, v)
        return _gather_attention(spec, pools, tables, q, mask), pools

    return attend


def _retention_decode_addressing(rows, t, write, one_pass=None):
    """Token-step `t` of a decode chunk, one token a lane: its k, v and
    gate join the chunk's buffers, its queries read the state row
    `rows[i]` as the chunk found it and add the chunk's own tokens
    (decoder.py, "the decode chunk"); where `write`, the row is
    replaced by its state as of this token-step. A layer's cache is
    (state, chunk buffers). The pass over the rows is `one_pass`: by
    default on a TPU the kernel, elsewhere jax.numpy."""
    from ..ops import pallas_kernels as _pk
    if one_pass is None:
        one_pass = _pk.retention_decode if _pk.pallas_available() \
            else decoder.retention_pass

    def attend(cache, q, k, v, gate):
        state, chunk = cache
        chunk = decoder.retention_chunk_push(chunk, t, k[:, 0], v[:, 0],
                                             gate[:, 0])
        ctx, state = decoder.retention_chunk_step(
            state, rows, q[:, 0], chunk, t, write, one_pass)
        return ctx[:, None], (state, chunk)

    return attend


def _retention_decode_run(spec, step, n_steps):
    """The retention decoder's decode program: `make_decode_fn`'s
    contract, with the chunk's k, v and gates of every layer in the
    scan's carry. Token-steps read the rows; a row is written once every
    `span` token-steps (the chunk, or the kernel's most,
    RETENTION_CHUNK) and at the last. `run.writes_per_dispatch` states
    how many writes of a live row a dispatch makes (one a layer each)."""
    from ..ops.pallas_kernels import RETENTION_CHUNK
    span = min(n_steps, RETENTION_CHUNK)
    kv, hd = spec.n_kv_heads, spec.head_dim

    def run(state, rows, toks, positions, params, key):
        b = rows.shape[0]
        empty = (jnp.zeros((b, kv, span, hd), jnp.float32),
                 jnp.zeros((b, kv, span, hd), jnp.float32),
                 jnp.zeros((b, kv, span), jnp.float32))

        def body(carry, xs):
            caches, toks, positions = carry
            step_key, t = xs
            at = t % span
            caches, tok = step(
                caches, lambda: _retention_decode_addressing(
                    rows, at, (at == span - 1) | (t == n_steps - 1)),
                toks, positions, params, step_key)
            return (caches, tok, positions + 1), tok

        keys = jax.random.split(key, n_steps)
        (caches, _, _), out = jax.lax.scan(
            body, (tuple((s, empty) for s in state), toks, positions),
            (keys, jnp.arange(n_steps)))
        return tuple(s for s, _ in caches), out        # [n_steps, B]

    run.writes_per_dispatch = -(-n_steps // span)
    return run


def _retention_prefill_addressing(rows, prompt_lens):
    """A whole prompt a row, right-padded: the queries attend through
    the quadratic form (cheaper than the features under a few thousand
    tokens), and row `rows[i]` of the state is WRITTEN, whole, with
    what prompt i leaves behind as of its last true token — which is
    also the zeroing of a row that an earlier request held."""

    def attend(state, q, k, v, gate):
        ctx = decoder.retained_attention(q, k, v, gate, prompt_lens)
        new = decoder.retention_state(k, v, gate, prompt_lens)
        for i in range(rows.shape[0]):
            state = tuple(
                jax.lax.dynamic_update_slice_in_dim(
                    pool, n[i:i + 1].astype(pool.dtype), rows[i], axis=0)
                for pool, n in zip(state, new))
        return ctx, state

    return attend


def make_decode_fn(spec, block_size: int, sampling, n_steps: int = 1):
    """``n_steps`` token boundaries for every running slot, fused into
    one dispatch (lax.scan over the single-token step).

    run(pools, tables, toks, positions, params, key)
        -> (pools', toks [n_steps, B])

    toks [B] is each slot's last emitted token, positions [B] the
    logical index where its K/V land (== tokens held so far); for a
    retention decoder `tables` is `rows [B]`, a state row a slot.
    `sampling` is (temperature, top_k, top_p). The step is
    generation.py's ragged decode step under `_decode_addressing`.

    n_steps > 1 is the multi-step-scheduling lever: admission/retire
    decisions then happen every n_steps tokens instead of every token,
    trading a bounded TTFT granularity for host-dispatch amortization
    (the per-token jit round-trip is the serving loop's overhead
    floor). Rows whose budget or eos fires mid-chunk over-decode at
    most n_steps-1 junk tokens; their writes land in their own
    reserved pages (or clamp to their last page), which die with the
    request — the host trims the emitted stream.

    A retention decoder's program is `_retention_decode_run`: its rows
    are read every token-step and written once a chunk.
    """

    def step(pools, address, toks, positions, params, key):
        """One token-step; `address()` gives the layers' `attend`."""
        with _scope("embed"):
            x = decoder.embed(params, toks, positions)[:, None]
        x, pools = decoder.blocks(spec, params, x, pools, address(),
                                  positions[:, None])
        with _scope("lm_head"):
            tok = _pick(decoder.final_logits(spec, params, x)[:, 0], key,
                        *sampling)
        return pools, tok

    if spec.mixer == "retention":
        return _retention_decode_run(spec, step, n_steps)

    def run(pools, tables, toks, positions, params, key):
        def body(carry, step_key):
            pools, toks, positions = carry
            pools, tok = step(
                pools, lambda: _decode_addressing(spec, block_size, tables,
                                                  positions),
                toks, positions, params, step_key)
            return (pools, tok, positions + 1), tok
        keys = jax.random.split(key, n_steps)
        (pools, _, _), out = jax.lax.scan(
            body, (pools, toks, positions), keys)
        return pools, out                              # [n_steps, B]

    return run


def make_prefill_fn(spec, block_size: int, sampling):
    """Bucketed admission prefill: the whole admit batch — MIXED true
    lengths — shares ONE executable per (admit width, bucket len).

    run(pools, tables, ids, prompt_lens, params, key) -> (pools', tok)

    ids [A, S] is right-padded to the bucket width S (a multiple of
    block_size: BucketLadder refuses any other; for a retention
    decoder `tables` is `rows [A]`); a true token attends
    causally, so never past its row's prompt_lens [A], and each row's
    hidden state at its own last true token is exactly what the dense
    ragged path computes.
    Each layer's K/V rows are scattered page-wise into the pools and
    the first generated token is picked from the last-token logits.
    """

    def run(pools, tables, ids, prompt_lens, params, key):
        s = ids.shape[1]
        with _scope("embed"):
            x = decoder.embed(params, ids, jnp.arange(s))
        if spec.mixer == "retention":
            attend = _retention_prefill_addressing(tables, prompt_lens)
        else:
            attend = _prefill_addressing(spec, block_size, tables, s,
                                         prompt_lens)
        x, pools = decoder.blocks(spec, params, x, pools, attend,
                                  jnp.arange(s))
        with _scope("lm_head"):
            idx = (prompt_lens - 1).astype(jnp.int32)
            last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
            tok = _pick(decoder.final_logits(spec, params, last)[:, 0],
                        key, *sampling)
        return pools, tok

    return run


def make_chunk_fn(spec, block_size: int, sampling):
    """Mid-stream multi-token forward over the PAGED cache — the one
    program behind both raw-speed levers:

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
    lens [B] valid counts (1..S); position q of row i is logical
    ``starts[i] + q`` (`_chunk_addressing`).

    all_tok is each position's greedy argmax (the verify receipt);
    picked is the sampled/argmax token at each row's LAST valid
    position (the next token a non-speculative boundary would emit).
    """

    def run(pools, tables, toks, starts, lens, params, key):
        offs = jnp.arange(toks.shape[1], dtype=jnp.int32)
        positions = starts[:, None] + offs[None, :]        # [B, S]
        valid = offs[None, :] < lens[:, None]              # [B, S]
        with _scope("embed"):
            n_pos = params["wpe"].shape[0]
            x = decoder.embed(params, toks,
                              jnp.clip(positions, 0, n_pos - 1))
        x, pools = decoder.blocks(
            spec, params, x, pools, _chunk_addressing(
                spec, block_size, tables, positions, valid))
        with _scope("lm_head"):
            logits = decoder.final_logits(spec, params, x)  # [B, S, V]
            all_tok = jnp.argmax(logits.astype(jnp.float32),
                                 axis=-1).astype(jnp.int32)
            idx = (lens - 1).astype(jnp.int32)
            last = jnp.take_along_axis(
                logits, idx[:, None, None], axis=1)[:, 0]  # [B, V]
            picked = _pick(last, key, *sampling)
        return pools, all_tok, picked

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
