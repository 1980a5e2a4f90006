"""The paged decode-attention kernel against the portable reference.

On a TPU `make_decode_fn` attends through
`pallas_kernels.paged_decode_attention`; elsewhere through the gather
and `masked_attention`, which stay the reference and the anchor of the f32
"paged equals dense" contract (tests/test_serving_engine.py). Here the
kernel runs under the Pallas interpreter on toy pools and is held to
that reference; `tests/test_pallas_mosaic_compile.py` compiles it with
the real Mosaic for a v5e.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models.decoder import masked_attention, prefix_mask
from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.serving.programs import _gathered

BS, W, HD, N_BLOCKS = 4, 11, 8, 40      # W no multiple of the pages a step
# heads x head size: a page's row is nh * hd lanes. The engine's toy
# width (4 x 16 = 64, no multiple of the 128 lanes), tp=2's shard of
# GPT-2-large (10 x 64) and a head as wide as the lanes (3 x 128)
WIDTHS = {"toy_4x16": (4, 16), "tp_shard_10x64": (10, 64),
          "head128_3x128": (3, 128)}
# the tokens the first slot holds: one, a page's last row, the next
# page's first row, a round's last page, the first page of the second
# round, the whole table
LENGTHS = {"one": 1, "page_last_row": BS, "page_first_row": BS + 1,
           "round_last_row": BS * pk._PAGES_PER_STEP,
           "second_round": BS * pk._PAGES_PER_STEP + 1,
           "full_table": BS * W}


def _reference(q, kp, vp, tables, lengths, scale):
    nh, hd = q.shape[1:]
    kc = _gathered(kp, tables, nh, hd)
    vc = _gathered(vp, tables, nh, hd)
    mask = prefix_mask(kc.shape[2], lengths)
    return masked_attention(q[:, None], kc, vc, mask, scale)[:, 0]


def _case(dtype, nh, length, hd=HD):
    """Four slots over one pool of pages [BS, nh * hd] (one row a
    token, heads side by side). Slot 0 holds `length` tokens in pages
    in shuffled order; slot 1 shares slot 0's first two pages and goes
    on in its own; slot 2 is an inactive lane (an all-zero table row,
    length 1: it has no round, and its output is 0); slot 3 holds some
    other number of tokens."""
    rng = np.random.default_rng(length * 31 + nh)
    mk = lambda *shape: jnp.asarray(rng.standard_normal(shape), dtype)
    kp, vp = mk(N_BLOCKS, BS, nh * hd), mk(N_BLOCKS, BS, nh * hd)
    q = mk(4, nh, hd)
    pages = rng.permutation(N_BLOCKS - 1) + 1
    tables = np.zeros((4, W), np.int32)
    tables[0] = pages[:W]
    tables[1, :2] = tables[0, :2]
    tables[1, 2:5] = pages[W:W + 3]
    tables[3, :7] = pages[W + 3:W + 10]
    lengths = np.asarray([length, 2 * BS + 3, 1, 6 * BS + 2], np.int32)
    return q, kp, vp, jnp.asarray(tables), jnp.asarray(lengths)


def _check(dtype, nh, length, hd):
    q, kp, vp, tables, lengths = _case(dtype, nh, length, hd)
    assert kp.shape == (N_BLOCKS, BS, nh * hd)
    scale = 1.0 / math.sqrt(hd)
    got = pk.paged_decode_attention(q, kp, vp, tables, lengths, scale,
                                    interpret=True)
    assert got.shape == q.shape and got.dtype == kp.dtype
    # the inactive lane's row is exactly 0; the live ones are attended
    assert not np.asarray(got[2], np.float32).any()
    live = np.asarray([0, 1, 3])
    q, tables, lengths, got = q[live], tables[live], lengths[live], got[live]
    want = _reference(q, kp, vp, tables, lengths, scale)
    got32 = np.asarray(got, np.float32)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got32, np.asarray(want), atol=1e-5,
                                   rtol=1e-5)
    else:
        # the reference rounds scores and probabilities to bf16; the
        # kernel keeps them in f32 and rounds once, at the end, so it
        # sits within one rounding of the same pools attended in f32
        exact = _reference(*(a.astype(jnp.float32) for a in (q, kp, vp)),
                           tables, lengths, scale)
        np.testing.assert_allclose(got32, np.asarray(exact), atol=2 ** -8,
                                   rtol=2 ** -8)
        np.testing.assert_allclose(got32, np.asarray(want, np.float32),
                                   atol=3e-2, rtol=3e-2)
    # the greedy token through a made-up head is the same
    head = jnp.asarray(np.random.default_rng(7).standard_normal(
        (nh * hd, 64)), jnp.float32)
    pick = lambda ctx: np.asarray(jnp.argmax(
        ctx.astype(jnp.float32).reshape(len(live), -1) @ head, axis=-1))
    np.testing.assert_array_equal(pick(got), pick(want))


@pytest.mark.parametrize("length", list(LENGTHS.values()),
                         ids=list(LENGTHS))
@pytest.mark.parametrize("nh", [4, 2], ids=["nh4", "tp_shard_nh2"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_kernel_matches_gather_and_attend(dtype, nh, length):
    _check(dtype, nh, length, HD)


@pytest.mark.parametrize("length", ["one", "second_round", "full_table"])
@pytest.mark.parametrize("width", list(WIDTHS.values()), ids=list(WIDTHS))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_kernel_follows_the_page_width(dtype, width, length):
    """Heads and head size come from q's shape, the row's width from
    the pool's: nothing in the kernel assumes 20 x 64."""
    nh, hd = width
    _check(dtype, nh, LENGTHS[length], hd)


# lanes of a call and which of them are live: the rest carry an all-zero
# table row and a length of 1-4, as a chunk's inactive lanes do
LANES = {"interleaved_3_of_8": (8, (1, 2, 5)),   # dead first, between, last
         "one_of_32": (32, (17,)),
         "none_of_8": (8, ())}


def _lanes(dtype, b, live, nh=4, hd=HD):
    """`b` lanes over one pool; each live lane holds its own pages in
    shuffled order and 1 to BS * W tokens (one or two rounds)."""
    rng = np.random.default_rng(b * 7 + len(live))
    mk = lambda *shape: jnp.asarray(rng.standard_normal(shape), dtype)
    kp, vp = mk(N_BLOCKS, BS, nh * hd), mk(N_BLOCKS, BS, nh * hd)
    q = mk(b, nh, hd)
    pages = (rng.permutation(N_BLOCKS - 1) + 1)[:len(live) * W]
    tables = np.zeros((b, W), np.int32)
    tables[list(live)] = pages.reshape(len(live), W)
    lengths = rng.integers(1, 5, b).astype(np.int32)
    lengths[list(live)] = rng.integers(1, BS * W + 1, len(live))
    return q, kp, vp, tables, lengths


@pytest.mark.parametrize("lanes", list(LANES))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_inactive_lanes_read_zero_and_leave_live_lanes_bit_equal(dtype,
                                                                 lanes):
    """A lane whose first table entry is the scratch page has no round
    and reads exactly 0; a live lane reads, bit for bit, what it reads
    in a call of the live lanes alone, and the reference's attention."""
    b, live = LANES[lanes]
    q, kp, vp, tables, lengths = _lanes(dtype, b, live)
    scale = 1.0 / math.sqrt(HD)
    got = np.asarray(pk.paged_decode_attention(
        q, kp, vp, jnp.asarray(tables), jnp.asarray(lengths), scale,
        interpret=True), np.float32)
    assert np.isfinite(got).all()
    dead = [i for i in range(b) if i not in live]
    assert not got[dead].any()
    if not live:
        return
    live = np.asarray(live)
    alone = pk.paged_decode_attention(
        q[live], kp, vp, jnp.asarray(tables[live]),
        jnp.asarray(lengths[live]), scale, interpret=True)
    np.testing.assert_array_equal(got[live], np.asarray(alone, np.float32))
    exact = _reference(*(a.astype(jnp.float32) for a in (q[live], kp, vp)),
                       jnp.asarray(tables[live]), jnp.asarray(lengths[live]),
                       scale)
    tol = 1e-5 if dtype == jnp.float32 else 2 ** -8
    np.testing.assert_allclose(got[live], np.asarray(exact), atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("lanes", list(LANES))
def test_work_list_gives_inactive_lanes_no_round(lanes):
    """`_decode_rounds` takes a lane with the scratch page first in its
    table to hold nothing: the rounds are the live lanes' (one at
    least), in lane order, and each live window is its table entry."""
    b, live = LANES[lanes]
    _, _, _, tables, lengths = _lanes(jnp.float32, b, live)
    pps = pk._PAGES_PER_STEP
    tables = np.pad(tables, ((0, 0), (0, -W % pps)))
    taken, n, slot, rnd, fetch = (np.asarray(a) for a in pk._decode_rounds(
        jnp.asarray(tables), jnp.asarray(lengths), BS, pps))
    pages = -(-lengths // BS)
    rounds = {i: -(-pages[i] // pps) for i in live}
    assert int(n) == max(sum(rounds.values()), 1)
    want = np.zeros_like(lengths)
    want[list(live)] = lengths[list(live)]
    np.testing.assert_array_equal(taken, want)
    if not live:
        return
    np.testing.assert_array_equal(
        slot[:n], np.repeat(list(live), [rounds[i] for i in live]))
    for t in range(int(n)):
        for i in range(pps):
            col = rnd[t] * pps + i
            if col < pages[slot[t]]:
                assert fetch[t * pps + i] == tables[slot[t], col] != 0
