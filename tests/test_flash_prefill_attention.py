"""The flash prefill-attention kernel against the portable reference.

On a TPU `make_prefill_fn` attends through
`pallas_kernels.flash_prefill_attention`; elsewhere through
`masked_attention` under `causal_mask(s, prompt_lens)`, which stays the
reference and the anchor of the f32 greedy parity across the cache
addressings (tests/test_decoder_block.py). Here the kernel runs under
the Pallas interpreter and is held to that reference on every TRUE
query (a pad query's result is read by nothing: the kernel takes the
lengths to skip blocks, not to mask); what it writes for pad queries
must still be finite everywhere, and exactly zero where a whole query
block lies past the row's end, because it flows through the layers
above into K/V rows of live pages.
`tests/test_pallas_mosaic_compile.py` compiles it with the real Mosaic
for a v5e, alone and inside the prefill program.
"""
import math

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models.decoder import causal_mask, masked_attention
from paddle_tpu.ops import pallas_kernels as pk

ROWS = 5
# true lengths of the five rows, by bucket: the whole bucket, one
# token, one that is no multiple of a block or a tile, one that ends
# on a block's last row, one that starts the next
LENGTHS = {128: (128, 1, 77, 64, 65),
           256: (256, 1, 131, 128, 129),
           1024: (1024, 1, 300, 512, 513)}
# heads x head size; a row is nh * hd lanes read in blocks of
# lcm(hd, 128): the engine's toy width (one block of 64 lanes, four
# heads in it), pairs of 64-wide heads (GPT-2's; tp=2's shard has 10),
# a head as wide as the lanes, and one that straddles them (384-lane
# blocks of four heads)
WIDTHS = {"toy_4x16": (4, 16), "pairs_4x64": (4, 64),
          "head128_2x128": (2, 128), "straddling_4x96": (4, 96)}


def _reference(q, k, v, lens, scale):
    kc = jnp.einsum("bsnh->bnsh", k)
    vc = jnp.einsum("bsnh->bnsh", v)
    return masked_attention(q, kc, vc, causal_mask(q.shape[1], lens),
                            scale)


def _case(dtype, s, nh, hd):
    rng = np.random.default_rng(s * 7 + nh * hd)
    mk = lambda: jnp.asarray(rng.standard_normal((ROWS, s, nh, hd)), dtype)
    return mk(), mk(), mk(), jnp.asarray(LENGTHS[s], jnp.int32)


def _check(dtype, s, nh, hd, block=None):
    q, k, v, lens = _case(dtype, s, nh, hd)
    scale = 1.0 / math.sqrt(hd)
    if block is None:
        got = pk.flash_prefill_attention(q, k, v, lens, scale,
                                         interpret=True)
        block = pk._pick_block(s, pk._PREFILL_BLOCK)[1]
    else:
        # the jitted entry would hand back the default's trace
        got = pk.flash_prefill_attention.__wrapped__(
            q, k, v, lens, scale, True)
    assert got.shape == q.shape and got.dtype == q.dtype
    got32 = np.asarray(got, np.float32)
    assert np.isfinite(got32).all()
    want = np.asarray(_reference(q, k, v, lens, scale), np.float32)
    exact = want if dtype == jnp.float32 else np.asarray(_reference(
        *(a.astype(jnp.float32) for a in (q, k, v)), lens, scale))
    for r, n in enumerate(LENGTHS[s]):
        if dtype == jnp.float32:
            np.testing.assert_allclose(got32[r, :n], want[r, :n],
                                       atol=1e-5, rtol=1e-5)
        else:
            # the reference rounds the scores to bf16; the kernel keeps
            # them in f32 and rounds the probabilities as the reference
            # does, so it sits nearer the same rows attended in f32
            np.testing.assert_allclose(got32[r, :n], exact[r, :n],
                                       atol=2 ** -6, rtol=2 ** -6)
            np.testing.assert_allclose(got32[r, :n], want[r, :n],
                                       atol=3e-2, rtol=3e-2)
        past = -(-n // block) * block       # first block wholly past n
        assert not got32[r, past:].any(), (r, n, past)
    return got32


@pytest.mark.parametrize("s", list(LENGTHS))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_kernel_matches_masked_attention(dtype, s):
    _check(dtype, s, 2, 64)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_query_blocks_past_the_length_are_zero(dtype, monkeypatch):
    """With 128-row blocks a 1,024 bucket has eight query blocks a
    row: all but the first of the one-token row, all past the third of
    the 300-token row, are skipped and must read exactly 0 (not
    whatever the output window held)."""
    monkeypatch.setattr(pk, "_PREFILL_BLOCK", 128)
    got = _check(dtype, 1024, 2, 64, block=128)
    assert got[1, :128].any() and not got[1, 128:].any()
    assert got[2, 256:384].any() and not got[2, 384:].any()


@pytest.mark.parametrize("width", list(WIDTHS.values()), ids=list(WIDTHS))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_kernel_follows_the_row_width(dtype, width):
    """Heads and head size come from q's shape: nothing in the kernel
    assumes 20 x 64."""
    _check(dtype, 128, *width)


def test_a_bucket_of_no_whole_tile_is_padded():
    """Buckets are multiples of the page (16), not of the 128-row
    tile: 48 rows are attended as one padded block and come back 48."""
    q, k, v, _ = _case(jnp.float32, 128, 2, 64)
    q, k, v = q[:, :48], k[:, :48], v[:, :48]
    lens = jnp.asarray([48, 1, 17, 32, 33], jnp.int32)
    got = pk.flash_prefill_attention(q, k, v, lens, 0.125, interpret=True)
    want = _reference(q, k, v, lens, 0.125)
    for r, n in enumerate(np.asarray(lens)):
        np.testing.assert_allclose(np.asarray(got)[r, :n],
                                   np.asarray(want)[r, :n],
                                   atol=1e-5, rtol=1e-5)
