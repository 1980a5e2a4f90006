"""models/decoder.py — the one block body behind generation and serving.

The seam's three contracts, all in f32 at toy widths:
- `block` under a dense causal `attend` is GPTBlock.forward on the same
  weights (the training copy of the block, on the framework's Tensor
  ops), and embed + blocks + final_logits are the model's logits;
- one decode step from the same cache state gives the same hidden
  state BIT FOR BIT whichever way the cache is addressed: dense
  (generation._step_hidden), paged with the gather
  (programs._decode_addressing off a TPU) or the chunk program's
  several-queries-a-slot form;
- a heads-major spec on `permute_qkv_heads`-permuted weights is the
  plain layout bit for bit, and the engine's spec and snapshot agree on
  which layout a config gets;
- the MLP's activation, `decoder._gelu`, is the erf GELU: against
  `math.erf` in float64 it is exact to f32's last digits, and in bf16
  one rounding of the exact value.
"""
import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.sharding import MeshPlan, permute_qkv_heads
from paddle_tpu.models import GPTConfig, GPTForCausalLM, decoder
from paddle_tpu.models.decoder import DecoderSpec
from paddle_tpu.models.generation import (_gpt_params, _prefill,
                                          _step_hidden)
from paddle_tpu.serving import ServingConfig
from paddle_tpu.serving.engine import (build_serving_snapshot,
                                       serving_decoder_spec)
from paddle_tpu.serving.programs import (_chunk_addressing,
                                         _decode_addressing)

B, BS, W = 3, 4, 6                      # slots, page rows, table width
T = BS * W                              # keys either cache holds


def _model(**kw):
    paddle.seed(11)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=97, hidden_size=32, num_layers=2, num_heads=4,
        max_seq_len=64, dropout=0.0, **kw))
    m.eval()
    return m


@pytest.fixture(scope="module")
def model():
    return _model(use_flash_attention=False)


def _causal(spec, s):
    """`blocks`' attend of a dense causal forward that keeps no cache."""
    mask = decoder.causal_mask(s)

    def attend(_, q, k, v):
        kc = jnp.einsum("bsnh->bnsh", k)
        vc = jnp.einsum("bsnh->bnsh", v)
        return decoder.masked_attention(q, kc, vc, mask, spec.scale), None
    return attend


def _causal_block(spec, bp, x):
    return decoder.block(spec, bp, x, functools.partial(
        _causal(spec, x.shape[1]), None))[0]


@pytest.mark.parametrize("variant", ["sdpa", "flash", "scan_layers"])
def test_block_is_the_training_block(variant):
    """f32, eval mode: the serving body against GPTBlock.forward, and
    the whole stack with the embeddings and the tied head against the
    model's logits, within 1e-5."""
    m = _model(use_flash_attention=variant == "flash",
               scan_layers=variant == "scan_layers")
    spec, params = DecoderSpec.of(m.gpt.config), _gpt_params(m)
    rng = np.random.RandomState(0)
    if variant != "scan_layers":        # a scanned stack has no blocks[0]
        x = rng.standard_normal((2, 9, 32)).astype(np.float32)
        want = np.asarray(m.gpt.blocks[0](paddle.to_tensor(x))._data)
        got = _causal_block(spec, params["blocks"][0], jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)
    ids = rng.randint(0, 97, (2, 9)).astype(np.int32)
    want = np.asarray(m(paddle.to_tensor(ids))._data)
    x, _ = decoder.blocks(spec, params,
                          decoder.embed(params, ids, jnp.arange(9)),
                          None, _causal(spec, 9))
    np.testing.assert_allclose(
        np.asarray(decoder.final_logits(spec, params, x)), want, atol=1e-5)


def _paged(caches, tables):
    """Dense [B, N, T, hd] caches copied into pools [n_blocks, BS,
    N * hd] at each row's pages (page 0 stays the scratch)."""
    pools = []
    for kc, vc in caches:
        pair = []
        for c in (kc, vc):
            rows = np.einsum("bnth->btnh", np.asarray(c)).reshape(
                B, W, BS, -1)
            pool = np.zeros((1 + B * W, BS, rows.shape[-1]), np.float32)
            pool[tables] = rows
            pair.append(jnp.asarray(pool))
        pools.append(tuple(pair))
    return pools


@pytest.fixture(scope="module")
def cache_state(model):
    """Three ragged prompts prefilled into a dense cache of T keys, the
    same K/V paged (tables in shuffled page order), and the next token
    of each row embedded at its position."""
    spec, params = DecoderSpec.of(model.gpt.config), _gpt_params(model)
    rng = np.random.RandomState(1)
    lens = jnp.asarray([5, 9, 2], jnp.int32)
    ids = jnp.asarray(rng.randint(0, 97, (B, 9)), jnp.int32)
    _, caches = _prefill(spec, params, ids, T, prompt_lens=lens)
    tables = (rng.permutation(B * W) + 1).reshape(B, W).astype(np.int32)
    toks = jnp.asarray(rng.randint(0, 97, (B,)), jnp.int32)
    x = decoder.embed(params, toks, lens)[:, None]
    want, dense = _step_hidden(spec, params, x, caches, lens)
    return dict(spec=spec, params=params, lens=lens, x=x, toks=toks,
                tables=jnp.asarray(tables),
                pools=_paged(caches, tables),
                want=np.asarray(want), dense=dense)


@pytest.mark.parametrize("addressing", ["paged_gather", "chunk_1",
                                        "chunk_3_padded"])
def test_one_decode_step_is_the_same_bits_however_addressed(
        cache_state, addressing):
    """The hidden state of one token a row, and the K/V rows it leaves
    in the cache, against the dense step: equal, not close. (With two
    padding queries beside the token the matmuls have three rows a slot
    and the CPU blocks them otherwise: that case is held to 2e-6, and
    its padding must land in the scratch page and nowhere else.)"""
    st = cache_state
    spec, params, lens = st["spec"], st["params"], st["lens"]
    if addressing == "paged_gather":
        x = st["x"]
        attend = _decode_addressing(spec, BS, st["tables"], lens)
    else:
        s = int(addressing.split("_")[1])
        offs = jnp.arange(s, dtype=jnp.int32)
        positions = lens[:, None] + offs[None, :]
        valid = jnp.broadcast_to(offs[None, :] < 1, (B, s))
        toks = jnp.zeros((B, s), jnp.int32).at[:, 0].set(st["toks"])
        x = decoder.embed(params, toks, positions)
        attend = _chunk_addressing(spec, BS, st["tables"], positions,
                                   valid)
    got, pools = decoder.blocks(spec, params, x, st["pools"], attend)
    same = (np.testing.assert_array_equal if x.shape[1] == 1 else
            lambda a, b: np.testing.assert_allclose(a, b, atol=2e-6))
    same(np.asarray(got)[:, :1], st["want"])
    again = _paged(st["dense"], np.asarray(st["tables"]))
    live = np.asarray(st["tables"])              # scratch page 0 aside
    for (kp, vp), (kd, vd) in zip(pools, again):
        same(np.asarray(kp)[live], np.asarray(kd)[live])
        same(np.asarray(vp)[live], np.asarray(vd)[live])


@pytest.mark.parametrize("queries", ["prompt", "one_token"])
def test_heads_major_on_permuted_weights_is_the_plain_layout(model,
                                                             queries):
    spec, params = DecoderSpec.of(model.gpt.config), _gpt_params(model)
    major = dataclasses.replace(spec, qkv_heads_major=True)
    bp = params["blocks"][1]
    permuted = dict(bp, **{k: permute_qkv_heads(bp[k], spec.n_heads)
                           for k in ("qkv_w", "qkv_b")})
    s = 7 if queries == "prompt" else 1
    x = jnp.asarray(np.random.RandomState(2).standard_normal(
        (2, s, 32)), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(_causal_block(major, permuted, x)),
        np.asarray(_causal_block(spec, bp, x)))


@pytest.mark.parametrize("tp", [1, 2])
def test_engine_spec_and_snapshot_agree_on_the_qkv_layout(model, tp):
    """One decision (engine._qkv_heads_major) feeds both: the spec the
    programs run and the columns of the snapshot they are handed."""
    mcfg = model.gpt.config
    cfg = ServingConfig(dtype=None,
                        plan=MeshPlan(tp=tp) if tp > 1 else None)
    spec = serving_decoder_spec(mcfg, cfg)
    assert spec == dataclasses.replace(
        DecoderSpec.of(mcfg), n_heads=mcfg.num_heads // tp,
        qkv_heads_major=tp > 1, reduce=spec.reduce)
    assert (spec.reduce is None) == (tp == 1)
    assert hash(DecoderSpec.of(mcfg)) == hash(DecoderSpec.of(mcfg))
    raw = _gpt_params(model)
    snap = build_serving_snapshot(raw, cfg, n_heads=mcfg.num_heads)
    for bp, got in zip(raw["blocks"], snap["blocks"]):
        want = (permute_qkv_heads(bp["qkv_w"], mcfg.num_heads)
                if spec.qkv_heads_major else bp["qkv_w"])
        np.testing.assert_array_equal(np.asarray(got["qkv_w"]),
                                      np.asarray(want))


def _exact_gelu(x):
    """0.5 x (1 + erf(x / sqrt 2)) in float64 through `math.erf`."""
    x = np.asarray(x, np.float64)
    return 0.5 * x * (1.0 + np.vectorize(math.erf)(x * math.sqrt(0.5)))


def _f64(a):
    return np.asarray(a.astype(jnp.float32)).astype(np.float64)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gelu_is_the_erf_gelu(dtype):
    """`_gelu` computes in f32 and rounds once. f32: a grid of 200,001
    points over +-8 within 2e-6 absolute of the float64 value (it reads
    1.0e-6; `1 + erf` has no more digits than 1 has). bf16: EVERY bf16
    value in +-8 as input: within one rounding (2**-8 relative) of the
    exact value where that is 1e-3 or more in size, within 2e-6 below,
    and its worst absolute error no larger than what
    `jax.nn.gelu(approximate=False)`, which computes in bf16, makes of
    the same inputs (0.0078 against 0.0098)."""
    if dtype == "float32":
        x = jnp.linspace(-8.0, 8.0, 200001, dtype=jnp.float32)
        got = decoder._gelu(x)
        assert got.dtype == jnp.float32
        assert np.abs(_f64(got) - _exact_gelu(_f64(x))).max() <= 2e-6
        return
    bits = np.arange(1 << 16, dtype=np.uint16)
    x = jax.lax.bitcast_convert_type(jnp.asarray(bits), jnp.bfloat16)
    x = x[jnp.abs(x.astype(jnp.float32)) <= 8.0]       # drops nan, inf
    assert x.shape[0] > 33000
    got = decoder._gelu(x)
    assert got.dtype == jnp.bfloat16
    want = _exact_gelu(_f64(x))
    err = np.abs(_f64(got) - want)
    big = np.abs(want) >= 1e-3
    assert (err[big] <= 2.0 ** -8 * np.abs(want[big])).all()
    assert err[~big].max() <= 2e-6
    stored = np.abs(_f64(jax.nn.gelu(x, approximate=False)) - want)
    assert err.max() <= stored.max()
