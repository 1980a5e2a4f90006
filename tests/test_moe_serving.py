"""A decoder of routed-expert layers over grouped-query attention, window
and full attention mixed by layer, behind the serving engine, held to
its plain reference (perfbench/configs/mellum2-12b-a2.5b.reference.py:
float32, attention in blocks of queries, each token's experts for that
token alone) at the configuration's toy widths on the CPU: hidden 64, 4
query and 2 key-value heads of 16, 8 experts of width 32 (2 a token),
layers [sliding, sliding, sliding, full] with a window of 8 and YaRN on
the full layer, vocabulary 512, seeded random weights. Compared on
LOGITS: with random weights the largest logit changes on rounding.

Besides: the two paged kernels with K/V heads shared by groups of query
heads and with a window, under the Pallas interpreter, against the
gather and `masked_attention`; the YaRN table against its closed form;
the dropless expert layer against a per-token loop; a layer told which
experts it holds; and the engine's refusals.
"""
import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import MoEConfig, MoEForCausalLM, decoder
from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.serving import ServingConfig, ServingEngine, programs
from paddle_tpu.serving.paged_cache import PagedKVCache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, "perfbench", "configs")
NAME = "mellum2-12b-a2.5b"


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def reference():
    return _load("mellum_reference",
                 os.path.join(CONFIGS, NAME + ".reference.py"))


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(CONFIGS, NAME + ".json")) as f:
        full = json.load(f)
    return {**full, **full["toy"]}


@pytest.fixture(scope="module")
def weights(reference, cfg):
    return reference.make_params(cfg, jax.random.key(11), "float32")


def _model(cfg, weights, **over):
    """MoEForCausalLM at the toy widths over the reference's weights
    (the benchmark's recipe, mellum2-12b-a2.5b.program.py)."""
    recipe = _load("mellum_program", os.path.join(CONFIGS,
                                                  NAME + ".program.py"))
    mcfg = recipe.model_config(cfg)
    for k, v in over.items():
        setattr(mcfg, k, v)
    model = MoEForCausalLM(mcfg)
    model.eval()
    state = model.state_dict()
    assert sorted(state) == sorted(weights)
    for name, tensor in state.items():
        assert tuple(tensor.shape) == tuple(weights[name].shape), name
        tensor._data = weights[name]
    return model


@pytest.fixture(scope="module")
def model(cfg, weights):
    return _model(cfg, weights)


PROMPT_LENS = (5, 17, 30, 12)
N_NEW = 11
BLOCK, WIDTH = 8, 8            # pages of 8 tokens, 8 pages a table


@pytest.fixture(scope="module")
def prompts(cfg):
    rng = np.random.default_rng(5)
    return [rng.integers(0, cfg["vocab_size"], n).astype(np.int32)
            for n in PROMPT_LENS]


def _cast(tree, dtype):
    return jax.tree_util.tree_map(lambda a: a.astype(dtype), tree)


def _serve_logits(spec, params, prompts, n_new, bucket, dtype):
    """The serving programs' arithmetic with the logits kept: one
    bucketed prefill of all the prompts through the paged prefill
    addressing (row i's pages after row i-1's, one dummy lane on the
    scratch page), then greedy decode one token a lane through the
    pages under the decode addressing, each layer with its window.
    Returns (tokens [n, n_new], logits [n, n_new, V])."""
    n = len(prompts)
    cache = PagedKVCache(len(params["blocks"]), 1 + n * WIDTH, BLOCK,
                         spec.n_kv_heads, spec.head_dim, dtype)
    pools = cache.pools
    tables = np.zeros((n + 1, WIDTH), np.int32)
    ids = np.zeros((n + 1, bucket), np.int32)
    lens = np.ones((n + 1,), np.int32)
    for i, p in enumerate(prompts):
        tables[i] = 1 + i * WIDTH + np.arange(WIDTH)
        ids[i, :p.size], lens[i] = p, p.size
    tables, lens = jnp.asarray(tables), jnp.asarray(lens)

    @jax.jit
    def prefill(pools, params):
        live = (jnp.arange(bucket)[None] < lens[:, None]) \
            & programs._live_lanes(tables)[:, None]
        x, pools, _ = decoder.blocks(
            spec, params, decoder.embed(params, jnp.asarray(ids), None),
            pools, programs._prefill_addressing(spec, BLOCK, tables,
                                                bucket, lens),
            jnp.arange(bucket), live)
        last = jnp.take_along_axis(x, (lens - 1)[:, None, None], axis=1)
        return pools, decoder.final_logits(spec, params, last)[:, 0]

    @jax.jit
    def decode(pools, params, tok, positions):
        x, pools, _ = decoder.blocks(
            spec, params, decoder.embed(params, tok, positions)[:, None],
            pools, programs._decode_addressing(spec, BLOCK, tables,
                                               positions),
            positions[:, None], programs._live_lanes(tables)[:, None])
        return pools, decoder.final_logits(spec, params, x)[:, 0]

    pools, first = prefill(pools, params)
    logits = [first]
    positions = lens
    for _ in range(n_new - 1):
        tok = jnp.argmax(logits[-1].astype(jnp.float32), axis=-1)
        pools, nxt = decode(pools, params, tok, positions)
        logits.append(nxt)
        positions = positions + 1
    logits = np.asarray(jnp.stack(logits, axis=1), np.float32)[:n]
    return logits.argmax(-1), logits


def _reference_logits(reference, cfg, weights, prompts, tokens):
    """The reference's full forward over prompt + served tokens (right-
    padded to one length, so one shape compiles), at every served
    position: [n, n_new, V]."""
    seqs = [np.concatenate([p, t.astype(np.int32)])
            for p, t in zip(prompts, tokens)]
    batch = np.zeros((len(seqs), max(map(len, seqs))), np.int32)
    for i, q in enumerate(seqs):
        batch[i, :q.size] = q
    with jax.default_matmul_precision("highest"):
        lg = np.asarray(reference.logits(weights, cfg, jnp.asarray(batch)))
    return np.stack([lg[i, p.size - 1:p.size - 1 + t.size]
                     for i, (p, t) in enumerate(zip(prompts, tokens))])


# -- (f), (g): served logits against the reference ---------------------------

@pytest.mark.parametrize("dtype,fails", [("float32", False),
                                         ("bfloat16", True)])
def test_served_logits_match_the_reference(reference, cfg, weights, model,
                                           prompts, dtype, fails):
    """f32: prefill, then decode through the pages, within 1e-4 of the
    reference's full forward (the only differences are the order of
    sums). bf16 weights and pages, the configuration's precision one
    step down from the reference, must fail that tolerance: the
    comparison sees a precision loss of that size."""
    spec = model.decoder_spec()
    params = _cast(model.decoder_params(), dtype)
    toks, got = _serve_logits(spec, params, prompts, N_NEW, 32, dtype)
    want = _reference_logits(reference, cfg, weights, prompts, toks)
    err = float(np.max(np.abs(got - want)))
    if fails:
        assert err > 1e-4, err
    else:
        assert err < 1e-4, err


@pytest.mark.parametrize("sharing", [False, True],
                         ids=["prefill", "shared_prefix_chunk"])
def test_engine_greedy_tokens_are_the_served_argmax(cfg, weights, model,
                                                    prompts, sharing):
    """The engine (scheduler, buckets, chunked decode, the pick) in f32
    serves the tokens whose logits the test above compares; with prefix
    sharing every admission runs the chunk program (rotary positions,
    the windows, the expert rows), and the tokens are the same."""
    spec = model.decoder_spec()
    toks, _ = _serve_logits(spec, model.decoder_params(), prompts, N_NEW,
                            32, "float32")
    e = dict(cfg["engine"], dtype=None, prefill_buckets=(16, 32, 64),
             prefix_sharing=sharing)
    eng = ServingEngine(model, ServingConfig(**e)).warmup()
    got = eng.generate_tokens(prompts, N_NEW)
    assert [list(t) for t in toks] == [list(g) for g in got]


def test_packed_prefill_matches_the_row_prefill(cfg, model, prompts):
    """The expert block with window layers (window 8, prompts of 5-30
    tokens laid end to end from page boundaries): the packed row gives
    each prompt the first token and the K/V rows of its pages that a
    row a prompt gives; the padding between and after the prompts is
    routed to no expert."""
    from test_serving_engine import assert_same_prefill, packed_against_rows
    assert max(PROMPT_LENS) > cfg["sliding_window"]
    by_row, by_pack, tables = packed_against_rows(
        model.decoder_spec(), model.decoder_params(), prompts, BLOCK,
        WIDTH, 32)
    assert_same_prefill(by_row, by_pack, tables, PROMPT_LENS, BLOCK)
    rows = np.asarray(by_pack[2])[0]                 # [layers, experts]
    np.testing.assert_array_equal(rows, np.asarray(by_row[2])[0])
    routed = sum(PROMPT_LENS) * cfg["num_experts_per_tok"]
    assert (rows.sum(axis=1) == routed).all()


def test_forward_matches_the_reference(reference, cfg, weights, model,
                                       prompts):
    seq = prompts[2][None]
    assert seq.shape[1] > cfg["sliding_window"]
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.forward(seq)._data)
        want = np.asarray(reference.logits(weights, cfg, jnp.asarray(seq)))
    np.testing.assert_allclose(got, want, atol=1e-4)


# -- (a): the paged decode kernel with groups and a window -------------------

BS, W_PAGES, N_BLOCKS = 4, 11, 48


@pytest.mark.parametrize("group,window", [(1, None), (1, 9), (4, None),
                                          (4, 9), (4, 3)])
def test_paged_decode_kernel_groups_and_window(group, window):
    """Query head i reads K/V head i // group of a page's n_kv * hd
    lanes; with a window a slot reads its last `window` positions
    only. Against the gather and `masked_attention`: f32 within 1e-5;
    an inactive lane's row is 0."""
    n_kv, hd = (2, 128) if group > 1 else (4, 16)
    nh = n_kv * group
    rng = np.random.default_rng(group * 10 + (window or 0))
    mk = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    kp, vp = mk(N_BLOCKS, BS, n_kv * hd), mk(N_BLOCKS, BS, n_kv * hd)
    q = mk(4, nh, hd)
    pages = rng.permutation(N_BLOCKS - 1) + 1
    tables = np.zeros((4, W_PAGES), np.int32)
    tables[0] = pages[:W_PAGES]
    tables[1, :5] = pages[W_PAGES:W_PAGES + 5]
    tables[3, :9] = pages[W_PAGES + 5:W_PAGES + 14]
    lengths = np.asarray([BS * W_PAGES, 2 * BS + 3, 1, 8 * BS + 2], np.int32)
    tables, lengths = jnp.asarray(tables), jnp.asarray(lengths)
    scale = 1.0 / math.sqrt(hd)
    got = pk.paged_decode_attention(q, kp, vp, tables, lengths, scale,
                                    interpret=True, window=window)
    assert got.shape == q.shape
    assert not np.asarray(got[2]).any()
    live = np.asarray([0, 1, 3])
    kc = programs._gathered(kp, tables[live], n_kv, hd)
    vc = programs._gathered(vp, tables[live], n_kv, hd)
    mask = decoder.prefix_mask(kc.shape[2], lengths[live])
    if window is not None:
        mask = mask & (jnp.arange(kc.shape[2])[None, None, None, :]
                       >= (lengths[live] - window)[:, None, None, None])
    want = decoder.masked_attention(q[live][:, None], kc, vc, mask,
                                    scale)[:, 0]
    np.testing.assert_allclose(np.asarray(got[live]), np.asarray(want),
                               atol=1e-5)


# -- (b): the flash prefill kernel with groups and a window ------------------

@pytest.mark.parametrize("group,window", [(1, None), (2, None), (1, 100),
                                          (2, 700), (4, 513)])
def test_flash_prefill_kernel_groups_and_window(group, window):
    """Against `masked_attention` under `causal_mask(S, lens, window)`
    on every true query, f32 within 1e-5."""
    s, n_kv, hd = 1024, 2, 128
    nh = n_kv * group
    rng = np.random.default_rng(group * 100 + (window or 0))
    q = jnp.asarray(rng.standard_normal((3, s, nh, hd)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((3, s, n_kv, hd)), jnp.float32)
            for _ in range(2))
    lens = jnp.asarray([1024, 77, 600], jnp.int32)
    scale = 1.0 / math.sqrt(hd)
    got = pk.flash_prefill_attention(q, k, v, lens, scale, interpret=True,
                                     window=window)
    kc, vc = (jnp.einsum("bsnh->bnsh", t) for t in (k, v))
    want = decoder.masked_attention(q, kc, vc,
                                    decoder.causal_mask(s, lens, window),
                                    scale)
    for r, n in enumerate(np.asarray(lens)):
        np.testing.assert_allclose(np.asarray(got[r, :n]),
                                   np.asarray(want[r, :n]), atol=1e-5)


# -- (c): YaRN against its closed form ---------------------------------------

@pytest.mark.parametrize("kind", ["full_attention", "sliding_attention"])
def test_rope_table_against_the_closed_form(kind):
    """The published numbers (head 128, theta 500,000, YaRN factor 16
    over 8,192 positions, beta 32 / 1, attention factor 1.2773 on the
    full layers): angles and scale as transformers computes them."""
    with open(os.path.join(CONFIGS, NAME + ".json")) as f:
        c = json.load(f)
    p = c["rope_parameters"][kind]
    spec = MoEConfig(num_layers=1, layer_types=[kind],
                     rope_theta=p["rope_theta"],
                     rope_scaling=c["rope_parameters"]["full_attention"]
                     ).decoder_spec()
    # up to the cell's longest request (an f32 angle of a few thousand
    # radians is good to a few 1e-4)
    positions = np.asarray([0, 1, 17, 1023, 2047, 5215])
    cos, sin = decoder._rope_table(spec, jnp.asarray(positions),
                                   spec.ropes[0])
    d, theta = 128, float(p["rope_theta"])
    base = theta ** (np.arange(0, d, 2) / d)
    inv, scale = 1.0 / base, 1.0
    if p["rope_type"] == "yarn":
        f, orig = p["factor"], p["original_max_position_embeddings"]
        dim = lambda r: d * math.log(orig / (r * 2 * math.pi)) \
            / (2 * math.log(theta))
        low = max(math.floor(dim(p["beta_fast"])), 0)
        high = min(math.ceil(dim(p["beta_slow"])), d - 1)
        ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
        inv = inv / f * ramp + inv * (1 - ramp)
        scale = p["attention_factor"]
        assert (low, high) == (18, 35)
    ang = positions[:, None] * np.concatenate([inv, inv])[None]
    np.testing.assert_allclose(np.asarray(cos)[:, 0], scale * np.cos(ang),
                               atol=1e-3 * scale)
    np.testing.assert_allclose(np.asarray(sin)[:, 0], scale * np.sin(ang),
                               atol=1e-3 * scale)
    near = positions < 32           # f32 angles are exact enough there
    np.testing.assert_allclose(np.asarray(cos)[near, 0],
                               scale * np.cos(ang[near]), atol=1e-5)


# -- (d), (e): the dropless expert layer -------------------------------------

E, K, H, F = 8, 2, 16, 12


def _expert_case(seed=0, tie=True):
    rng = np.random.default_rng(seed)
    mk = lambda *shape: jnp.asarray(rng.standard_normal(shape) * 0.5,
                                    jnp.float32)
    router = mk(H, E)
    if tie:
        # experts 5 and 2 score alike for every token: ties to the lower id
        router = router.at[:, 5].set(router[:, 2])
    bp = {"router_w": router, "experts_gate_up_w": mk(E, H, 2 * F),
          "experts_down_w": mk(E, F, H)}
    return bp, mk(3, 7, H)


def _spec(held=None):
    return decoder.DecoderSpec(
        eps=1e-6, n_heads=2, head_dim=8, norm="rms", bias=False,
        n_kv_heads=1, mlp="moe", tied_head=False, n_experts=E, top_k=K,
        expert_width=F, experts_held=held)


def _per_token(bp, h, held=(0, E)):
    """Each token alone: its probabilities over all experts, the top K
    by (probability, then the lower id), renormalised; the held ones'
    SwiGLUs, weighted."""
    h = np.asarray(h, np.float64).reshape(-1, H)
    out = np.zeros_like(h)
    loads = np.zeros(E, int)
    for t, x in enumerate(h):
        lg = x @ np.asarray(bp["router_w"], np.float64)
        p = np.exp(lg - lg.max())
        p /= p.sum()
        top = sorted(range(E), key=lambda e: (-np.float32(p[e]), e))[:K]
        w = p[top] / p[top].sum()
        for e, we in zip(top, w):
            if not held[0] <= e < held[1]:
                continue
            loads[e] += 1
            gu = x @ np.asarray(bp["experts_gate_up_w"][e], np.float64)
            g, u = gu[:F], gu[F:]
            out[t] += we * ((g / (1 + np.exp(-g)) * u)
                            @ np.asarray(bp["experts_down_w"][e],
                                         np.float64))
    return out, loads


def _held_params(bp, lo, hi):
    return dict(bp, experts_gate_up_w=bp["experts_gate_up_w"][lo:hi],
                experts_down_w=bp["experts_down_w"][lo:hi])


def test_expert_layer_against_a_per_token_loop():
    bp, h = _expert_case()
    with jax.default_matmul_precision("highest"):
        got, load = decoder._experts(_spec(), bp, h, None)
    want, loads = _per_token(bp, h)
    np.testing.assert_allclose(np.asarray(got).reshape(-1, H), want,
                               atol=1e-5)
    assert list(np.asarray(load)) == list(loads)
    assert loads[2] > 0     # the tie was met, and went to the lower id


@pytest.mark.parametrize("split", [4, 3])
def test_expert_shares_add_up_to_the_whole_layer(split):
    """A layer told it holds experts [0, split) and one told [split, E)
    each route over all E (the weights are renormalised over a token's
    whole top K, held or not) and give their own experts' part: the two
    parts sum to the whole layer, their row counts too. Tokens not live
    are routed nowhere."""
    bp, h = _expert_case(1, tie=False)
    live = jnp.asarray(np.random.default_rng(2).random((3, 7)) < 0.7)
    with jax.default_matmul_precision("highest"):
        whole, lw = decoder._experts(_spec(), bp, h, live)
        a, la = decoder._experts(_spec((0, split)),
                                 _held_params(bp, 0, split), h, live)
        b, lb = decoder._experts(_spec((split, E)),
                                 _held_params(bp, split, E), h, live)
    np.testing.assert_allclose(np.asarray(a + b), np.asarray(whole),
                               atol=1e-5)
    assert (np.asarray(la)[split:] == 0).all()
    assert (np.asarray(lb)[:split] == 0).all()
    assert list(np.asarray(la + lb)) == list(np.asarray(lw))
    assert int(np.asarray(lw).sum()) == K * int(np.asarray(live).sum())
    want, _ = _per_token(bp, h, (0, split))
    keep = np.asarray(live).reshape(-1)[:, None]
    np.testing.assert_allclose(np.asarray(a).reshape(-1, H),
                               want * keep, atol=1e-5)
    assert not np.asarray(whole)[~np.asarray(live)].any()


# -- (h): what the engine refuses for an expert model ------------------------

@pytest.mark.parametrize("option,match", [
    ("tp", "tp plan is not supported for an expert model"),
    (dict(quant="int8"), "quant is not supported for an expert model")])
def test_engine_refuses_what_the_expert_layer_cannot_do(cfg, model, option,
                                                        match):
    if option == "tp":
        from paddle_tpu.distributed import MeshPlan
        option = dict(plan=MeshPlan(tp=2))
    e = dict(cfg["engine"], prefill_buckets=(16, 32, 64), **option)
    with pytest.raises(ValueError, match=match):
        ServingEngine(model, ServingConfig(**e))


def test_traced_sync_carries_the_expert_rows(cfg, model, prompts):
    """A traced step's `sync` carries the dispatch's expert rows, the
    sum over layers of the busiest expert's and the experts hit (over
    layers and token-steps); a prefill of n true tokens routes n x
    top_k rows in every layer."""
    from paddle_tpu.observability import reqtrace
    e = dict(cfg["engine"], dtype=None, prefill_buckets=(16, 32, 64))
    eng = ServingEngine(model, ServingConfig(**e)).warmup()
    reqtrace.enable(True, capacity=4096)
    try:
        eng.generate_tokens(prompts[:2], 3)
        syncs = [ev for ev in reqtrace.get_tracer().events()
                 if ev.get("comp") == "sync"]
    finally:
        reqtrace.disable()
    layers, k = cfg["num_hidden_layers"], cfg["num_experts_per_tok"]
    first = [ev for ev in syncs if ev.get("kind") == "prefill"][0]
    assert first["expert_rows"] == layers * k * (
        PROMPT_LENS[0] + PROMPT_LENS[1])
    for ev in syncs:
        # experts hit: a decode dispatch counts each of its token-steps
        steps = cfg["engine"]["decode_chunk"] if ev["kind"] == "decode" \
            else 1
        assert layers <= ev["experts_hit"] \
            <= layers * cfg["num_experts"] * steps
        assert ev["expert_rows"] / layers / cfg["num_experts"] \
            <= ev["expert_rows_peak"] / layers <= ev["expert_rows"] / layers
    decodes = [ev for ev in syncs if ev.get("kind") == "decode"]
    assert decodes and all(ev["expert_rows"] > 0 for ev in decodes)
