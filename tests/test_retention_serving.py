"""A decoder of retention layers behind the serving engine, held to its
plain reference (perfbench/configs/brumby-14b-base.reference.py: float32,
the quadratic form over the whole sequence, no state) at toy widths on
the CPU: hidden 64, 4 query and 2 key-value heads of 16, 2 layers,
vocabulary 512, seeded random weights. Compared on LOGITS: with random
weights the largest logit changes on rounding, so tokens alone would
say little.

What is served here is what `serving/programs.py` runs: `decoder.blocks`
under the retention prefill addressing (a bucketed batch of right-padded
prompts of unequal length), then one token a lane under the decode
addressing through the state rows. The engine adds the scheduler and
the pick, which the last tests drive.
"""
import functools
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import RetentionConfig, RetentionForCausalLM, decoder
from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.serving import ServingConfig, ServingEngine, programs
from paddle_tpu.serving.state_cache import StateCache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, "perfbench", "configs")


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "brumby_reference",
        os.path.join(CONFIGS, "brumby-14b-base.reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(CONFIGS, "brumby-14b-base.json")) as f:
        full = json.load(f)
    return {**full, **full["toy"]}


def _model(cfg, weights):
    """RetentionForCausalLM at the toy widths over the reference's
    weights (the benchmark's recipe, brumby-14b-base.program.py)."""
    model = RetentionForCausalLM(RetentionConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        intermediate_size=cfg["intermediate_size"], max_seq_len=128,
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"]))
    model.eval()
    state = model.state_dict()
    assert sorted(state) == sorted(weights)
    for name, tensor in state.items():
        assert tuple(tensor.shape) == tuple(weights[name].shape), name
        tensor._data = weights[name]
    return model


@pytest.fixture(scope="module")
def weights(reference, cfg):
    return reference.make_params(cfg, jax.random.key(7), "float32")


@pytest.fixture(scope="module")
def model(cfg, weights):
    return _model(cfg, weights)


PROMPT_LENS = (5, 17, 30, 9)
N_NEW = 9


@pytest.fixture(scope="module")
def prompts(cfg):
    rng = np.random.default_rng(3)
    return [rng.integers(0, cfg["vocab_size"], n).astype(np.int32)
            for n in PROMPT_LENS]


def _cast(tree, dtype):
    return jax.tree_util.tree_map(lambda a: a.astype(dtype), tree)


def _pools(spec, n_layers, n_rows, dtype=jnp.float32):
    return StateCache(n_layers, n_rows, spec.n_kv_heads, spec.head_dim,
                      dtype).pools


def _serve_logits(spec, params, pools, prompts, n_new, bucket, chunk=2,
                  one_pass=None):
    """The serving programs' arithmetic with the logits kept: one
    bucketed prefill of all the prompts (row i + 1 of the state for
    prompt i, one padded lane on the scratch row), then greedy decode
    through the state rows in chunks of `chunk` token-steps as the
    engine's scan has them: every token-step reads the rows, the last
    of a chunk (and the last of all) writes them. `one_pass` is the
    pass over the rows (default: the platform's). Returns (tokens
    [n, n_new], logits [n, n_new, V], pools)."""
    n = len(prompts)
    ids = np.zeros((n + 1, bucket), np.int32)
    lens = np.ones((n + 1,), np.int32)
    for i, p in enumerate(prompts):
        ids[i, :p.size], lens[i] = p, p.size
    rows = jnp.asarray(list(range(1, n + 1)) + [0], jnp.int32)
    lens = jnp.asarray(lens)
    x, pools = decoder.blocks(
        spec, params, decoder.embed(params, jnp.asarray(ids), None), pools,
        programs._retention_prefill_addressing(rows, lens),
        jnp.arange(bucket))
    last = jnp.take_along_axis(x, (lens - 1)[:, None, None], axis=1)
    logits = [decoder.final_logits(spec, params, last)[:, 0]]
    positions = lens
    kv, hd = spec.n_kv_heads, spec.head_dim
    for s in range(n_new - 1):
        t = s % chunk
        if t == 0:
            empty = (jnp.zeros((n + 1, kv, chunk, hd)),) * 2 \
                + (jnp.zeros((n + 1, kv, chunk)),)
            caches = tuple((state, empty) for state in pools)
        attend = programs._retention_decode_addressing(
            rows, t, t == chunk - 1 or s == n_new - 2, one_pass)
        tok = jnp.argmax(logits[-1].astype(jnp.float32), axis=-1)
        x, caches = decoder.blocks(
            spec, params, decoder.embed(params, tok, positions)[:, None],
            caches, attend, positions[:, None])
        pools = tuple(state for state, _ in caches)
        logits.append(decoder.final_logits(spec, params, x)[:, 0])
        positions = positions + 1
    logits = np.asarray(jnp.stack(logits, axis=1), np.float32)[:n]
    return logits.argmax(-1), logits, pools


def _reference_logits(reference, cfg, weights, prompts, tokens):
    """The reference's full forward over prompt + served tokens, at
    every served position: [n, n_new, V]."""
    out = []
    for p, t in zip(prompts, tokens):
        seq = np.concatenate([p, t.astype(np.int32)])[None]
        lg = np.asarray(reference.logits(weights, cfg, jnp.asarray(seq)))[0]
        out.append(lg[p.size - 1:p.size - 1 + t.size])
    return np.stack(out)


# -- the reference against itself ---------------------------------------------

def test_reference_recurrence_equals_its_quadratic_form(reference, cfg,
                                                        weights):
    """phi in the textbook order, the state S += phi(k) v^T scaled by
    e^gate BEFORE the token is added: equal to (q.k)^2 exp(Gam_t -
    Gam_s) over the whole sequence. Ties the feature map and the
    gate's direction to the kernel."""
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg["vocab_size"], (2, 40)))
    quad = np.asarray(reference.logits(weights, cfg, ids))
    rec = np.asarray(reference.logits(weights, cfg, ids, recurrent=True))
    np.testing.assert_allclose(rec, quad, rtol=1e-5,
                               atol=1e-5 * np.abs(quad).max())


def test_reference_recurrence_over_the_full_power_equals_it_too(reference):
    """The state's control keeps x x^T whole (no gather at the cell's
    size): the same kernel, so the same mixer."""
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(2, 24, 4, 16)), jnp.float32)
    k, v = jnp.asarray(rng.normal(size=(2, 2, 24, 2, 16)), jnp.float32)
    gam = jnp.log(jnp.asarray(rng.uniform(0.5, 0.999, (2, 24, 2)),
                              jnp.float32))
    quad = np.asarray(reference.retention_quadratic(q, k, v, gam))
    full = np.asarray(reference.retention_recurrent(
        q, k, v, gam, features=reference.phi_full))
    np.testing.assert_allclose(full, quad, rtol=1e-4, atol=1e-5)


def test_reference_s_bf16_state_control_is_the_reference_but_for_the_state(
        reference, cfg, weights):
    """`precision="state-bfloat16"` (what `correct.control_serve` hands
    `score`): float32 everywhere, S and z rounded a token. Off the
    reference by the rounding, and by no more than that."""
    rng = np.random.default_rng(6)
    ids = rng.integers(0, cfg["vocab_size"], (2, 40)).astype(np.int32)
    quad = np.asarray(reference.logits(weights, cfg, jnp.asarray(ids)))
    low = np.asarray(reference.logits(weights, cfg, jnp.asarray(ids),
                                      precision=reference.STATE_BF16))
    # in the mean: the widest is a position whose normaliser is small
    off = np.sqrt(np.mean(np.square(low - quad)) / np.mean(np.square(quad)))
    assert 1e-3 < off < 0.2, off
    picks = np.zeros((1,) + ids.shape, np.int32)
    best, first, picked, margin = reference.score(
        weights, cfg, ids, picks, precision=reference.STATE_BF16,
        block_rows=2)
    assert first.shape == ids.shape and np.isfinite(best).all()


def test_reference_features_are_the_squared_dot_product(reference):
    rng = np.random.default_rng(1)
    x, y = rng.normal(size=(2, 5, 16)).astype(np.float32)
    want = np.square(np.sum(x * y, axis=-1))
    got = np.sum(np.asarray(reference.phi(jnp.asarray(x)))
                 * np.asarray(reference.phi(jnp.asarray(y))), axis=-1)
    assert reference.phi(jnp.asarray(x)).shape[-1] == 16 * 17 // 2
    np.testing.assert_allclose(got, want, rtol=1e-5)
    # the program's order of the same pairs (circular offsets)
    ours = np.sum(np.asarray(decoder.retention_features(jnp.asarray(x)))
                  * np.asarray(decoder.retention_features(jnp.asarray(y))),
                  axis=(-2, -1))
    np.testing.assert_allclose(ours, want, rtol=1e-5)


def test_reference_forward_flops_counts_the_issue_s_terms(reference):
    with open(os.path.join(CONFIGS, "brumby-14b-base.json")) as f:
        full = json.load(f)
    a_layer = 2 * 330_301_440 + 2 * (40 + 8) * 8256 * 128
    head = 2 * 5120 * 151_936
    assert reference.forward_flops(full, 0, 1, 1) == 4 * a_layer + head
    assert reference.forward_flops(full, 100, 7, 0) == 7 * 4 * a_layer
    assert reference.state_features(full) == 8256


# -- the served arithmetic against the reference, on logits -------------------

def test_model_forward_is_the_reference(reference, cfg, weights, model):
    rng = np.random.default_rng(2)
    ids = rng.integers(0, cfg["vocab_size"], (2, 33)).astype(np.int32)
    got = np.asarray(model(paddle.to_tensor(ids))._data)
    want = np.asarray(reference.logits(weights, cfg, jnp.asarray(ids)))
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_f32_serving_equals_the_reference_and_a_bf16_state_does_not(
        reference, cfg, weights, model, prompts):
    """Bucketed prefill (lengths 5, 17, 30, 9 in a bucket of 32), then
    decode through the state rows: every served position's logits
    within 1e-4 of the reference's full forward (logits are of order
    1; float32 both sides, the orders of summation differ). The
    planted fault: the same float32 arithmetic over a bfloat16 STATE
    (8 bits of a sum that runs over the whole request) fails that
    limit."""
    spec, params = model.decoder_spec(), model.decoder_params()
    toks, got, _ = _serve_logits(spec, params, _pools(spec, 2, 6),
                                 prompts, N_NEW, 32)
    want = _reference_logits(reference, cfg, weights, prompts, toks)
    assert np.abs(got - want).max() < 1e-4
    assert (toks == want.argmax(-1)).all()
    toks_lo, got_lo, _ = _serve_logits(
        spec, params, _pools(spec, 2, 6, jnp.bfloat16), prompts, N_NEW, 32)
    want_lo = _reference_logits(reference, cfg, weights, prompts, toks_lo)
    assert np.abs(got_lo - want_lo).max() > 1e-4


def test_bf16_serving_stays_near_the_reference(reference, cfg, weights,
                                               model, prompts):
    """The benchmark's precision: bfloat16 weights and activations, a
    float32 state. Every matmul's operands carry 8 bits (a relative
    4e-3 each), through 2 layers and the head onto logits of order 1:
    within 0.06 absolute, and a mean error under 0.015. (The reference
    is given the same bfloat16-rounded weights, so this reads the
    arithmetic's error, not the rounding of the weights.)"""
    spec = model.decoder_spec()
    lo = _cast(weights, jnp.bfloat16)
    params = _cast(model.decoder_params(), jnp.bfloat16)
    toks, got, _ = _serve_logits(spec, params, _pools(spec, 2, 6), prompts,
                                 N_NEW, 32)
    want = _reference_logits(reference, cfg, _cast(lo, jnp.float32),
                             prompts, toks)
    assert np.abs(got - want).max() < 0.06
    assert np.abs(got - want).mean() < 0.015


# -- what a state row may and may not see -------------------------------------

@pytest.mark.parametrize("decay", ["config", "fast"])
def test_a_padded_rows_junk_leaves_its_state_and_logits_alone(
        model, prompts, decay):
    """One prompt prefilled in a bucket that just holds it and in one
    four times wider: the same state row and the same first logits.
    With `fast` decays (a gate bias of -3: 0.05 a token) a junk query
    60 tokens past the prompt's end weighs every live key at 0 after
    underflow: it must come out finite (0, not 0/0), or the next layer
    multiplies NaN at weight 0 into the true rows and the state."""
    spec, params = model.decoder_spec(), model.decoder_params()
    if decay == "fast":
        params = dict(params, blocks=[
            dict(bp, g_b=jnp.full_like(bp["g_b"], -3.0))
            for bp in params["blocks"]])
    one = [prompts[3]]                      # 9 tokens
    _, narrow, p_narrow = _serve_logits(spec, params, _pools(spec, 2, 3),
                                        one, 2, 16)
    _, wide, p_wide = _serve_logits(spec, params, _pools(spec, 2, 3),
                                    one, 2, 64)
    assert np.isfinite(wide).all()
    np.testing.assert_allclose(wide, narrow, atol=2e-6)
    for (s_n, z_n), (s_w, z_w) in zip(p_narrow, p_wide):
        np.testing.assert_allclose(np.asarray(s_w[1]), np.asarray(s_n[1]),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(z_w[1]), np.asarray(z_n[1]),
                                   rtol=1e-5, atol=1e-6)


def _step_inputs(rng, spec, b):
    nh, kv, hd = spec.n_heads, spec.n_kv_heads, spec.head_dim
    return (jnp.asarray(rng.normal(size=(b, nh, hd)), jnp.float32),
            jnp.asarray(rng.normal(size=(b, kv, hd)), jnp.float32),
            jnp.asarray(rng.normal(size=(b, kv, hd)), jnp.float32),
            jnp.asarray(-rng.uniform(0.01, 0.3, size=(b, kv)), jnp.float32))


def _random_state(rng, spec, n_rows):
    s, z = _pools(spec, 1, n_rows)[0]
    s = jnp.asarray(rng.normal(size=s.shape), jnp.float32)
    feats = spec.head_dim // 2 + 1
    z = jnp.asarray(rng.normal(size=z.shape) + 3.0, jnp.float32)
    return s, z.at[:, :, feats:].set(0.0)


def _chunk_inputs(rng, spec, b, n, decay=None):
    """n token-steps of inputs for b lanes: q, k, v as `_step_inputs`,
    the gates all log(decay) where a decay is given."""
    steps = [_step_inputs(rng, spec, b) for _ in range(n)]
    if decay is not None:
        steps = [(q, k, v, jnp.full_like(g, np.log(decay)))
                 for q, k, v, g in steps]
    return steps


def _empty_chunk(spec, b, n):
    kv, hd = spec.n_kv_heads, spec.head_dim
    return (jnp.zeros((b, kv, n, hd)),) * 2 + (jnp.zeros((b, kv, n)),)


def _run_chunk(state, rows, steps, one_pass, spec):
    """A whole chunk through `retention_chunk_step`, written at its
    last token-step: ([ctx a token-step], state after each)."""
    chunk = _empty_chunk(spec, rows.shape[0], len(steps))
    ctxs, states = [], []
    for t, (q, k, v, g) in enumerate(steps):
        chunk = decoder.retention_chunk_push(chunk, t, k, v, g)
        ctx, state = decoder.retention_chunk_step(
            state, rows, q, chunk, t, t == len(steps) - 1, one_pass)
        ctxs.append(ctx)
        states.append(state)
    return ctxs, states


_PASSES = {"jnp": decoder.retention_pass,
           "kernel": functools.partial(pk.retention_decode, interpret=True)}


def _prefilled_state(rng, spec, n_rows, n_tokens=12):
    """Rows as a prefill leaves them: 12 tokens of random k and v at
    decays 0.5-0.999 (z a positive sum of features, so phi(q).z is a
    sum of squares, as it is in a served row)."""
    kv, hd = spec.n_kv_heads, spec.head_dim
    k, v = (jnp.asarray(rng.normal(size=(n_rows, n_tokens, kv, hd)),
                        jnp.float32) for _ in range(2))
    gate = jnp.log(jnp.asarray(rng.uniform(0.5, 0.999,
                                           (n_rows, n_tokens, kv)),
                               jnp.float32))
    return decoder.retention_state(k, v, gate,
                                   jnp.full((n_rows,), n_tokens))


@pytest.mark.parametrize("step", ["jnp", "kernel"])
def test_a_dead_lane_changes_only_the_scratch_row(model, step):
    """A whole chunk of 3 token-steps, two dead lanes on row 0: the
    token-steps before the last write no row but the scratch row, and
    after the flush every row nobody holds is bit-equal to what it
    was."""
    spec = model.decoder_spec()
    rng = np.random.default_rng(4)
    state = _random_state(rng, spec, 5)
    rows = jnp.asarray([3, 0, 0], jnp.int32)
    _, states = _run_chunk(state, rows, _chunk_inputs(rng, spec, 3, 3),
                           _PASSES[step], spec)
    for mid in states[:-1]:
        for old, new in zip(state, mid):
            assert (np.asarray(new)[1:] == np.asarray(old)[1:]).all()
    for old, new in zip(state, states[-1]):
        old, new = np.asarray(old), np.asarray(new)
        for r in (1, 2, 4):
            assert (new[r] == old[r]).all()
        assert not (new[3] == old[3]).all()


@pytest.mark.parametrize("decay", [0.05, 0.5, 0.999])
@pytest.mark.parametrize("n_steps", [1, 2, 3, 4])
def test_a_chunk_equals_its_token_steps_one_by_one(model, n_steps, decay):
    """The chunked form (rows read every token-step, written at the
    chunk's last) against n `retention_step`s that read and write every
    token-step: each token-step's context, and S and z after the chunk,
    within float32 rounding; dead lanes on row 0. A decay of 0.05 a
    token is the fast gate under which a junk query's weights
    underflow."""
    spec = model.decoder_spec()
    rng = np.random.default_rng(7 + n_steps)
    state = _prefilled_state(rng, spec, 5)
    rows = jnp.asarray([2, 0, 4, 0], jnp.int32)
    steps = _chunk_inputs(rng, spec, 4, n_steps, decay)
    ctxs, states = _run_chunk(state, rows, steps, decoder.retention_pass,
                              spec)
    ref = state
    for (q, k, v, g), got in zip(steps, ctxs):
        want, ref = decoder.retention_step(ref, rows, q, k, v, g)
        live = np.asarray(want)[[0, 2]]
        np.testing.assert_allclose(np.asarray(got)[[0, 2]], live,
                                   rtol=1e-4, atol=1e-5 * np.abs(live).max())
    for new, old, before in zip(states[-1], ref, state):
        new = np.asarray(new)
        np.testing.assert_allclose(new[[2, 4]], np.asarray(old)[[2, 4]],
                                   rtol=1e-5, atol=1e-5)
        assert (new[[1, 3]] == np.asarray(before)[[1, 3]]).all()


def test_a_row_given_to_a_new_request_starts_from_that_request_alone(
        model, prompts):
    """The prefill WRITES the row: over whatever an earlier request
    left there (here: ones) it leaves what it leaves in a zero row."""
    spec, params = model.decoder_spec(), model.decoder_params()
    clean = _pools(spec, 2, 3)
    used = jax.tree_util.tree_map(jnp.ones_like, clean)
    _, lg_clean, p_clean = _serve_logits(spec, params, clean, [prompts[1]],
                                         3, 32)
    _, lg_used, p_used = _serve_logits(spec, params, used, [prompts[1]],
                                       3, 32)
    assert (lg_clean == lg_used).all()
    for (s_c, z_c), (s_u, z_u) in zip(p_clean, p_used):
        assert (np.asarray(s_c[1]) == np.asarray(s_u[1])).all()
        assert (np.asarray(z_c[1]) == np.asarray(z_u[1])).all()
        assert (np.asarray(s_u[2]) == 1.0).all()    # nobody's row: kept


# -- the kernel against the jax.numpy step ------------------------------------

@pytest.mark.parametrize("write", [False, True], ids=["read", "write"])
@pytest.mark.parametrize("heads", [(4, 2, 16), (10, 2, 128), (8, 8, 32)],
                         ids=["toy", "grp5_hd128", "grp1_hd32"])
def test_retention_decode_kernel_equals_the_jnp_pass(heads, write):
    """Interpret mode, a chunk of 3 keys at its last token-step:
    phi(q)^T S and phi(q).z of the visited rows within float32 rounding
    of `decoder.retention_pass`; S and z written as the jnp pass writes
    them, or, in a read-only step, not at all (bit-equal); every row
    nobody visits bit-equal; z's padding rows stay zero."""
    nh, kv, hd = heads
    spec = RetentionConfig(num_heads=nh, num_kv_heads=kv, head_dim=hd
                           ).decoder_spec()
    rng = np.random.default_rng(5)
    state = _random_state(rng, spec, 4)
    rows = jnp.asarray([2, 0, 3], jnp.int32)
    q = _step_inputs(rng, spec, 3)[0]
    keys, vals = (jnp.asarray(rng.normal(size=(3, kv, 3, hd)), jnp.float32)
                  for _ in range(2))
    decay, w = decoder.retention_chunk_weights(
        jnp.asarray(-rng.uniform(0.01, 0.3, (3, kv, 3)), jnp.float32), 2)
    args = (state, rows, q, keys, vals, decay, w, write)
    n_w, d_w, (s_w, z_w) = decoder.retention_pass(*args)
    n_g, d_g, (s_g, z_g) = pk.retention_decode(*args, interpret=True)
    for got, want in ((n_g, n_w), (d_g, d_w)):
        scale = float(np.abs(np.asarray(want)).max())
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-6 * scale)
    if write:
        np.testing.assert_allclose(np.asarray(s_g), np.asarray(s_w),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(z_g), np.asarray(z_w),
                                   atol=1e-5)
    else:       # the scratch row 0 may take a block of junk
        assert (np.asarray(s_g)[1:] == np.asarray(state[0])[1:]).all()
        assert (np.asarray(z_g)[1:] == np.asarray(state[1])[1:]).all()
    assert (np.asarray(s_g[1]) == np.asarray(state[0][1])).all()
    assert (np.asarray(z_g)[:, :, hd // 2 + 1:] == 0.0).all()


def test_retention_decode_refuses_a_state_that_is_not_float32(model):
    spec = model.decoder_spec()
    rng = np.random.default_rng(6)
    state = _cast(_random_state(rng, spec, 2), jnp.bfloat16)
    q, k, v, g = _step_inputs(rng, spec, 1)
    with pytest.raises(ValueError, match="float32"):
        pk.retention_decode(state, jnp.zeros((1,), jnp.int32), q,
                            k[:, :, None], v[:, :, None], g, g[..., None],
                            True, interpret=True)


def test_greedy_tokens_equal_through_the_kernel_and_the_jnp_step(
        model, prompts):
    spec, params = model.decoder_spec(), model.decoder_params()
    t_jnp, lg_jnp, _ = _serve_logits(spec, params, _pools(spec, 2, 6),
                                     prompts, N_NEW, 32)
    t_ker, lg_ker, _ = _serve_logits(
        spec, params, _pools(spec, 2, 6), prompts, N_NEW, 32,
        one_pass=_PASSES["kernel"])
    assert (t_jnp == t_ker).all()
    np.testing.assert_allclose(lg_ker, lg_jnp, atol=1e-5)


# -- the cache and the engine -------------------------------------------------

def test_state_cache_rows():
    cache = StateCache(n_layers=2, n_rows=4, n_kv_heads=2, head_dim=16)
    s, z = cache.pools[0]
    assert s.shape == (4, 2, 9, 16, 16) and z.shape == (4, 2, 16, 16)
    assert s.dtype == z.dtype == jnp.float32
    assert (cache.n_live, cache.n_free, cache.available_pages) == (0, 3, 3)
    rows = [cache.alloc(rid, 100) for rid in "abc"]
    assert sorted(rows) == [1, 2, 3] and cache.n_live == 3
    assert cache.blocks_for(5) == cache.blocks_for(5000) == 1
    with pytest.raises(MemoryError):
        cache.alloc("d")
    with pytest.raises(ValueError):
        cache.alloc("a")
    assert list(cache.table_array(["b", None, "a"], 7)) == [rows[1], 0,
                                                           rows[0]]
    assert cache.free("b") == rows[1]
    assert (cache.n_live, cache.n_free) == (2, 1)
    assert cache.alloc("d") == rows[1]      # a freed row is the next given
    cache.check_invariants()
    with pytest.raises(ValueError):
        StateCache(1, 1, 2, 16)


def _engine(model, **kw):
    cfg = dict(max_slots=3, max_admit=2, block_size=8, n_blocks=4,
               prefill_buckets=(16, 32), decode_chunk=2,
               max_total_tokens=64, dtype=None)
    return ServingEngine(model, ServingConfig(**{**cfg, **kw}))


@pytest.mark.parametrize("decode_chunk", [1, 2, 4])
def test_engine_serves_the_reference_s_greedy_tokens(reference, cfg,
                                                     weights, model,
                                                     prompts, decode_chunk):
    """Through `ServingEngine.step()`, the FIFO scheduler and the
    bucket ladder: four requests over three slots (the fourth waits
    for a row, and gets a used one), admitted two at a time, decoded
    in chunks of 1, 2 and 4 token-steps (a row written every
    token-step, every second, every fourth): each request's tokens are
    the reference's argmax at every served position. Rows come back;
    the ladder holds."""
    eng = _engine(model, decode_chunk=decode_chunk).warmup()
    assert isinstance(eng.cache, StateCache)
    assert eng.executable_count() == eng.expected_executables == 3
    budgets = [6, 5, 7, 4]
    outs = eng.generate_tokens(prompts, budgets)
    for p, out, n in zip(prompts, outs, budgets):
        assert len(out) == n
        want = _reference_logits(reference, cfg, weights, [p],
                                 [np.asarray(out)])[0].argmax(-1)
        assert list(want) == out
    assert (eng.cache.n_live, eng.cache.n_free) == (0, 3)
    assert eng.executable_count() == 3
    eng.cache.check_invariants()


def test_engine_admits_by_free_rows(model, prompts):
    eng = _engine(model).warmup()
    for p in prompts:
        eng.submit(p, 8)
    eng.step()
    assert (eng.sched.n_running, eng.cache.n_live) == (2, 2)
    eng.step()
    assert (eng.sched.n_running, eng.cache.n_live) == (3, 3)
    assert eng.sched.queue_depth == 1       # no row: it waits
    eng.run_to_completion()
    assert eng.cache.n_live == 0


@pytest.mark.parametrize("option,match", [
    (dict(prefix_sharing=True), "prefix_sharing"),
    (dict(speculative_k=2), "speculative_k"),
    (dict(quant="int8"), "quant"),
    ("tp", "tp plan")])
def test_engine_refuses_what_a_state_row_cannot_do(model, option, match):
    if option == "tp":
        from paddle_tpu.distributed import MeshPlan
        option = dict(plan=MeshPlan(tp=2))
    with pytest.raises(ValueError, match=match):
        _engine(model, **option)


@pytest.mark.parametrize("mix,match", [
    (dict(bias=False), "biases come with"),
    (dict(n_kv_heads=2), "biases come with"),
    (dict(mlp="swiglu"), "biases come with"),
    (dict(mixer="retention"), "set n_kv_heads"),
    (dict(qk_norm=True), "set n_kv_heads"),
    (dict(bias=False, n_kv_heads=2, mlp="swiglu", qkv_heads_major=True),
     "tp layout"),
    (dict(norm="batch"), "norm"),
    (dict(mixer="sliding"), "mixer")])
def test_decoder_spec_refuses_a_mix_block_has_no_leaves_for(mix, match):
    with pytest.raises(ValueError, match=match):
        decoder.DecoderSpec(eps=1e-5, n_heads=4, head_dim=16, **mix)


def test_step_span_carries_the_live_rows(model, prompts):
    from paddle_tpu.observability import reqtrace
    eng = _engine(model).warmup()
    reqtrace.enable(True, capacity=4096)
    try:
        eng.generate_tokens(prompts[:2], 3)
        steps = [e for e in reqtrace.get_tracer().events()
                 if e.get("comp") == "step"]
    finally:
        reqtrace.disable()
    assert steps and all("state_rows_live" in e and "executables" in e
                         for e in steps)
    assert max(e["state_rows_live"] for e in steps) == 2


def test_step_span_counts_the_row_writes(model, prompts):
    """A step that decodes writes `state_row_writes` on its span: live
    lanes x layers x writes a dispatch (one: a chunk of 2 is written
    once). A step that does not decode writes none."""
    from paddle_tpu.observability import reqtrace
    eng = _engine(model).warmup()
    reqtrace.enable(True, capacity=4096)
    try:
        eng.generate_tokens(prompts[:2], 5)
        evts = reqtrace.get_tracer().events()
    finally:
        reqtrace.disable()
    steps = [e for e in evts if e.get("comp") == "step"]
    writes = [e["state_row_writes"] for e in steps
              if "state_row_writes" in e]
    # one `decode` span a live lane and dispatch; 2 layers
    lanes = sum(1 for e in evts if e.get("comp") == "decode")
    assert writes and sum(writes) == 2 * lanes
    assert len(writes) < len(steps)     # the last step only retires


@pytest.mark.parametrize("n_steps,writes", [(1, 1), (4, 1), (8, 1),
                                            (10, 2), (17, 3)])
def test_a_retention_decode_program_states_its_writes(model, n_steps,
                                                      writes):
    """A row is written once a chunk, and at least once every
    RETENTION_CHUNK token-steps (the kernel's tile of keys)."""
    run = programs.make_decode_fn(model.decoder_spec(), 8,
                                  (0.0, None, None), n_steps)
    assert run.writes_per_dispatch == writes
