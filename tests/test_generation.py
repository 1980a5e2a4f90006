"""KV-cache generation (models/generation.py): the compiled decode loop
must agree with naive full re-forward decoding, step for step.

Reference decoding capability: beam_search ops + dynamic_decode
(/root/reference/paddle/fluid/operators/beam_search_op.cc,
python/paddle/fluid/layers/rnn.py) — driven per-step from Python there,
one jitted lax.scan here."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.models.decoder import DecoderSpec


@pytest.fixture(scope="module")
def model():
    paddle.seed(3)
    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                    num_heads=4, max_seq_len=64, dropout=0.0,
                    use_flash_attention=False)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _naive_greedy(model, ids, n_new):
    """Reference decode: full re-forward each step, argmax."""
    cur = np.asarray(ids)
    for _ in range(n_new):
        logits = model(paddle.to_tensor(cur.astype(np.int32)))
        nxt = np.asarray(logits._data)[:, -1].argmax(-1)
        cur = np.concatenate([cur, nxt[:, None]], axis=1)
    return cur


class TestKVCacheDecode:
    @pytest.mark.slow  # 13.6 s; beam1_equals_greedy + ragged
    #   rows_match_unbatched keep decode-parity in tier-1
    def test_greedy_matches_full_reforward(self, model):
        rng = np.random.RandomState(0)
        ids = rng.randint(0, 97, (2, 7)).astype(np.int32)
        out = model.generate(paddle.to_tensor(ids), max_new_tokens=9,
                             temperature=0.0)
        want = _naive_greedy(model, ids, 9)
        np.testing.assert_array_equal(np.asarray(out._data), want)

    def test_eos_rows_emit_pad(self, model):
        rng = np.random.RandomState(1)
        ids = rng.randint(0, 97, (2, 5)).astype(np.int32)
        # find the token greedy decode emits first for row 0, use it as eos
        first = _naive_greedy(model, ids, 1)[0, -1]
        out = np.asarray(model.generate(
            paddle.to_tensor(ids), max_new_tokens=6,
            temperature=0.0, eos_token_id=int(first),
            pad_token_id=96)._data)
        row = out[0, 5:]
        assert row[0] == first
        assert (row[1:] == 96).all()

    def test_sampling_shapes_and_range(self, model):
        rng = np.random.RandomState(2)
        ids = rng.randint(0, 97, (3, 4)).astype(np.int32)
        out = np.asarray(model.generate(
            paddle.to_tensor(ids), max_new_tokens=5, temperature=0.8,
            top_k=10, seed=7)._data)
        assert out.shape == (3, 9)
        assert (out >= 0).all() and (out < 97).all()
        # deterministic under the same seed
        out2 = np.asarray(model.generate(
            paddle.to_tensor(ids), max_new_tokens=5, temperature=0.8,
            top_k=10, seed=7)._data)
        np.testing.assert_array_equal(out, out2)

    def test_length_guard(self, model):
        ids = np.zeros((1, 60), np.int32)
        with pytest.raises(ValueError, match="max_seq_len"):
            model.generate(paddle.to_tensor(ids), max_new_tokens=10)

    def test_repeated_generate_reuses_compile(self, model):
        from paddle_tpu.models.generation import _build_run
        rng = np.random.RandomState(5)
        ids = rng.randint(0, 97, (2, 6)).astype(np.int32)
        model.generate(paddle.to_tensor(ids), max_new_tokens=4)
        run = _build_run(DecoderSpec.of(model.gpt.config), 0.0, None,
                         None, 0, 4, 6, 10, None)
        before = run._cache_size()
        model.generate(paddle.to_tensor(ids), max_new_tokens=4)
        model.generate(paddle.to_tensor(ids + 1), max_new_tokens=4)
        assert run._cache_size() == before  # no retrace, no recompile


class TestBeamSearch:
    def _logprob_of(self, model, seq, prompt_len):
        """Total log-prob of seq's generated suffix under the model."""
        lg = model(paddle.to_tensor(seq[None].astype(np.int32)))
        lp = np.asarray(lg._data, np.float64)[0]
        lp = lp - np.log(np.exp(lp - lp.max(-1, keepdims=True)).sum(
            -1, keepdims=True)) - lp.max(-1, keepdims=True)
        total = 0.0
        for t in range(prompt_len, len(seq)):
            total += lp[t - 1, seq[t]]
        return total

    def test_beam1_equals_greedy(self, model):
        # exercise the BEAM builder itself at W=1 (generate() dispatches
        # num_beams=1 to the greedy builder, which would be vacuous)
        from paddle_tpu.models.generation import (_build_beam_run,
                                                  _gpt_params)
        import jax
        rng = np.random.RandomState(6)
        ids = rng.randint(0, 97, (2, 5)).astype(np.int32)
        g = np.asarray(model.generate(paddle.to_tensor(ids),
                                      max_new_tokens=6)._data)
        run = _build_beam_run(DecoderSpec.of(model.gpt.config), 1, None,
                              0, 6, 5, 11, None)
        b, _ = run(_gpt_params(model), ids, jax.random.key(0))
        np.testing.assert_array_equal(g, np.asarray(b))

    def test_beam_not_worse_than_greedy(self, model):
        rng = np.random.RandomState(7)
        ids = rng.randint(0, 97, (1, 5)).astype(np.int32)
        g = np.asarray(model.generate(paddle.to_tensor(ids),
                                      max_new_tokens=7)._data)[0]
        b = np.asarray(model.generate(paddle.to_tensor(ids),
                                      max_new_tokens=7,
                                      num_beams=4)._data)[0]
        lp_g = self._logprob_of(model, g, 5)
        lp_b = self._logprob_of(model, b, 5)
        assert lp_b >= lp_g - 1e-4, (lp_b, lp_g)

    def test_beam_eos_freezes(self, model):
        rng = np.random.RandomState(8)
        ids = rng.randint(0, 97, (1, 4)).astype(np.int32)
        # eos := the step-1 top-1 token. The beam that emits it freezes
        # at that (maximal) step-1 score while every other beam only
        # accumulates negative log-probs, so the frozen beam is
        # GUARANTEED to win: the best sequence must be [eos, pad...].
        first = np.asarray(model.generate(
            paddle.to_tensor(ids), max_new_tokens=1,
            num_beams=4)._data)[0, -1]
        out = np.asarray(model.generate(
            paddle.to_tensor(ids), max_new_tokens=5, num_beams=4,
            eos_token_id=int(first), pad_token_id=96)._data)[0]
        gen = out[4:]
        assert gen[0] == first
        assert (gen[1:] == 96).all()


class TestServingDtype:
    """dtype="bfloat16" serving decode (generation.py generate_gpt):
    bf16 weights + KV cache, f32 layernorm moments and sampling."""

    def test_bf16_deterministic_and_sane(self, model):
        rng = np.random.RandomState(1)
        ids = rng.randint(0, 97, (2, 7)).astype(np.int32)
        a = model.generate(paddle.to_tensor(ids), max_new_tokens=9,
                           temperature=0.0, dtype="bfloat16")
        b = model.generate(paddle.to_tensor(ids), max_new_tokens=9,
                           temperature=0.0, dtype="bfloat16")
        a, b = np.asarray(a._data), np.asarray(b._data)
        np.testing.assert_array_equal(a, b)  # deterministic
        assert a.shape == (2, 16) and a.dtype == np.int32
        np.testing.assert_array_equal(a[:, :7], ids)  # prompt kept
        assert ((a >= 0) & (a < 97)).all()

    def test_bf16_mostly_agrees_with_f32_greedy(self, model):
        # bf16 rounding may flip near-tie argmaxes; demand strong but
        # not exact agreement so the test is hardware-independent
        rng = np.random.RandomState(2)
        ids = rng.randint(0, 97, (4, 7)).astype(np.int32)
        f32 = model.generate(paddle.to_tensor(ids), max_new_tokens=8,
                             temperature=0.0)
        b16 = model.generate(paddle.to_tensor(ids), max_new_tokens=8,
                             temperature=0.0, dtype="bfloat16")
        f32 = np.asarray(f32._data)[:, 7:]
        b16 = np.asarray(b16._data)[:, 7:]
        agree = (f32 == b16).mean()
        assert agree >= 0.75, f"bf16 decode agreement {agree}"

    def test_bf16_beam_runs(self, model):
        rng = np.random.RandomState(3)
        ids = rng.randint(0, 97, (2, 5)).astype(np.int32)
        out = model.generate(paddle.to_tensor(ids), max_new_tokens=6,
                             num_beams=3, dtype="bfloat16")
        out = np.asarray(out._data)
        assert out.shape == (2, 11)
        np.testing.assert_array_equal(out[:, :5], ids)

    def test_bf16_decode_hlo_receipt(self, model):
        # the serving-dtype claim is "weight reads are bf16": lower the
        # decode program at dtype=bfloat16 and assert no f32-operand
        # dot_general remains (mirrors tests/test_amp_dot_receipt.py)
        import re
        import jax
        from paddle_tpu.models.generation import (_build_run,
                                                  _gpt_params)
        run = _build_run(DecoderSpec.of(model.gpt.config), 0.0, None,
                         None, 0, 4, 6, 10, "bfloat16")
        params = _gpt_params(model)
        ids = np.zeros((2, 6), np.int32)
        text = run.lower(params, ids, jax.random.key(0)).as_text()
        lines = [ln for ln in text.splitlines() if "dot_general" in ln]
        assert len(lines) >= 4, "expected prefill+decode dots"
        bad = [ln.strip()[:120] for ln in lines
               if re.search(r"tensor<[0-9x]*f32>", ln.split("->")[0])]
        assert not bad, "f32-operand dot in bf16 decode:\n" + \
            "\n".join(bad[:4])


class TestGPTFlashWiring:
    """GPTBlock's use_flash_attention flag routes causal attention
    through the blockwise flash path; logits must match the SDPA form
    (dropout=0 in eval, so both paths are deterministic)."""

    def test_flash_matches_sdpa_logits(self):
        paddle.seed(4)
        cfg_kw = dict(vocab_size=97, hidden_size=32, num_layers=2,
                      num_heads=4, max_seq_len=64, dropout=0.0)
        m_sdpa = GPTForCausalLM(GPTConfig(use_flash_attention=False,
                                          **cfg_kw))
        paddle.seed(4)
        m_flash = GPTForCausalLM(GPTConfig(use_flash_attention=True,
                                           **cfg_kw))
        m_sdpa.eval(), m_flash.eval()
        ids = paddle.to_tensor(np.random.RandomState(0).randint(
            0, 97, (2, 12)).astype(np.int32))
        a = np.asarray(m_sdpa(ids)._data)
        b = np.asarray(m_flash(ids)._data)
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)
        # decode receipt that exercises flash: naive re-forward greedy
        # THROUGH the flash forward path must equal the KV-cache decode
        # (generate's own attention is cache-specialized, not flash —
        # this pins the two against each other)
        g_cache = np.asarray(m_flash.generate(ids,
                                              max_new_tokens=6)._data)
        g_naive = _naive_greedy(m_flash, np.asarray(ids._data), 6)
        np.testing.assert_array_equal(g_cache, g_naive)


class TestRaggedPrompts:
    """prompt_lens: ragged (right-padded) prompt batching in ONE
    compiled decode — per-row cache positions. The receipt: each row
    of the ragged batch decodes exactly as that row's true prompt
    decoded alone."""

    def test_rows_match_unbatched(self, model):
        rng = np.random.RandomState(10)
        lens = [7, 4, 2]
        P = max(lens)
        ids = np.zeros((3, P), np.int32)
        rows = []
        for i, L in enumerate(lens):
            row = rng.randint(0, 97, (L,)).astype(np.int32)
            ids[i, :L] = row
            rows.append(row)
        out = np.asarray(model.generate(
            paddle.to_tensor(ids), max_new_tokens=6,
            prompt_lens=paddle.to_tensor(
                np.asarray(lens, np.int32)))._data)
        assert out.shape == (3, P + 6)
        for i, row in enumerate(rows):
            solo = np.asarray(model.generate(
                paddle.to_tensor(row[None]), max_new_tokens=6)._data)
            np.testing.assert_array_equal(out[i, P:], solo[0, len(row):],
                                          err_msg=f"row {i}")

    def test_uniform_lens_equal_plain_path(self, model):
        rng = np.random.RandomState(11)
        ids = rng.randint(0, 97, (2, 6)).astype(np.int32)
        plain = np.asarray(model.generate(
            paddle.to_tensor(ids), max_new_tokens=5)._data)
        ragged = np.asarray(model.generate(
            paddle.to_tensor(ids), max_new_tokens=5,
            prompt_lens=paddle.to_tensor(
                np.asarray([6, 6], np.int32)))._data)
        np.testing.assert_array_equal(plain, ragged)

    def test_eos_ragged(self, model):
        # per-row done/pad logic must compose with per-row positions:
        # use row 1's first greedy token as eos; it must freeze to pad
        rng = np.random.RandomState(13)
        ids = np.zeros((2, 6), np.int32)
        ids[0] = rng.randint(0, 97, 6)
        short = rng.randint(0, 97, 3)
        ids[1, :3] = short
        lens = paddle.to_tensor(np.asarray([6, 3], np.int32))
        first = np.asarray(model.generate(
            paddle.to_tensor(short[None]), max_new_tokens=1)._data)[0, -1]
        out = np.asarray(model.generate(
            paddle.to_tensor(ids), max_new_tokens=5,
            prompt_lens=lens, eos_token_id=int(first),
            pad_token_id=96)._data)
        gen = out[1, 6:]
        assert gen[0] == first
        assert (gen[1:] == 96).all()

    def test_bad_lens_raise(self, model):
        ids = np.zeros((2, 4), np.int32)
        for bad in ([9, 4], [0, 4], [4]):
            with pytest.raises(ValueError,
                               match="prompt_lens"):
                model.generate(paddle.to_tensor(ids), max_new_tokens=2,
                               prompt_lens=paddle.to_tensor(
                                   np.asarray(bad, np.int32)))

    def test_sampling_ragged_deterministic(self, model):
        rng = np.random.RandomState(12)
        ids = np.zeros((2, 5), np.int32)
        ids[0] = rng.randint(0, 97, 5)
        ids[1, :2] = rng.randint(0, 97, 2)
        lens = paddle.to_tensor(np.asarray([5, 2], np.int32))
        out = np.asarray(model.generate(
            paddle.to_tensor(ids), max_new_tokens=6, temperature=0.7,
            top_k=12, seed=3, prompt_lens=lens)._data)
        out2 = np.asarray(model.generate(
            paddle.to_tensor(ids), max_new_tokens=6, temperature=0.7,
            top_k=12, seed=3, prompt_lens=lens)._data)
        np.testing.assert_array_equal(out, out2)
        assert ((out >= 0) & (out < 97)).all()

    def test_beam_rejects_ragged(self, model):
        ids = np.zeros((2, 4), np.int32)
        with pytest.raises(ValueError, match="prompt_lens"):
            model.generate(paddle.to_tensor(ids), max_new_tokens=3,
                           num_beams=2,
                           prompt_lens=paddle.to_tensor(
                               np.asarray([4, 2], np.int32)))


class TestTopP:
    """Nucleus (top_p) sampling: smallest descending-probability prefix
    whose mass reaches top_p stays; everything else is cut. Capability
    beyond the reference's greedy/beam decode surface."""

    def test_pick_semantics(self):
        from paddle_tpu.models.generation import _pick
        import jax
        import jax.numpy as jnp
        # probs ~ [0.6, 0.3, 0.08, 0.02]: top_p=0.7 keeps {0, 1}
        logits = jnp.log(jnp.asarray([[0.6, 0.3, 0.08, 0.02]],
                                     jnp.float32))
        toks = [int(_pick(logits, jax.random.key(s), 1.0, None, 0.7)[0])
                for s in range(200)]
        assert set(toks) <= {0, 1}
        assert len(set(toks)) == 2     # both survivors actually drawn
        # top_p=0.55: only token 0's mass is needed -> deterministic
        toks = [int(_pick(logits, jax.random.key(s), 1.0, None, 0.55)[0])
                for s in range(50)]
        assert set(toks) == {0}
        # top_p=1.0 is a no-op vs plain sampling
        a = int(_pick(logits, jax.random.key(7), 1.0, None, 1.0)[0])
        b = int(_pick(logits, jax.random.key(7), 1.0, None, None)[0])
        assert a == b

    @pytest.mark.slow  # 7.9 s; pick_semantics + validation/topk
    #   siblings keep top-p in tier-1
    def test_generate_top_p_deterministic_and_in_range(self):
        import paddle_tpu as paddle
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
        paddle.seed(0)
        model = GPTForCausalLM(GPTConfig(vocab_size=97, hidden_size=32,
                                         num_layers=2, num_heads=4,
                                         max_seq_len=32, dropout=0.0))
        model.eval()
        rng = np.random.RandomState(0)
        ids = paddle.to_tensor(rng.randint(0, 97, (2, 5)).astype(np.int32))
        out = np.asarray(model.generate(ids, max_new_tokens=6,
                                        temperature=0.8, top_p=0.9,
                                        seed=5)._data)
        out2 = np.asarray(model.generate(ids, max_new_tokens=6,
                                         temperature=0.8, top_p=0.9,
                                         seed=5)._data)
        np.testing.assert_array_equal(out, out2)
        assert ((out >= 0) & (out < 97)).all()
        # combines with top_k
        out3 = np.asarray(model.generate(ids, max_new_tokens=4,
                                         temperature=0.8, top_k=10,
                                         top_p=0.9, seed=5)._data)
        assert out3.shape == (2, 9)

    def test_top_p_validation_and_topk_combination(self):
        from paddle_tpu.models.generation import _pick
        import jax
        import jax.numpy as jnp
        import paddle_tpu as paddle
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
        paddle.seed(0)
        model = GPTForCausalLM(GPTConfig.tiny(dropout=0.0))
        model.eval()
        ids = paddle.to_tensor(np.zeros((1, 4), np.int32))
        with pytest.raises(ValueError, match="top_p"):
            model.generate(ids, max_new_tokens=2, temperature=0.8,
                           top_p=0.0)
        # sequential semantics: top_k=2 first, then nucleus over the
        # RENORMALIZED top-2 mass — top_p=0.7 keeps only token 0
        # (0.6/0.9 = 0.667 >= ... first token exclusive mass 0, second
        # token exclusive mass 0.667 < 0.7 -> both kept); top_p=0.6
        # keeps only token 0
        logits = jnp.log(jnp.asarray([[0.6, 0.3, 0.08, 0.02]],
                                     jnp.float32))
        toks = [int(_pick(logits, jax.random.key(s), 1.0, 2, 0.6)[0])
                for s in range(60)]
        assert set(toks) == {0}
        toks = [int(_pick(logits, jax.random.key(s), 1.0, 2, 0.7)[0])
                for s in range(200)]
        assert set(toks) == {0, 1}
