"""Serving raw speed (ISSUE 16): true-int8 decode, speculative
decoding, and copy-on-write prefix page sharing.

Receipts pinned here:
- int8 PTQ: dequant round-trip error bounded by one code step per
  channel, treedef-stable quantization (hot swaps keep working), an
  int8 engine serves end-to-end with executables pinned, and the
  logits-drift receipt bounds int8 drift;
- speculative decoding: accepted tokens BIT-IDENTICAL to
  non-speculative greedy under the f32 parity contract, at
  steady-state executables == expected and zero recompile events;
  draft==target accepts every proposal;
- COW prefix sharing: refcounted shared pages never free while
  referenced, writer-copy preserves reader bytes,
  free+live+scratch==n_blocks with shared pages counted once (all
  under churn), and engine-level sharing keeps bit-exact parity while
  pages_live falls;
- explain_tail grows ``draft``/``prefix_match`` components and shares
  still sum to 1.0 ±0.02.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.models.decoder import DecoderSpec
from paddle_tpu.models.generation import _gpt_params
from paddle_tpu.quant import QuantConfig
from paddle_tpu.quant.int8_serving import (
    QUANT_WEIGHT_KEYS, int8_matmul, logits_drift_receipt,
    quantize_params, quantize_weight)
from paddle_tpu.serving import (PagedKVCache, ServingConfig,
                                ServingEngine, build_serving_snapshot)

V = 97


def _model(seed=3, layers=2, hidden=32, heads=4):
    paddle.seed(seed)
    cfg = GPTConfig(vocab_size=V, hidden_size=hidden, num_layers=layers,
                    num_heads=heads, max_seq_len=64, dropout=0.0,
                    use_flash_attention=False)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture(scope="module")
def model():
    return _model(seed=3)


@pytest.fixture(scope="module")
def draft():
    # a genuinely different (smaller) proposer over the same vocab
    return _model(seed=7, layers=1, hidden=16, heads=2)


def f32_config(**kw):
    base = dict(max_slots=4, max_admit=2, block_size=4, n_blocks=32,
                prefill_buckets=(8, 16), max_total_tokens=32,
                decode_chunk=2, dtype=None)
    base.update(kw)
    return ServingConfig(**base)


def solo_greedy(model, ids, n_new):
    out = model.generate(paddle.to_tensor(ids[None]),
                         max_new_tokens=n_new)
    return np.asarray(out._data)[0, len(ids):]


# -- int8 ---------------------------------------------------------------------

class TestInt8:
    def test_quantize_weight_roundtrip(self):
        rng = np.random.RandomState(0)
        w = rng.randn(24, 12).astype(np.float32) * \
            rng.uniform(0.1, 4.0, (12,)).astype(np.float32)
        leaf = quantize_weight(w)
        assert leaf["q8"].dtype == np.int8
        assert leaf["s"].shape == (12,)
        # dequant error is at most half a code step per channel
        err = np.abs(np.asarray(leaf["q8"], np.float32)
                     * np.asarray(leaf["s"]) - w)
        assert (err <= 0.5 * np.asarray(leaf["s"]) + 1e-7).all()

    def test_quantize_params_treedef_stable(self, model):
        import jax
        p = _gpt_params(model)
        q1 = quantize_params(p)
        q2 = quantize_params(p)
        assert (jax.tree_util.tree_structure(q1)
                == jax.tree_util.tree_structure(q2))
        for k in QUANT_WEIGHT_KEYS:
            assert isinstance(q1["blocks"][0][k], dict)
        # non-matmul leaves ride through untouched
        assert q1["blocks"][0]["qkv_b"] is p["blocks"][0]["qkv_b"]
        assert q1["wte"] is p["wte"]

    def test_int8_matmul_close_to_float(self):
        import jax.numpy as jnp
        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(4, 24).astype(np.float32))
        w = rng.randn(24, 12).astype(np.float32)
        leaf = quantize_weight(w)
        got = np.asarray(int8_matmul(x, leaf["q8"], leaf["s"]))
        ref = np.asarray(x) @ w
        # two abs-max int8 quantizations: relative error ~1e-2
        assert np.abs(got - ref).max() < 0.05 * np.abs(ref).max() + 0.05

    def test_quant_config_threading(self):
        cfg = f32_config(quant=QuantConfig(int8_compute=True))
        assert cfg.quant == "int8"
        assert cfg.quant_config is not None
        with pytest.raises(ValueError, match="int8_compute"):
            f32_config(quant=QuantConfig())
        with pytest.raises(ValueError, match="quant"):
            f32_config(quant="bf16")

    @pytest.mark.slow  # ~7 s: tier-1 rebalance (PR 18); sibling
    # test_logits_drift_receipt_bounds keeps the int8 end-to-end leg
    # and the unit quant tests keep the roundtrip/treedef contracts
    def test_int8_engine_serves_with_pinned_executables(self, model):
        eng = ServingEngine(model, f32_config(quant="int8")).warmup()
        rng = np.random.RandomState(5)
        prompts = [rng.randint(0, V, (L,)).astype(np.int32)
                   for L in (5, 9, 3)]
        outs = eng.generate_tokens(prompts, [6, 5, 4])
        assert [len(o) for o in outs] == [6, 5, 4]
        assert eng.executable_count() == eng.expected_executables
        assert eng.sentinel.fired == 0
        # greedy top-1 agreement vs the f32 parity reference: int8
        # drift flips only near-tie argmaxes on this tiny random model
        ref = ServingEngine(model, f32_config())
        routs = ref.generate_tokens(prompts, [6, 5, 4])
        agree = np.mean([t == r for o, ro in zip(outs, routs)
                         for t, r in zip(o, ro)])
        assert agree >= 0.5, f"top-1 agreement collapsed: {agree}"

    def test_logits_drift_receipt_bounds(self, model):
        import jax.numpy as jnp
        rng = np.random.RandomState(6)
        ids = jnp.asarray(rng.randint(0, V, (4, 8)), jnp.int32)
        rec = logits_drift_receipt(_gpt_params(model),
                                   DecoderSpec.of(model.gpt.config), ids)
        assert np.isfinite(rec["logit_drift_int8"])
        assert rec["logit_drift_int8"] < 1.0   # tiny-model logit scale
        assert 0.0 <= rec["top1_agreement_last"] <= 1.0

    def test_int8_hot_swap_keeps_treedef(self, model):
        eng = ServingEngine(model, f32_config(quant="int8")).warmup()
        # cast=True re-runs the FULL snapshot build (incl. PTQ) so the
        # int8 treedef matches; a shared pre-built pool flips too
        eng.swap_weights(_gpt_params(model), cast=True)
        eng.swap_weights(
            build_serving_snapshot(_gpt_params(model), eng.config),
            cast=False)
        rng = np.random.RandomState(8)
        eng.generate_tokens([rng.randint(0, V, (5,)).astype(np.int32)],
                            [4])
        assert eng.sentinel.fired == 0


# -- speculative decoding -----------------------------------------------------

class TestSpeculative:
    def test_bit_identical_to_greedy(self, model, draft):
        """The acceptance bar: staggered-admission speculative decode
        emits EXACTLY the non-speculative greedy stream, with
        executables == expected and zero recompiles."""
        eng = ServingEngine(model, f32_config(speculative_k=2),
                            draft_model=draft).warmup()
        rng = np.random.RandomState(2)
        specs = [(7, 8), (3, 6), (11, 5), (2, 7)]
        prompts = [rng.randint(0, V, (L,)).astype(np.int32)
                   for L, _ in specs]
        rids = [eng.submit(prompts[0], specs[0][1])]
        eng.step()
        rids.append(eng.submit(prompts[1], specs[1][1]))
        eng.step()
        rids.append(eng.submit(prompts[2], specs[2][1]))
        rids.append(eng.submit(prompts[3], specs[3][1]))
        done = {r.rid: r for r in eng.run_to_completion()}
        for rid, p, (_, n) in zip(rids, prompts, specs):
            np.testing.assert_array_equal(
                np.asarray(done[rid].out), solo_greedy(model, p, n),
                err_msg=f"request {rid}")
        assert eng.executable_count() == eng.expected_executables
        assert eng.sentinel.fired == 0
        eng.cache.check_invariants()
        eng.draft_cache.check_invariants()
        assert eng.draft_cache.n_free == eng.draft_cache.n_blocks - 1

    @pytest.mark.slow  # ~7 s: tier-1 rebalance (PR 18); sibling
    # test_bit_identical_to_greedy keeps the speculative-decode
    # acceptance contract
    def test_draft_equals_target_accepts_everything(self, model):
        from paddle_tpu.observability import metrics
        eng = ServingEngine(model, f32_config(speculative_k=3),
                            draft_model=model).warmup()
        rng = np.random.RandomState(4)
        p = rng.randint(0, V, (6,)).astype(np.int32)
        with metrics.enabled_scope(True):
            metrics.reset(prefix="serving.")
            outs = eng.generate_tokens([p], [9])
            prop = metrics.get("serving.spec_proposed_total")
            acc = metrics.get("serving.spec_accepted_total")
        np.testing.assert_array_equal(np.asarray(outs[0]),
                                      solo_greedy(model, p, 9))
        # an identical proposer is never rejected — every scored
        # proposal lands (acceptance rate exactly 1.0)
        assert prop.value() > 0
        assert acc.value() == prop.value()

    def test_validation(self, model, draft):
        with pytest.raises(ValueError, match="draft_model"):
            ServingEngine(model, f32_config(speculative_k=2))
        with pytest.raises(ValueError, match="greedy"):
            f32_config(speculative_k=2, temperature=0.7)
        wrong_vocab = _model(seed=9)
        wrong_vocab.gpt.config.vocab_size = 11
        with pytest.raises(ValueError, match="vocab"):
            ServingEngine(model, f32_config(speculative_k=2),
                          draft_model=wrong_vocab)


# -- COW prefix sharing -------------------------------------------------------

def make_cache(n_blocks=32, block_size=4, **kw):
    return PagedKVCache(n_layers=2, n_blocks=n_blocks,
                        block_size=block_size, n_heads=2, head_dim=4,
                        dtype="float32", **kw)


class TestCowInvariants:
    def test_shared_pages_counted_once_and_survive_free(self):
        c = make_cache(prefix_sharing=True)
        prefix = list(range(1, 13))            # 3 full pages
        c.alloc_shared("a", 16, prefix + [50])
        c.register_prefix("a", prefix + [50])
        c.check_invariants()
        blocks_a = c.table("a")
        _, shared = c.alloc_shared("b", 16, prefix + [60])
        assert shared == 12                    # 3 pages matched
        assert c.table("b")[:3] == blocks_a[:3]
        c.check_invariants()
        # shared pages counted ONCE: conservation holds
        assert 1 + c.n_free + c.n_live == c.n_blocks
        assert c.n_shared >= 3
        # creator dies; the shared pages stay live (b + index hold)
        c.free("a")
        c.check_invariants()
        for p in blocks_a[:3]:
            assert p in c._ref and p not in c._free
        # last holder dies; index still holds them (reclaimable)
        c.free("b")
        c.check_invariants()
        for p in blocks_a[:3]:
            assert p in c._ref
        assert c.available_pages == c.n_blocks - 1

    def test_match_capped_one_token_short(self):
        c = make_cache(prefix_sharing=True)
        prompt = list(range(1, 9))             # exactly 2 full pages
        c.alloc_shared("a", 12, prompt)
        c.register_prefix("a", prompt)
        # identical prompt: match caps at (8-1)//4 = 1 page, so the
        # suffix prefill always keeps >= 1 real token
        _, shared = c.alloc_shared("b", 12, prompt)
        assert shared == 4
        c.check_invariants()

    def test_churn_conservation(self):
        rng = np.random.RandomState(0)
        c = make_cache(n_blocks=24, prefix_sharing=True)
        prefixes = [list(range(10 * k + 1, 10 * k + 9))
                    for k in range(3)]          # 2 full pages each
        live = []
        for step in range(120):
            if live and (len(live) > 2 or rng.rand() < 0.4):
                c.free(live.pop(rng.randint(len(live))))
            else:
                rid = f"r{step}"
                prompt = (prefixes[rng.randint(3)]
                          + list(rng.randint(100, 120, (rng.randint(1, 6),))))
                need = c.blocks_for(len(prompt) + 4)
                if need > c.available_pages:
                    continue
                _, _ = c.alloc_shared(rid, len(prompt) + 4, prompt)
                c.register_prefix(rid, prompt)
                live.append(rid)
            c.check_invariants()
            assert 1 + c.n_free + c.n_live == c.n_blocks
        for rid in live:
            c.free(rid)
        c.check_invariants()

    def test_writer_copy_preserves_reader_bytes(self):
        import jax.numpy as jnp
        c = make_cache(prefix_sharing=True)
        prefix = list(range(1, 5))             # 1 full page
        c.alloc_shared("a", 8, prefix + [9])
        c.register_prefix("a", prefix + [9])
        _, shared = c.alloc_shared("b", 8, prefix + [7])
        assert shared == 4
        page = c.table("a")[0]
        assert c.table("b")[0] == page
        # stamp recognizable bytes into the shared page
        k0, v0 = c.pools[0]
        c.pools = ((k0.at[page].set(3.5), v0.at[page].set(-2.25)),) \
            + c.pools[1:]
        before = np.asarray(c.pools[0][0][page]).copy()
        copies = c.ensure_writable("b", 0, 4)
        assert copies == 1
        new_page = c.table("b")[0]
        assert new_page != page
        assert c.table("a")[0] == page         # reader untouched
        np.testing.assert_array_equal(
            np.asarray(c.pools[0][0][page]), before)
        np.testing.assert_array_equal(
            np.asarray(c.pools[0][0][new_page]), before)
        c.check_invariants()
        assert c.cow_copies == 1
        # unshared pages need no copy
        assert c.ensure_writable("b", 4, 2) == 0

    def test_index_reclaim_under_pressure(self):
        c = make_cache(n_blocks=8, prefix_sharing=True)  # 7 usable
        c.alloc_shared("a", 12, list(range(1, 13)))      # 3 pages
        c.register_prefix("a", list(range(1, 13)))
        c.free("a")
        assert c.n_free == 4 and c.available_pages == 7
        # a full-pool request forces LRU reclaim of the index pages
        c.alloc_shared("b", 28, list(range(50, 57)))     # 7 pages
        c.check_invariants()
        assert c.reclaimed_pages == 3
        with pytest.raises(MemoryError, match="exhausted"):
            c.alloc("z", 4)

    def test_sharing_disabled_contract_unchanged(self):
        c = make_cache()
        with pytest.raises(RuntimeError, match="prefix_sharing"):
            c.alloc_shared("a", 8, [1, 2, 3, 4, 5])
        assert c.register_prefix("a", [1, 2]) == 0
        assert c.available_pages == c.n_free


class TestEngineSharing:
    def test_shared_prefix_parity_and_pages_fall(self, model):
        """The 90%-shared acceptance receipt at test scale: the second
        request with a cached prefix prefills only its suffix, holds
        fewer fresh pages, and still emits the bit-exact greedy
        stream."""
        rng = np.random.RandomState(3)
        prefix = rng.randint(0, V, (8,)).astype(np.int32)  # 2 pages
        tails = [rng.randint(0, V, (3,)).astype(np.int32)
                 for _ in range(3)]
        prompts = [np.concatenate([prefix, t]) for t in tails]

        eng = ServingEngine(model,
                            f32_config(prefix_sharing=True)).warmup()
        r0 = eng.submit(prompts[0], 5)
        done = {r.rid: r for r in eng.run_to_completion()}
        live_after_first = eng.cache.stats()["pages_live"]
        # r0's full-prompt pages stay indexed after retirement
        assert live_after_first > 0
        r1 = eng.submit(prompts[1], 5)
        eng.step()
        req1 = eng.sched.running[r1]
        assert req1.shared_tokens == 8          # both prefix pages hit
        done.update({r.rid: r for r in eng.run_to_completion()})
        r2 = eng.submit(prompts[2], 5)
        done.update({r.rid: r for r in eng.run_to_completion()})
        for rid, p in zip((r0, r1, r2), prompts):
            np.testing.assert_array_equal(
                np.asarray(done[rid].out), solo_greedy(model, p, 5),
                err_msg=f"request {rid}")
        st = eng.cache.stats()
        assert st["prefix_hits"] == 2
        assert st["shared_pages_matched"] == 4
        assert eng.executable_count() == eng.expected_executables
        assert eng.sentinel.fired == 0
        eng.cache.check_invariants()

    def test_sharing_holds_fewer_fresh_pages(self, model):
        """Two same-prefix requests live at once: shared pages counted
        once means the engine holds strictly fewer distinct pages than
        the unshared engine for the same load — freed headroom IS the
        capacity gain."""
        rng = np.random.RandomState(13)
        prefix = rng.randint(0, V, (12,)).astype(np.int32)
        p1 = np.concatenate([prefix, rng.randint(0, V, (2,))
                             .astype(np.int32)])
        p2 = np.concatenate([prefix, rng.randint(0, V, (2,))
                             .astype(np.int32)])
        peak = {}
        for name, eng in (
                ("shared", ServingEngine(
                    model, f32_config(prefix_sharing=True)).warmup()),
                ("plain", ServingEngine(model, f32_config()).warmup())):
            # seed the radix index, then hold both live together
            eng.submit(p1, 4)
            eng.run_to_completion()
            eng.submit(p1, 4)
            eng.submit(p2, 4)
            eng.step()                      # both admitted (max_admit=2)
            peak[name] = eng.cache.stats()["pages_live"]
            eng.run_to_completion()
        # shared: 3 prefix pages once + 2 suffix/reserve pages each;
        # plain: two full 5-page allocations
        assert peak["shared"] < peak["plain"]

    @pytest.mark.slow  # ~6 s: tier-1 rebalance (PR 18); siblings
    # test_shared_prefix_parity_and_pages_fall +
    # test_sharing_holds_fewer_fresh_pages keep the sharing contract
    def test_speculative_plus_sharing_compose(self, model, draft):
        eng = ServingEngine(
            model, f32_config(speculative_k=2, prefix_sharing=True),
            draft_model=draft).warmup()
        rng = np.random.RandomState(17)
        prefix = rng.randint(0, V, (8,)).astype(np.int32)
        prompts = [np.concatenate([prefix,
                                   rng.randint(0, V, (3,))
                                   .astype(np.int32)])
                   for _ in range(2)]
        outs = eng.generate_tokens(list(prompts), [5, 6])
        for o, p, n in zip(outs, prompts, (5, 6)):
            np.testing.assert_array_equal(np.asarray(o),
                                          solo_greedy(model, p, n))
        assert eng.executable_count() == eng.expected_executables
        assert eng.sentinel.fired == 0


# -- loadgen shared-prefix trace mode -----------------------------------------

class TestSharedPrefixTrace:
    def test_shared_prefix_mode_deterministic(self):
        from paddle_tpu.serving.loadgen import synthetic_trace
        t1 = synthetic_trace(30, vocab_size=V, seed=5,
                             shared_prefix_len=8, shared_frac=0.7)
        t2 = synthetic_trace(30, vocab_size=V, seed=5,
                             shared_prefix_len=8, shared_frac=0.7)
        for a, b in zip(t1, t2):
            np.testing.assert_array_equal(a.ids, b.ids)
        # the shared requests carry ONE trace-wide common prefix
        shared = [it for it in t1 if it.ids.size > 8
                  and any(np.array_equal(it.ids[:8], o.ids[:8])
                          for o in t1 if o is not it)]
        assert shared, "no shared-prefix requests at frac=0.7"
        head = shared[0].ids[:8]
        n_shared = sum(np.array_equal(it.ids[:8], head) for it in t1)
        assert 10 <= n_shared <= 30
        # frac=0 keeps the legacy trace bit-identical
        legacy = synthetic_trace(10, vocab_size=V, seed=5)
        off = synthetic_trace(10, vocab_size=V, seed=5,
                              shared_prefix_len=0, shared_frac=0.9)
        for a, b in zip(legacy, off):
            np.testing.assert_array_equal(a.ids, b.ids)


# -- explain_tail taxonomy ----------------------------------------------------

class TestTailTaxonomy:
    def test_components_include_draft_and_prefix_match(self):
        from paddle_tpu.observability import reqtrace as rt
        assert "draft" in rt.COMPONENTS
        assert "prefix_match" in rt.COMPONENTS

    def test_shares_sum_to_one_with_new_components(self, model, draft):
        from paddle_tpu.observability import reqtrace as rt
        eng = ServingEngine(
            model, f32_config(speculative_k=2, prefix_sharing=True),
            draft_model=draft).warmup()
        rng = np.random.RandomState(19)
        prefix = rng.randint(0, V, (8,)).astype(np.int32)
        rt.enable()
        try:
            eng.submit(np.concatenate(
                [prefix, rng.randint(0, V, (2,)).astype(np.int32)]), 4)
            eng.run_to_completion()
            eng.submit(np.concatenate(
                [prefix, rng.randint(0, V, (3,)).astype(np.int32)]), 5)
            eng.run_to_completion()
            tail = rt.explain_tail(p=0.0)
        finally:
            rt.disable()
        assert tail["requests"] == 2
        comps = set()
        for row in tail["cohort"]:
            total = sum(row["components"].values())
            assert total == pytest.approx(1.0, abs=0.02)
            comps |= set(row["components"])
        assert "draft" in comps
        # the second request admitted with a prefix hit
        assert "prefix_match" in comps
