"""Continuous-batching serving engine (paddle_tpu.serving): the
decode-parity and executable-count contracts.

Receipts pinned here:
- paged greedy decode == models/generation.py dense-cache greedy,
  token-for-token, for every request in a STAGGERED-admission batch
  (f32 parity mode) — the acceptance parity bar;
- a 5-length prompt mix admits through the bucket ladder with
  executable count == bucket count (NOT per unique length) and zero
  RecompileSentinel events — the ragged-prompt batching fix;
- pages free on retirement, invariants hold under admission
  backpressure, bf16 default mode is deterministic;
- graph_lint's donation rule proves the donated cache pages alias in
  the compiled decode/prefill programs.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import (BucketLadder, FifoScheduler, Request,
                                ServingConfig, ServingEngine)


@pytest.fixture(scope="module")
def model():
    paddle.seed(3)
    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                    num_heads=4, max_seq_len=64, dropout=0.0,
                    use_flash_attention=False)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def f32_config(**kw):
    base = dict(max_slots=4, max_admit=2, block_size=4, n_blocks=32,
                prefill_buckets=(8, 16), max_total_tokens=32,
                decode_chunk=2, dtype=None)
    base.update(kw)
    return ServingConfig(**base)


@pytest.fixture(scope="module")
def engine(model):
    return ServingEngine(model, f32_config()).warmup()


def solo_greedy(model, ids, n_new):
    """The dense-cache reference: generation.py greedy, one request."""
    out = model.generate(paddle.to_tensor(ids[None]),
                         max_new_tokens=n_new)
    return np.asarray(out._data)[0, len(ids):]


class TestDecodeParity:
    def test_staggered_admission_bit_exact(self, model, engine):
        """Requests admitted at DIFFERENT token boundaries (r2 joins
        while r1 is mid-decode, r3/r4 while pages churn) each decode
        exactly as they would alone through generation.py."""
        rng = np.random.RandomState(1)
        specs = [(7, 8), (3, 6), (11, 5), (2, 7)]
        prompts = [rng.randint(0, 97, (L,)).astype(np.int32)
                   for L, _ in specs]
        rids = []
        rids.append(engine.submit(prompts[0], specs[0][1]))
        engine.step()
        engine.step()
        rids.append(engine.submit(prompts[1], specs[1][1]))
        engine.step()
        rids.append(engine.submit(prompts[2], specs[2][1]))
        rids.append(engine.submit(prompts[3], specs[3][1]))
        done = {r.rid: r for r in engine.run_to_completion()}
        for rid, p, (_, n) in zip(rids, prompts, specs):
            np.testing.assert_array_equal(
                np.asarray(done[rid].out), solo_greedy(model, p, n),
                err_msg=f"request {rid}")
        engine.cache.check_invariants()
        assert engine.cache.n_free == engine.cache.n_blocks - 1

    def test_batch_convenience_matches_solo(self, model, engine):
        rng = np.random.RandomState(2)
        prompts = [rng.randint(0, 97, (L,)).astype(np.int32)
                   for L in (5, 9, 4)]
        outs = engine.generate_tokens(prompts, [6, 4, 8])
        for p, o, n in zip(prompts, outs, [6, 4, 8]):
            np.testing.assert_array_equal(
                np.asarray(o), solo_greedy(model, p, n))

    def test_zero_steady_state_recompiles(self, engine):
        """After the module's traffic: executable count == ladder
        size, sentinel never fired (the serving compile contract)."""
        assert engine.executable_count() == engine.expected_executables
        assert engine.sentinel.fired == 0
        assert engine.sentinel.counter.value() == 0


class TestBucketedPrefill:
    @pytest.mark.slow  # ~8 s: tier-1 rebalance (PR 17); sibling
    # test_mixed_lengths_share_one_admit_prefill keeps the bucketed
    # ragged-admit contract in tier-1
    def test_five_length_mix_pins_executable_count(self, model):
        """The ragged-prompt batching fix: 5 DISTINCT prompt lengths
        admit through shared bucketed prefill programs — executable
        count is the bucket count (2 here), not one per length."""
        eng = ServingEngine(model, f32_config())
        lens = [3, 5, 6, 9, 12]          # -> buckets {8, 16} only
        rng = np.random.RandomState(4)
        prompts = [rng.randint(0, 97, (L,)).astype(np.int32)
                   for L in lens]
        outs = eng.generate_tokens(prompts, [4] * 5)
        assert eng._prefill._cache_size() == 2      # == buckets hit
        assert eng._decode._cache_size() == 1
        assert eng.sentinel.fired == 0
        for p, o in zip(prompts, outs):             # and still exact
            np.testing.assert_array_equal(
                np.asarray(o), solo_greedy(model, p, 4))

    def test_mixed_lengths_share_one_admit_prefill(self, model,
                                                   engine):
        """Two different-length prompts submitted together go through
        ONE prefill dispatch (admit batch), not one each."""
        rng = np.random.RandomState(5)
        a = rng.randint(0, 97, (3,)).astype(np.int32)
        b = rng.randint(0, 97, (7,)).astype(np.int32)
        engine.submit(a, 3)
        engine.submit(b, 3)
        before = engine.sentinel._steps
        engine.step()       # both admit at this one boundary
        assert engine.sched.n_running == 2
        engine.run_to_completion()
        assert engine.sentinel._steps > before


class TestLifecycle:
    def test_eos_finishes_early_and_frees_pages(self, model, engine):
        rng = np.random.RandomState(6)
        p = rng.randint(0, 97, (5,)).astype(np.int32)
        first = int(solo_greedy(model, p, 1)[0])
        rid = engine.submit(p, 8, eos_token_id=first)
        done = {r.rid: r for r in engine.run_to_completion()}
        r = done[rid]
        assert r.finish_reason == "eos"
        assert r.out[-1] == first and len(r.out) <= 8
        engine.cache.check_invariants()
        assert engine.cache.n_free == engine.cache.n_blocks - 1

    def test_admission_backpressure_fifo(self, model):
        """A pool too small for two requests queues the second until
        the first retires — FIFO, no starvation, invariants at every
        boundary."""
        eng = ServingEngine(model, f32_config(
            n_blocks=9, prefill_buckets=(8,), max_total_tokens=16))
        rng = np.random.RandomState(7)
        p = rng.randint(0, 97, (8,)).astype(np.int32)
        # each request: ceil((8+8)/4) = 4 pages; pool holds 8 -> 2 max
        r1 = eng.submit(p, 8)
        r2 = eng.submit(p, 8)
        r3 = eng.submit(p, 8)
        eng.step()
        assert eng.sched.n_running == 2      # r3 waits on pages
        assert eng.sched.queue_depth == 1
        order = []
        for _ in range(200):
            if not eng.has_work():
                break
            for r in eng.step():
                order.append(r.rid)
            eng.cache.check_invariants()
        assert sorted(order[:2]) == sorted([r1, r2])
        assert order[2] == r3                # admitted after a retire
        assert eng.cache.n_free == 8

    def test_submit_validation(self, model, engine):
        too_long = np.zeros((17,), np.int32)   # > largest bucket 16
        with pytest.raises(ValueError, match="bucket"):
            engine.submit(too_long, 2)
        with pytest.raises(ValueError, match="max_total_tokens"):
            engine.submit(np.zeros((16,), np.int32), 32)
        with pytest.raises(ValueError, match="max_new_tokens"):
            engine.submit(np.zeros((4,), np.int32), 0)


class TestBf16Default:
    @pytest.mark.slow  # ~8 s: tier-1 rebalance (PR 18); sibling
    # test_bf16_pools_and_params keeps the bf16-default contract and
    # TestDecodeParity keeps the determinism pin
    def test_default_dtype_is_bf16_and_deterministic(self, model):
        cfg = ServingConfig(max_slots=4, max_admit=2, block_size=4,
                            n_blocks=32, prefill_buckets=(8, 16),
                            max_total_tokens=32)
        assert cfg.dtype == "bfloat16"
        rng = np.random.RandomState(8)
        prompts = [rng.randint(0, 97, (L,)).astype(np.int32)
                   for L in (6, 3)]
        a = ServingEngine(model, cfg).generate_tokens(prompts, [5, 5])
        b = ServingEngine(model, cfg).generate_tokens(prompts, [5, 5])
        assert a == b
        for row in a:
            assert all(0 <= t < 97 for t in row)

    def test_bf16_pools_and_params(self, model):
        eng = ServingEngine(model, ServingConfig(
            max_slots=2, max_admit=1, block_size=4, n_blocks=16,
            prefill_buckets=(8,), max_total_tokens=16))
        k, v = eng.cache.pools[0]
        assert str(k.dtype) == "bfloat16" == str(v.dtype)
        assert str(eng.params["wte"].dtype) == "bfloat16"
        # a page is [block_size, hidden]: one row a token
        assert k.shape == v.shape == (16, 4, eng.params["wte"].shape[1])


class TestSchedulerUnits:
    def test_ladder_pick_and_errors(self):
        lad = BucketLadder((8, 16), (4,), block_size=4)
        assert lad.pick_prefill(3) == 8
        assert lad.pick_prefill(9) == 16
        assert lad.pick_decode(1) == 4
        assert lad.size == 3
        with pytest.raises(ValueError, match="exceeds"):
            lad.pick_prefill(17)
        with pytest.raises(ValueError, match="multiple"):
            BucketLadder((6,), (4,), block_size=4)

    def test_fifo_head_blocks(self):
        class FakeCache:
            n_free = 4
            available_pages = 4
            def blocks_for(self, n):
                return n
        s = FifoScheduler(max_slots=8, max_admit=8)
        s.submit(Request(ids=np.ones(2, np.int32), max_new_tokens=3))
        big = Request(ids=np.ones(2, np.int32), max_new_tokens=98)
        small = Request(ids=np.ones(2, np.int32), max_new_tokens=1)
        s.submit(big)
        s.submit(small)
        got = s.take_admissible(FakeCache())
        # head fits (5 > 4? no: 2+3=5 blocks_for -> 5 > 4) — nothing
        # overtakes the blocked head even though `small` would fit
        assert [r.max_new_tokens for r in got] == []
        assert s.queue_depth == 3

    def test_config_validation(self):
        with pytest.raises(ValueError, match="decode bucket"):
            ServingConfig(max_slots=8, decode_buckets=(4,))
        with pytest.raises(ValueError, match="max_total_tokens"):
            ServingConfig(prefill_buckets=(32,), max_total_tokens=16)
        with pytest.raises(ValueError, match="decode_chunk"):
            ServingConfig(decode_chunk=0)


class TestSampling:
    def test_temperature_sampling_deterministic_and_in_range(self,
                                                             model):
        """Sampling mode (temperature>0): per-boundary keys split into
        distinct prefill/decode subkeys; same seed -> same stream."""
        def build():
            return ServingEngine(model, f32_config(
                max_slots=2, max_admit=2, prefill_buckets=(8,),
                max_total_tokens=16, temperature=0.8, top_k=12,
                seed=11))
        rng = np.random.RandomState(9)
        prompts = [rng.randint(0, 97, (L,)).astype(np.int32)
                   for L in (5, 3)]
        a = build().generate_tokens(prompts, [6, 4])
        b = build().generate_tokens(prompts, [6, 4])
        assert a == b
        assert all(0 <= t < 97 for row in a for t in row)
        assert len(a[0]) == 6 and len(a[1]) == 4


class TestInferenceSurface:
    def test_create_serving_engine(self, model):
        """inference.create_serving_engine — the serving twin of
        create_predictor — builds a configured engine."""
        from paddle_tpu.inference import create_serving_engine
        eng = create_serving_engine(
            model, warmup=False, max_slots=2, max_admit=1,
            block_size=4, n_blocks=16, prefill_buckets=(8,),
            max_total_tokens=16, dtype=None)
        assert eng.expected_executables == 2
        assert eng.config.max_slots == 2
        with pytest.raises(ValueError, match="not both"):
            create_serving_engine(model, serving_config=eng.config,
                                  max_slots=2)


class TestGraphLintDonation:
    def test_decode_and_prefill_pools_alias(self, model, engine):
        """The donation receipt: both serving programs' donated page
        pools must appear in XLA's input_output_alias table (threshold
        lowered to this test's tiny pool bytes)."""
        import jax
        import numpy as np
        from paddle_tpu.analysis import (GraphLintConfig, ProgramAudit,
                                         run_rules)
        cfg = engine.config
        W = cfg.table_width
        key = jax.random.key(0)
        pool_bytes = int(np.prod(engine.cache.pools[0][0].shape)) * 4
        lint_cfg = GraphLintConfig(donation_bytes=min(pool_bytes, 64))
        lowered = engine._decode.lower(
            engine.cache.pools, np.zeros((4, W), np.int32),
            np.zeros((4,), np.int32), np.zeros((4,), np.int32),
            engine.params, key)
        audit = ProgramAudit("serving_decode", lowered=lowered,
                             config=lint_cfg)
        donated = [a for a in audit.flat_args() if a["donated"]]
        assert len(donated) == 2 * 2       # n_layers x (k, v) pools
        findings = run_rules(audit, only=["donation"])
        assert findings == [], [f.message for f in findings]
        lowered_p = engine._prefill.lower(
            engine.cache.pools, np.zeros((2, W), np.int32),
            np.zeros((2, 8), np.int32), np.ones((2,), np.int32),
            engine.params, key)
        audit_p = ProgramAudit("serving_prefill", lowered=lowered_p,
                               config=lint_cfg)
        findings = run_rules(audit_p, only=["donation"])
        assert findings == [], [f.message for f in findings]


class TestRetiredEvictedCounters:
    def test_retire_counts_retired_not_evicted(self, model):
        """Regression pin post-alias-retirement: finishing a request
        increments serving.retired_total and NOTHING else — the plain
        serving.evicted_total stays zero until a real eviction, and
        the PR 11 ``{deprecated=retired_alias}`` shim is gone (a
        labeled alias series must not even be created)."""
        from paddle_tpu.observability import metrics
        eng = ServingEngine(model, f32_config())
        rng = np.random.RandomState(11)
        p = rng.randint(0, 97, (4,)).astype(np.int32)
        with metrics.enabled_scope(True):
            metrics.reset(prefix="serving.")
            eng.generate_tokens([p], [3])
            assert metrics.get("serving.retired_total").value() == 1
            evicted = metrics.get("serving.evicted_total")
            assert evicted is None or evicted.value() == 0
            alias = metrics.get("serving.evicted_total",
                                deprecated="retired_alias")
            assert alias is None

    def test_evict_requests_counts_and_frees(self, model):
        from paddle_tpu.observability import metrics
        eng = ServingEngine(model, f32_config())
        rng = np.random.RandomState(12)
        prompts = [rng.randint(0, 97, (4,)).astype(np.int32)
                   for _ in range(3)]
        with metrics.enabled_scope(True):
            metrics.reset(prefix="serving.")
            for p in prompts:
                eng.submit(p, 6)
            eng.step()          # admit 2 (max_admit), 1 stays queued
            evicted = eng.evict_requests()
            assert metrics.get("serving.evicted_total").value() == 3
            assert metrics.get("serving.retired_total").value() == 0
        assert len(evicted) == 3
        # running first (with emitted state), then queued
        assert len(evicted[0].out) >= 1
        assert evicted[2].out == []
        # all pages back, scheduler empty
        assert eng.cache.n_free == eng.cache.n_blocks - 1
        assert not eng.has_work()
        eng.cache.check_invariants()

    def test_evicted_request_resumes_exactly(self, model):
        """Single-engine replay contract: prefill(prompt + emitted)
        continues the stream bit-identically (the fleet requeue math,
        provable without a fleet)."""
        eng = ServingEngine(model, f32_config()).warmup()
        rng = np.random.RandomState(13)
        p = rng.randint(0, 97, (5,)).astype(np.int32)
        eng.submit(p, 8)
        eng.step()
        eng.step()
        (r,) = eng.evict_requests()
        k = len(r.out)
        assert 1 <= k < 8
        resumed_ids = np.concatenate(
            [p, np.asarray(r.out, np.int32)])
        eng.submit(resumed_ids, 8 - k)
        done = eng.run_to_completion()
        suffix = done[-1].out
        full = list(r.out) + list(suffix)
        np.testing.assert_array_equal(
            np.asarray(full), solo_greedy(model, p, 8))


class TestHotWeightSwap:
    def test_same_weights_swap_mid_stream_is_identity(self, model):
        """Flip at a token boundary mid-decode: same weights => same
        stream, zero sentinel events, executable count pinned."""
        eng = ServingEngine(model, f32_config()).warmup()
        rng = np.random.RandomState(14)
        p = rng.randint(0, 97, (5,)).astype(np.int32)
        from paddle_tpu.models.generation import _gpt_params
        eng.submit(p, 8)
        eng.step()
        eng.step()
        eng.swap_weights(_gpt_params(model))    # token boundary
        done = eng.run_to_completion()
        np.testing.assert_array_equal(
            np.asarray(done[-1].out), solo_greedy(model, p, 8))
        assert eng.sentinel.fired == 0
        assert eng.executable_count() == eng.expected_executables

    def test_shape_mismatch_rejected_before_flip(self, model):
        import paddle_tpu as paddle
        paddle.seed(15)
        other = GPTForCausalLM(GPTConfig(
            vocab_size=97, hidden_size=64, num_layers=2, num_heads=4,
            max_seq_len=64, dropout=0.0, use_flash_attention=False))
        other.eval()
        eng = ServingEngine(model, f32_config())
        old = eng.params
        from paddle_tpu.models.generation import _gpt_params
        with pytest.raises(ValueError, match="swap rejected"):
            eng.swap_weights(_gpt_params(other))
        assert eng.params is old
