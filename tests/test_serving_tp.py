"""Tensor-parallel serving (paddle_tpu.serving + MeshPlan(tp=N)):
the ISSUE 20 contracts.

Receipts pinned here:
- tp=2 f32 greedy decode under STAGGERED admission is bit-identical
  per request to the dense-cache generation.py reference (and hence
  to the tp=1 engine, whose identical parity test_serving_engine
  pins) — parity by construction through the shared program bodies;
- the compile contract extends: executable count == the same
  feature-dependent ``expected_executables``, RecompileSentinel
  pinned at zero steady-state recompiles;
- the paged K/V pools shard over heads (P(None, None, 'tp', None)):
  per-chip shard bytes == pool bytes / tp, ``stats()`` carries
  ``pool_bytes_per_chip``, and the committed memory baseline holds
  the per-chip peak shrink vs the tp=1 rows;
- pools stay DONATED in the jit(shard_map) programs and the tp decode
  step shows no >=1 MiB implicit all-gather (graph_lint rules);
- config-time rejections name their dims: tp must divide n_heads,
  speculative_k / prefix_sharing / non-tp mesh axes are refused under
  a tp plan, int8 under tp stays deterministic with the same ladder;
- hot weight swap under tp re-shards the standby onto the plan's mesh
  with zero recompiles; the fleet stages the tp-sharded standby and
  keeps the exact-requeue contract (tp=2 group replicas).
"""
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.sharding import MeshPlan
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import ServingConfig, ServingEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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
    base["plan"] = MeshPlan(tp=2)
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


class TestTpParity:
    def test_staggered_admission_bit_exact(self, model, engine):
        """The acceptance bar: requests admitted at DIFFERENT token
        boundaries through the tp=2 shard_map programs each decode
        exactly as the dense-cache reference — the same prompts and
        stagger test_serving_engine pins for the tp=1 engine, so the
        two engines' streams are transitively bit-identical."""
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

    def test_zero_steady_state_recompiles(self, engine):
        """The compile contract under tp: same feature-dependent
        ladder size, sentinel never fired."""
        assert engine.executable_count() == engine.expected_executables
        assert engine.sentinel.fired == 0
        assert engine.sentinel.counter.value() == 0

    def test_swap_weights_resharts_zero_recompiles(self, model,
                                                   engine):
        """A hot swap under tp re-shards the standby onto the plan's
        mesh (device_put with the derived specs, NOT the tp=1 host
        round-trip) — same-weights swap leaves greedy output
        bit-identical with zero new executables."""
        before = engine.executable_count()
        from paddle_tpu.models.generation import _gpt_params
        engine.swap_weights(_gpt_params(model))
        rng = np.random.RandomState(5)
        p = rng.randint(0, 97, (6,)).astype(np.int32)
        out = engine.generate_tokens([p], [5])[0]
        np.testing.assert_array_equal(np.asarray(out),
                                      solo_greedy(model, p, 5))
        assert engine.executable_count() == before
        assert engine.sentinel.fired == 0


class TestTpPools:
    def test_pools_shard_over_heads(self, engine):
        """Each K/V page pool leaf shards P(None, None, 'tp'): 2
        shards, each holding n_heads/2 whole heads of every page, a
        contiguous half of the merged n_heads * head_dim axis —
        per-chip bytes exactly half the global pool."""
        for k, v in engine.cache.pools:
            for leaf in (k, v):
                shards = leaf.addressable_shards
                assert len(shards) == 2
                assert leaf.shape == (32, 4, 4 * 8)
                assert shards[0].data.shape == (32, 4, 2 * 8)
                assert shards[0].data.nbytes * 2 == leaf.nbytes
        st = engine.cache.stats()
        assert st["pool_bytes_per_chip"] * 2 == st["pool_bytes"]

    def test_memory_baseline_holds_per_chip_shrink(self):
        """The committed memory plane receipt: the serving_*_tp2 rows
        exist in tools/memory_baseline.json and their per-chip peaks
        sit well under the tp=1 rows (pools+weights halve; replicated
        tables/embeddings are the +epsilon that keeps it above 1/2)."""
        with open(os.path.join(REPO, "tools",
                               "memory_baseline.json")) as f:
            doc = json.load(f)
        progs = doc["programs"]
        for name in ("serving_decode", "serving_prefill"):
            full = progs[name]["peak_bytes"]
            per_chip = progs[name + "_tp2"]["peak_bytes"]
            assert 0.5 * full <= per_chip < 0.85 * full, \
                (name, full, per_chip)


class TestTpGraphLint:
    def test_decode_pools_alias_and_no_implicit_replication(self,
                                                            engine):
        """graph_lint over the tp decode step: the sharded page pools
        still alias (jit(shard_map) keeps input_output_alias) and
        NOTHING >= the tiny thresholds is implicitly all-gathered —
        a spec-derivation bug would materialize the pools or weights
        on every chip right here."""
        import jax
        from paddle_tpu.analysis import (GraphLintConfig, ProgramAudit,
                                         run_rules)
        W = engine.config.table_width
        lint_cfg = GraphLintConfig(donation_bytes=64)
        lowered = engine._decode.lower(
            engine.cache.pools, np.zeros((4, W), np.int32),
            np.zeros((4,), np.int32), np.zeros((4,), np.int32),
            engine.params, jax.random.key(0))
        audit = ProgramAudit("serving_tp_decode", lowered=lowered,
                             config=lint_cfg)
        donated = [a for a in audit.flat_args() if a["donated"]]
        assert len(donated) == 2 * 2       # n_layers x (k, v) pools
        findings = run_rules(audit,
                             only=["donation", "implicit-replication"])
        assert findings == [], [f.message for f in findings]


class TestConfigValidation:
    def test_tp_must_divide_n_heads_names_dims(self, model):
        """The config-time rejection NAMES the offending dims."""
        with pytest.raises(ValueError, match=r"tp=3 must divide "
                                             r"n_heads=4"):
            ServingEngine(model, f32_config(plan=MeshPlan(tp=3)))

    def test_speculative_rejected_under_tp(self):
        with pytest.raises(ValueError,
                           match="speculative_k is not supported "
                                 "under a tp plan"):
            f32_config(speculative_k=2)

    def test_prefix_sharing_rejected_under_tp(self):
        with pytest.raises(ValueError,
                           match="prefix_sharing is not supported "
                                 "under a tp plan"):
            f32_config(prefix_sharing=True)

    def test_non_tp_axes_rejected(self):
        """The engine shards over 'tp' only — replica parallelism is
        the fleet's job."""
        with pytest.raises(ValueError, match="shard over 'tp' only"):
            f32_config(plan=MeshPlan(dp=2, tp=2))

    def test_plan_type_checked(self):
        with pytest.raises(ValueError, match="MeshPlan"):
            ServingConfig(plan="tp2")
        with pytest.raises(ValueError, match="tp_wire"):
            f32_config(tp_wire="int4")

    def test_create_serving_engine_plan_passthrough(self, model):
        from paddle_tpu.inference import create_serving_engine
        eng = create_serving_engine(
            model, warmup=False, plan=MeshPlan(tp=2), max_slots=2,
            max_admit=1, block_size=4, n_blocks=16,
            prefill_buckets=(8,), max_total_tokens=16, dtype=None)
        assert eng.tp == 2
        with pytest.raises(ValueError, match="not both"):
            create_serving_engine(model, serving_config=eng.config,
                                  plan=MeshPlan(tp=2))


def tp_fleet_config(**kw):
    """Requeue-capable tp=2 ladder (largest prefill bucket covers
    every resumable prefix, the fleet build-time validation)."""
    base = dict(max_slots=4, max_admit=2, block_size=4, n_blocks=48,
                prefill_buckets=(24,), max_total_tokens=24,
                decode_chunk=2, dtype=None, plan=MeshPlan(tp=2))
    base.update(kw)
    return ServingConfig(**base)


class TestFleetTp:
    """A fleet replica generalizes to a tp-GROUP: every engine the
    fleet spawns runs the tp=2 shard_map programs, and the standby
    weight pool it stages is built ONCE with the tp-sharded treedef
    (qkv head-major permutation + device_put on the plan's mesh)."""

    def test_exact_requeue_under_tp(self, model, tmp_path):
        """Kill a tp-group mid-decode: its requests resume on the
        other group and every stitched stream stays bit-identical to
        the dense-cache reference — the exact-requeue contract
        re-pinned under tp=2."""
        from paddle_tpu.serving import (FleetConfig, ServingFleet,
                                        ServingSLO)
        fl = ServingFleet(
            model, tp_fleet_config(), ServingSLO(),
            FleetConfig(replicas=2, min_replicas=1, max_replicas=2,
                        autoscale=False, backoff_base=0.0,
                        receipts_dir=str(tmp_path)))
        rng = np.random.RandomState(1)
        specs = [(7, 8), (3, 6), (11, 5), (2, 7)]
        prompts = [rng.randint(0, 97, (L,)).astype(np.int32)
                   for L, _ in specs]
        frs = [fl.submit(p, n) for p, (_, n) in zip(prompts, specs)]
        done = []
        for _ in range(3):
            done.extend(fl.step())
        target = next(fr for fr in frs
                      if len(fr.emitted) >= 2
                      and fr.replica is not None)
        fl.kill_replica(target.replica)
        done.extend(fl.run_until_drained())
        assert len(done) == 4
        assert target.evictions == 1
        for fr, p, (_, n) in zip(frs, prompts, specs):
            assert list(fr.emitted) == \
                [int(t) for t in solo_greedy(model, p, n)], fr.rid
        assert fl.requeued_total >= 1
        assert fl.recompile_events() == 0

    @pytest.mark.slow  # heaviest fleet drill; tier-1 keeps the
    #                    engine-level swap pin (TestTpParity) and the
    #                    requeue sibling above
    def test_swap_flip_under_tp_zero_recompiles(self, model,
                                                tmp_path):
        """swap_weights stages ONE tp-sharded standby and flips each
        group at a token boundary: zero drops, zero recompiles,
        same-weights swap keeps outputs bit-identical."""
        from paddle_tpu.serving import (FleetConfig, ServingFleet,
                                        ServingSLO)
        fl = ServingFleet(
            model, tp_fleet_config(), ServingSLO(),
            FleetConfig(replicas=1, min_replicas=1, max_replicas=1,
                        autoscale=False, backoff_base=0.0,
                        receipts_dir=str(tmp_path)))
        rng = np.random.RandomState(8)
        prompts = [rng.randint(0, 97, (L,)).astype(np.int32)
                   for L in (5, 3, 7)]
        frs = [fl.submit(p, 6) for p in prompts]
        for _ in range(2):
            fl.step()
        assert fl.swap_weights(model) is True   # same weights
        done = fl.run_until_drained()
        while fl._standby is not None:          # finish pending flips
            fl.step()
        assert len(done) == 3
        assert fl.swaps_total == 1
        assert fl.recompile_events() == 0
        # the staged standby was the tp-sharded treedef: the live
        # engine's params carry the plan's 2-shard placement
        eng = fl._replicas[0].engine
        qkv = eng.params["blocks"][0]["qkv_w"]
        assert len(qkv.addressable_shards) == 2
        for fr, p in zip(frs, prompts):
            assert list(fr.emitted) == \
                [int(t) for t in solo_greedy(model, p, 6)]


class TestInt8UnderTp:
    def test_int8_tp_deterministic_with_pinned_ladder(self, model):
        """quant="int8" composes with a tp plan: the {"q8","s"} leaves
        shard by the same rules (codes like their float parent, scales
        like its columns), decode stays deterministic run-to-run, and
        the ladder lands on expected_executables with zero sentinel
        events. (Bitwise tp=1 parity is NOT claimed: the row-parallel
        proj/fc2 dynamic activation scales are computed on the local
        shard, a bounded drift the int8 contract already carries.)"""
        eng = ServingEngine(model, f32_config(
            quant="int8", prefill_buckets=(8,), max_slots=2,
            max_admit=2, max_total_tokens=16)).warmup()
        rng = np.random.RandomState(7)
        prompts = [rng.randint(0, 97, (L,)).astype(np.int32)
                   for L in (5, 7)]
        a = eng.generate_tokens(prompts, [5, 4])
        b = eng.generate_tokens(prompts, [5, 4])
        assert a == b
        assert all(0 <= t < 97 for row in a for t in row)
        assert eng.executable_count() == eng.expected_executables
        assert eng.sentinel.fired == 0
