"""Unified mesh & sharding planner (distributed/sharding.MeshPlan).

Four planes, mirroring the tentpole's layers:

- cost model: candidate_layouts / estimate_layout / choose_layout —
  three pinned (mesh × model) corners where dp, fsdp and tp must each
  win, plus the must-raise-at-plan-time infeasibility contract
- spec derivation: one layout declaration -> every param / activation /
  optimizer-state / data PartitionSpec (embedding fsdp×tp product,
  row/col projections, stacked [S,...] pipeline specs), mesh-FREE so a
  host without the gang's devices (a regrown elastic slot) can compute
  its resync plan
- ParamSynchronizer: the explicit-manual FSDP bucket surface — flat
  partitioning over GradSynchronizer's fused buckets, gather/scatter
  round-trips through every wire tier
- the ONE-executable contract: the planner-driven dp×tp×pp engine
  trains f32-parity-equal to the composed manual spmd engine, in ONE
  donated-buffer executable (compile_count == 1, one dispatch/step,
  RecompileSentinel quiet after step 1)

The expensive parity run lives in a module-scoped fixture: tier-1
budget measures call phases, and every assertion over the trained
engines is cheap.
"""
import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
import paddle_tpu.nn as nn
from paddle_tpu.distributed.sharding import (LayoutCost, MeshPlan,
                                             ModelDims,
                                             candidate_layouts,
                                             choose_layout,
                                             estimate_layout)

GiB = 2 ** 30


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------

class TestCostModel:
    def test_candidates_factorize_device_count(self):
        for n in (1, 2, 4, 8):
            for c in candidate_layouts(n):
                prod = c["dp"] * c["fsdp"] * c["tp"] * c["pp"]
                assert prod == n, c
        # caps prune the space
        assert all(c["tp"] <= 2 and c["pp"] <= 2
                   for c in candidate_layouts(8, max_tp=2, max_pp=2))

    def test_corner_small_model_prefers_pure_dp(self):
        # 10M params fit replicated with room to spare: every sharding
        # axis only adds wire, so dp must win outright
        dims = ModelDims(n_params=10_000_000, hidden=1024, n_layers=4,
                         batch=64, seq=128)
        best, reports = choose_layout(8, dims,
                                      hbm_bytes_per_chip=16 * GiB)
        assert best == {"dp": 8, "fsdp": 1, "tp": 1, "pp": 1}
        assert any(not r.feasible or r.cost > 0 for r in reports)

    def test_corner_big_model_forces_fsdp(self):
        # 2B params × (4B param+grad + 8B adam moments) ≈ 32 GB of
        # state: replicated is infeasible at 12 GB/chip, and fsdp
        # shards state at far less wire than tp's per-layer activation
        # all-reduces at this batch
        dims = ModelDims(n_params=2_000_000_000, hidden=4096,
                         n_layers=24, batch=128, seq=512)
        best, _ = choose_layout(8, dims, hbm_bytes_per_chip=12 * GiB)
        assert best == {"dp": 1, "fsdp": 8, "tp": 1, "pp": 1}

    def test_corner_huge_layer_forces_tp(self):
        # one 1.5B-param layer: fsdp's transient full-layer gather
        # workspace blows the budget unless tp also splits the layer —
        # every feasible layout must carry tp > 1 (pp capped at 2 so
        # deep pipelining can't dodge the big layer)
        dims = ModelDims(n_params=4_000_000_000, hidden=8192,
                         n_layers=8, batch=16, seq=512,
                         largest_layer_params=1_500_000_000)
        best, reports = choose_layout(8, dims,
                                      hbm_bytes_per_chip=12 * GiB,
                                      max_pp=2)
        assert best["tp"] > 1, best
        assert all(r.sizes["tp"] > 1 for r in reports if r.feasible)

    def test_infeasible_raises_at_plan_time_with_closest(self):
        dims = ModelDims(n_params=4_000_000_000, hidden=8192,
                         n_layers=8, batch=16, seq=512)
        with pytest.raises(ValueError, match="closest"):
            choose_layout(8, dims, hbm_bytes_per_chip=1 * GiB)

    def test_estimate_reports_are_auditable(self):
        dims = ModelDims(n_params=1_000_000, hidden=256, n_layers=2,
                         batch=8, seq=64)
        r = estimate_layout({"dp": 2, "fsdp": 2, "tp": 2, "pp": 1},
                            dims, hbm_bytes_per_chip=8 * GiB)
        assert isinstance(r, LayoutCost) and r.feasible
        d = r.as_dict()
        assert d["sizes"] == {"dp": 2, "fsdp": 2, "tp": 2, "pp": 1}
        assert d["hbm_per_chip"] > 0 and d["wire_per_chip"] > 0

    def test_compression_tier_shrinks_wire(self):
        dims = ModelDims(n_params=50_000_000, hidden=1024, n_layers=4,
                         batch=32, seq=128)
        sizes = {"dp": 8, "fsdp": 1, "tp": 1, "pp": 1}
        none = estimate_layout(sizes, dims, 16 * GiB, compress="none")
        int8 = estimate_layout(sizes, dims, 16 * GiB,
                               compress="int8_ef")
        assert int8.wire_per_chip < none.wire_per_chip

    def test_auto_plan_carries_report(self):
        dims = ModelDims(n_params=10_000_000, hidden=1024, n_layers=4,
                         batch=64, seq=128)
        plan = MeshPlan.auto(8, dims, hbm_bytes_per_chip=16 * GiB)
        assert plan.sizes["dp"] == 8
        assert plan.report and all(isinstance(r, LayoutCost)
                                   for r in plan.report)
        assert "report" in plan.describe()

    def test_candidate_report_carries_both_absolute_estimates(self):
        """PR 18: every candidate names its analytic step-time in
        absolute seconds, decomposes wire per logical axis with call
        counts (the shape the calibration latency+bandwidth model
        consumes), and — when a calibration table is supplied — ALSO
        the calibrated estimate plus which one ranked it."""
        from paddle_tpu.observability import calibration as cal
        dims = ModelDims(n_params=10_000_000, hidden=1024, n_layers=4,
                         batch=64, seq=128)
        sizes = {"dp": 2, "fsdp": 1, "tp": 2, "pp": 2}
        plain = estimate_layout(sizes, dims, 16 * GiB)
        assert plain.analytic_step_time_s > 0
        assert plain.calibrated_step_time_s is None
        assert plain.used == "analytic"
        assert plain.step_time_s == plain.analytic_step_time_s
        for axis in ("dp", "tp", "pp"):
            row = plain.wire_by_axis[axis]
            assert row["bytes"] > 0 and row["calls"] >= 1, axis

        calib = cal.Calibration(cal.build_table(device_kind="cpu",
                                                n_devices=8))
        scored = estimate_layout(sizes, dims, 16 * GiB,
                                 calibration=calib)
        assert scored.used == "calibrated"
        assert scored.calibrated_step_time_s > 0
        assert scored.analytic_step_time_s \
            == plain.analytic_step_time_s     # both always reported
        assert scored.step_time_s == scored.calibrated_step_time_s
        d = scored.as_dict()
        assert d["used"] == "calibrated"
        assert d["calibrated_step_time_s"] > 0
        # feasibility is byte math — the ruler never changes it
        assert scored.feasible == plain.feasible
        assert scored.hbm_per_chip == plain.hbm_per_chip

    def test_calibrated_ranking_preserves_feasibility(self):
        """choose_layout under a calibration table still returns a
        feasible factorization of the device count — the table only
        re-ranks, never admits an infeasible layout."""
        from paddle_tpu.observability import calibration as cal
        calib = cal.Calibration(cal.build_table(device_kind="cpu",
                                                n_devices=8))
        dims = ModelDims(n_params=10_000_000, hidden=1024, n_layers=4,
                         batch=64, seq=128)
        sizes, report = choose_layout(8, dims, 16 * GiB,
                                      calibration=calib)
        n = 1
        for v in sizes.values():
            n *= v
        assert n == 8
        best = next(r for r in report if r.sizes == sizes)
        assert best.feasible and best.used == "calibrated"
        # the winner minimizes the calibrated ruler among feasible
        feasible = [r for r in report if r.feasible]
        assert best.calibrated_step_time_s == min(
            r.calibrated_step_time_s for r in feasible)
        # infeasible stays infeasible with the table supplied
        big = ModelDims(n_params=4_000_000_000, hidden=8192,
                        n_layers=8, batch=16, seq=512)
        with pytest.raises(ValueError, match="closest"):
            choose_layout(8, big, hbm_bytes_per_chip=1 * GiB,
                          calibration=calib)


# ---------------------------------------------------------------------------
# spec derivation (mesh-free: no devices touched)
# ---------------------------------------------------------------------------

def _annotated_params():
    qkv = paddle.create_parameter([64, 192], "float32")
    qkv.sharding_spec = P(None, "tp")        # col-parallel
    out = paddle.create_parameter([64, 64], "float32")
    out.sharding_spec = P("tp", None)        # row-parallel
    norm = paddle.create_parameter([64], "float32")
    emb = paddle.create_parameter([256, 64], "float32")
    emb.sharding_spec = P("tp", None)        # vocab-sharded table
    return qkv, out, norm, emb


class TestSpecDerivation:
    def test_full_hybrid_layout(self):
        plan = MeshPlan(dp=2, fsdp=2, tp=2, pp=2)
        qkv, out, norm, emb = _annotated_params()
        # projections keep their tp dim, fsdp lands on the free dim
        assert plan.param_spec("attn.qkv.weight", qkv) == \
            P("fsdp", "tp")
        assert plan.param_spec("attn.out.weight", out) == \
            P("tp", "fsdp")
        # ZeRO-3: even the norm vector shards over fsdp
        assert plan.param_spec("ln.weight", norm) == P("fsdp")
        # the ISSUE's embedding case: vocab dim carries the
        # ('fsdp','tp') PRODUCT, not a fallback to the hidden dim
        assert plan.param_spec("embed.weight", emb) == \
            P(("fsdp", "tp"), None)
        # optimizer moments mirror the param layout exactly
        assert plan.state_spec("embed.weight", emb) == \
            plan.param_spec("embed.weight", emb)

    def test_stacked_and_data_specs(self):
        plan = MeshPlan(dp=2, fsdp=2, tp=2, pp=2)
        qkv, _, _, _ = _annotated_params()
        assert plan.stacked_param_spec("attn.qkv.weight", qkv) == \
            P("pp", "fsdp", "tp")
        assert plan.data_spec(np.zeros((8, 16))) == \
            P(("dp", "fsdp"), None)
        assert plan.activation_spec(3) == P(("dp", "fsdp"), None, None)
        assert plan.stacked_activation_spec(3) == \
            P("pp", ("dp", "fsdp"), None)

    def test_axis_names_drop_size_one(self):
        assert MeshPlan(dp=4, pp=2).axis_names() == ("pp", "dp")
        assert MeshPlan(dp=4, pp=2).mesh_shape() == {"pp": 2, "dp": 4}
        assert MeshPlan().axis_names() == ()

    def test_stale_annotation_degrades_to_replicated(self):
        # a model annotated for tp, planned onto a dp-only layout:
        # the tp labels sanitize away instead of crashing mesh checks
        plan = MeshPlan(dp=2)
        qkv, _, norm, _ = _annotated_params()
        assert plan.param_spec("attn.qkv.weight", qkv) == P(None, None)
        assert plan.param_spec("ln.weight", norm) == P()

    def test_derivation_is_mesh_free(self):
        # a regrown elastic slot computes its resync plan on a host
        # WITHOUT the gang's devices: deriving specs must not build
        # the device mesh
        plan = MeshPlan(dp=2, fsdp=2, tp=2, pp=2)   # 16 "devices"
        qkv, out, norm, emb = _annotated_params()
        for name, t in (("attn.qkv.weight", qkv), ("ln.weight", norm),
                        ("embed.weight", emb)):
            plan.param_spec(name, t)
        plan.resync_assignments({"q": qkv, "n": norm})
        assert plan._mesh is None

    def test_resync_assignments(self):
        qkv, out, norm, emb = _annotated_params()
        named = {"q": qkv, "o": out, "n": norm, "e": emb}
        # fsdp in the layout: every fsdp-sharded param needs all_gather
        fsdp = MeshPlan(dp=2, fsdp=2, tp=2, pp=2)
        assert set(fsdp.resync_assignments(named).values()) == \
            {"all_gather"}
        # dp/tp-only layouts replicate across the data axes: any
        # survivor owns the bytes
        assert set(MeshPlan(dp=2, tp=2).resync_assignments(
            named).values()) == {"broadcast"}


# ---------------------------------------------------------------------------
# ParamSynchronizer: the explicit FSDP bucket surface
# ---------------------------------------------------------------------------

def _psync_params():
    rng = np.random.RandomState(3)
    return {"a": rng.randn(6, 5).astype(np.float32),
            "b": rng.randn(7).astype(np.float32),
            "c": rng.randn(3, 3).astype(np.float32)}


class TestParamSynchronizer:
    def test_world1_identity(self):
        from paddle_tpu.distributed.comm import (CommConfig,
                                                 ParamSynchronizer)
        params = _psync_params()
        ps = ParamSynchronizer(CommConfig())
        chunks = ps.shard(params)
        back = ps.gather(chunks, params)
        for k in params:
            np.testing.assert_array_equal(back[k], params[k])
        g, _ = ps.scatter_grads(params)
        assert set(g) == set(chunks)

    @pytest.mark.parametrize("compress,rtol", [
        ("f32", 0.0), ("bf16", 1e-2), ("int8_ef", 0.12)])
    def test_fsdp4_roundtrip_tiers(self, compress, rtol):
        import jax.numpy as jnp
        from paddle_tpu.distributed.comm import (CommConfig,
                                                 ParamSynchronizer)
        from jax.sharding import Mesh
        shard_map = jax.shard_map

        params = _psync_params()
        mesh = Mesh(np.array(jax.devices()[:4]), ("fsdp",))
        ps = ParamSynchronizer(CommConfig(compress=compress))

        def body(_):
            chunks = ps.shard(params)
            full = ps.gather(chunks, params)
            # grads = params: after reduce-scatter each owned chunk
            # must equal world * its shard slice of the flat bucket
            scat, _ = ps.scatter_grads(params)
            return full, scat, chunks

        full, scat, chunks = shard_map(
            body, mesh=mesh, in_specs=(P("fsdp"),),
            out_specs=(P(), P("fsdp"), P("fsdp")),
            check_vma=False)(jnp.zeros((4,)))
        for k in params:
            if compress == "none":
                np.testing.assert_array_equal(full[k], params[k])
            else:
                np.testing.assert_allclose(
                    np.asarray(full[k]), params[k], rtol=rtol,
                    atol=rtol)
        # every rank contributed identical grads: the reduced owned
        # chunks are 4x the sharded ones (within the wire tier)
        for key in chunks:
            np.testing.assert_allclose(
                np.asarray(scat[key]), 4.0 * np.asarray(chunks[key]),
                rtol=max(rtol, 1e-6), atol=max(rtol, 1e-6) * 4)


# ---------------------------------------------------------------------------
# one-executable parity: planner engine vs composed manual spmd engine
# ---------------------------------------------------------------------------

S, M, H, MB = 2, 8, 16, 8


class _TanhStage(nn.Layer):
    def __init__(self, wi, bi):
        super().__init__()
        self.lin = nn.Linear(H, H)
        self.lin.weight.set_value(np.asarray(wi))
        self.lin.bias.set_value(np.asarray(bi))
        self.lin.weight.sharding_spec = P(None, "tp")  # col-parallel
        self.lin.bias.sharding_spec = P("tp")

    def forward(self, xx):
        return paddle.tanh(self.lin(xx))


def _train(planner, w0, b0, xh, yh, steps=5):
    paddle.seed(0)
    stages = [_TanhStage(w0[i], b0[i]) for i in range(S)]
    x, y = paddle.to_tensor(xh), paddle.to_tensor(yh)
    opt = paddle.optimizer.SGD(learning_rate=1e-2)
    if planner:
        plan = MeshPlan(dp=2, tp=2, pp=S)
        eng = dist.PipelineParallel(
            stages, lambda o, t: ((o - t) ** 2).mean(), opt,
            num_micro=M, mesh=plan.build_mesh(),
            exec_mode="spmd_1f1b", plan=plan)
    else:
        mesh = dist.build_mesh({"pp": S}, devices=jax.devices()[:S])
        eng = dist.PipelineParallel(
            stages, lambda o, t: ((o - t) ** 2).mean(), opt,
            num_micro=M, mesh=mesh, exec_mode="spmd_1f1b")
    losses = [float(eng.train_batch(x, y).item()) for _ in range(steps)]
    eng.sync_to_layers()
    weights = [np.asarray(st.lin.weight._data) for st in stages]
    return losses, weights, eng


@pytest.fixture(scope="module")
def parity():
    """Train the same 2-stage model through BOTH engines (expensive:
    two spmd compiles — module-scoped so tier-1 pays it once)."""
    if jax.device_count() < 8:
        pytest.skip("needs 8 virtual devices (conftest forces them)")
    rng = np.random.RandomState(0)
    w0 = rng.randn(S, H, H).astype(np.float32) * 0.3
    b0 = rng.randn(S, H).astype(np.float32) * 0.1
    xh = rng.randn(M * MB, H).astype(np.float32)
    yh = rng.randn(M * MB, H).astype(np.float32)
    ml, mw, meng = _train(False, w0, b0, xh, yh)
    pl, pw, peng = _train(True, w0, b0, xh, yh)
    return dict(ml=ml, mw=mw, pl=pl, pw=pw, peng=peng, xh=xh)


class TestPlannerEngineParity:
    def test_losses_match_composed_engine(self, parity):
        # dp2×tp2×pp2 planner executable vs the pp-only manual engine:
        # same math, f32 parity over every step
        np.testing.assert_allclose(parity["ml"], parity["pl"],
                                   rtol=2e-5)
        assert all(np.isfinite(parity["pl"]))

    def test_weights_match_after_training(self, parity):
        for i in range(S):
            np.testing.assert_allclose(parity["mw"][i], parity["pw"][i],
                                       rtol=2e-5, atol=1e-6)

    def test_one_executable_no_recompiles(self, parity):
        eng = parity["peng"]
        # ONE jitted step function, compiled exactly once across all 5
        # steps, one dispatch per train_batch — the RecompileSentinel
        # contract the tentpole's acceptance names
        assert eng.compile_count == 1
        assert eng.last_dispatch_count == 1

    def test_eval_path_shares_the_planner_specs(self, parity):
        eng = parity["peng"]
        out = eng.eval_batch(paddle.to_tensor(parity["xh"]))
        assert np.asarray(out._data).shape == (M * MB, H)
        assert np.all(np.isfinite(np.asarray(out._data)))

    def test_planner_leg_carries_a_stamped_plan_receipt(self, parity):
        # The first live train_batch self-stamps the plan's falsifiable
        # prediction — every planner-built executable (the ERNIE legs
        # ride this same engine path) carries it with no opt-in, so the
        # plan-audit loop always has something to join measured values
        # onto.
        eng = parity["peng"]
        r = eng.plan.receipt
        assert r is not None
        assert r.sizes == {"dp": 2, "fsdp": 1, "tp": 2, "pp": S}
        for v in (r.predicted_step_time_s, r.predicted_hbm_bytes,
                  r.predicted_wire_bytes):
            assert np.isfinite(v) and v > 0
        # stamped from the LIVE workload shape: micro-ring input is
        # (M, MB, H) → batch = M*MB
        assert eng.plan.dims.batch == M * MB
        assert r.used in ("analytic", "calibrated")
        # the receipt is join-ready: audit against the prediction
        # itself yields zero error on all three planes
        from paddle_tpu.observability import calibration as cal
        audit = cal.audit(r, {"step_time_s": r.predicted_step_time_s,
                              "hbm_bytes": r.predicted_hbm_bytes,
                              "wire_bytes": r.predicted_wire_bytes})
        assert audit["metrics_joined"] == 3
        assert all(e == 0.0
                   for e in audit["prediction_error"].values())


# ---------------------------------------------------------------------------
# DataParallel(plan=) and fleet integration
# ---------------------------------------------------------------------------

class TestDataParallelPlan:
    def test_plan_places_params_and_batches(self):
        if jax.device_count() < 4:
            pytest.skip("needs 4 devices")
        plan = MeshPlan(dp=2, fsdp=2)
        net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(),
                            nn.Linear(16, 4))
        ddp = dist.DataParallel(net, plan=plan)
        # fsdp-sharded placement: the largest dim of each weight rides
        # the fsdp axis
        w = net.state_dict()["0.weight"]
        assert "fsdp" in str(w._data.sharding.spec)
        x = paddle.to_tensor(
            np.random.RandomState(0).randn(8, 8).astype(np.float32))
        y = ddp(x)
        assert np.asarray(y._data).shape == (8, 4)
        # batch dim sharded over BOTH data axes
        assert ddp._data_axes == ("dp", "fsdp")


class TestFleetPlanner:
    def test_strategy_degrees_to_mesh_plan(self):
        st = dist.fleet.DistributedStrategy()
        st.hybrid_configs = {"dp_degree": 2, "fsdp_degree": 2,
                             "mp_degree": 2, "pp_degree": 1}
        plan = st.mesh_plan(8)
        assert plan.sizes == {"dp": 2, "fsdp": 2, "tp": 2, "pp": 1}
        # fsdp divides out of dp in the mesh shape
        assert plan.mesh_shape() == {"dp": 2, "fsdp": 2, "tp": 2}

    def test_build_mesh_plan_auto_layout(self):
        fleet = dist.fleet.fleet
        fleet.init()
        dims = ModelDims(n_params=10_000_000, hidden=1024, n_layers=4,
                         batch=64, seq=128)
        plan = fleet.build_mesh_plan(layout="auto", dims=dims,
                                     hbm_bytes_per_chip=16 * GiB)
        assert plan.sizes["dp"] == jax.device_count()
        assert plan.report
        with pytest.raises(ValueError, match="auto"):
            fleet.build_mesh_plan(layout="auto")

    def test_build_pipeline_consumes_plan(self):
        if jax.device_count() < 8:
            pytest.skip("needs 8 devices")
        fleet = dist.fleet.fleet
        st = dist.fleet.DistributedStrategy()
        st.pipeline = True
        st.pipeline_configs = {"accumulate_steps": 4}
        fleet.init(strategy=st)
        plan = MeshPlan(dp=2, tp=2, pp=2)
        stages = [nn.Sequential(nn.Linear(8, 8), nn.ReLU())
                  for _ in range(2)]
        eng = fleet.build_pipeline(
            stages, lambda o, y: ((o - y) ** 2).mean(),
            paddle.optimizer.SGD(learning_rate=1e-3), plan=plan,
            schedule="1f1b")
        assert eng.plan is plan
        assert eng.exec_mode == "spmd_1f1b"
