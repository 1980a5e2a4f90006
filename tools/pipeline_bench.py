"""Pipeline throughput receipt (run by bench.py in a subprocess with a
forced virtual-CPU mesh; also runnable standalone).

Prints ONE JSON line. The HEADLINE numbers are the spmd_1f1b engine's
(PipelineParallel exec_mode='spmd_1f1b': the whole train step — every
microbatch forward/backward, grad accumulation, optimizer update — as
ONE jitted shard_map program with donated state):

  speedup_vs_single        spmd_1f1b rows/s vs the identical model as a
                           single-device TrainStep
  compile_count            train executables XLA built (contract: 1)
  dispatches_per_step      jit dispatches per train_batch (contract: 1)
  orchestration_fraction   (median step wall - serial device-compute
                           estimate) / wall, via profiler.StepClock
  step_ms_p50/p99          per-step host wall percentiles

The host-driven dispatch engine (per-stage executables, O(stages x
microbatches) tick loop) is measured alongside under host_* names, with
per-tick dispatch p50/p99 from engine.last_tick_ms — the orchestration
budget the spmd form eliminates.

Shapes are env-tunable so the tier-1 smoke (tests/
test_pipeline_bench_smoke.py) can run tiny: PD_PIPE_BENCH_DEVICES,
PD_PIPE_BENCH_MICRO, PD_PIPE_BENCH_WIDTH, PD_PIPE_BENCH_DEPTH,
PD_PIPE_BENCH_BATCH, PD_PIPE_BENCH_STEPS. PD_PIPE_BENCH_FULL=1 adds the
round-5 receipt legs (raw gpipe/1F1B schedule forms and the stacked
SpmdPipelineParallel engine).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

N_DEV = int(os.environ.get("PD_PIPE_BENCH_DEVICES", 2))

# the CPU device count must be pinned BEFORE the backend exists
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", N_DEV)

import numpy as np  # noqa: E402


def main():
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.distributed as dist
    from paddle_tpu import profiler
    from paddle_tpu.static import TrainStep

    S = N_DEV          # one stage per device
    # default M=4 on the 2-stage CPU acceptance mesh: 16-row
    # microbatches keep the per-microbatch GEMMs out of
    # latency-bound territory so the CPU receipt tracks schedule +
    # dispatch cost, not tiny-GEMM inefficiency (hardware sweeps
    # override via env)
    M = int(os.environ.get("PD_PIPE_BENCH_MICRO", 4))  # microbatches
    width = int(os.environ.get("PD_PIPE_BENCH_WIDTH", 1024))
    depth_per_stage = int(os.environ.get("PD_PIPE_BENCH_DEPTH", 3))
    batch = int(os.environ.get("PD_PIPE_BENCH_BATCH", 64))
    steps = int(os.environ.get("PD_PIPE_BENCH_STEPS", 5))
    full = bool(int(os.environ.get("PD_PIPE_BENCH_FULL", "0")))

    def make_stage():
        layers = []
        for _ in range(depth_per_stage):
            layers += [nn.Linear(width, width), nn.ReLU()]
        return nn.Sequential(*layers)

    def loss_fn(out, y):
        return ((out - y) ** 2).mean()

    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(batch, width).astype(np.float32))
    y = paddle.to_tensor(rng.randn(batch, width).astype(np.float32))
    mesh = dist.build_mesh({"pp": S}, devices=jax.devices()[:S])

    # -- host-driven dispatch engine over pp=S -----------------------------
    paddle.seed(0)
    stages = [make_stage() for _ in range(S)]
    opt = paddle.optimizer.SGD(learning_rate=1e-3)
    engine = dist.PipelineParallel(stages, loss_fn, opt, num_micro=M,
                                   mesh=mesh)
    engine.train_batch(x, y)            # compile
    float(engine.train_batch(x, y).item())
    host_clock = profiler.StepClock()
    for _ in range(steps):
        with host_clock.step():
            loss = engine.train_batch(x, y)
            float(loss.item())   # device-complete inside the bracket
        host_clock.add_ticks(engine.last_tick_ms)
    host_t = host_clock.step_ms(50) / 1e3
    host_dispatches = engine.last_dispatch_count

    # -- identical model, single device ------------------------------------
    paddle.seed(0)
    whole = nn.Sequential(*[make_stage() for _ in range(S)])
    opt2 = paddle.optimizer.SGD(learning_rate=1e-3,
                                parameters=whole.parameters())
    dist.set_mesh(None)
    step = TrainStep(whole, loss_fn, opt2)
    step(x, y)
    float(step(x, y).item())
    # same estimator as the engine legs (StepClock p50): a mean here
    # against medians there would let one GC pause in either loop skew
    # the headline speedup ratio the tier-1 smoke gates on
    single_clock = profiler.StepClock()
    for _ in range(steps):
        with single_clock.step():
            loss = step(x, y)
            float(loss.item())
    single_t = single_clock.step_ms(50) / 1e3

    # per-microbatch stage costs (fwd / remat-bwd / optimizer): the
    # device-compute yardstick both orchestration fractions measure
    # against. With every virtual device timesharing this host's cores,
    # device compute serializes, so
    #   serial_compute = S*M*(t_fwd + t_bwd) + S*t_opt
    # and whatever remains of a measured step is host-side schedule +
    # dispatch cost. On real chips compute parallelizes but the host
    # cost per step is the same — the fraction is the upper bound on
    # what orchestration steals from an S-way speedup.
    st0 = engine.stages[0]
    micro_x = st0.place_input((x._data[: batch // M],))[0]
    y0, _ = st0.fwd_jit(st0.params, st0.buffers,
                        jax.random.key(0), micro_x)
    reps = 20
    t0 = time.perf_counter()
    for _ in range(reps):
        y0, _ = st0.fwd_jit(st0.params, st0.buffers,
                            jax.random.key(0), micro_x)
    np.asarray(y0).ravel()[:1]
    t_f = (time.perf_counter() - t0) / reps
    one = jnp.ones((), jnp.float32)
    gacc, gx = st0.bwd_jit(st0.params, st0.buffers, jax.random.key(0),
                           micro_x, y0, one, None)
    t0 = time.perf_counter()
    for _ in range(reps):
        gacc, gx = st0.bwd_jit(st0.params, st0.buffers,
                               jax.random.key(0), micro_x, y0, one,
                               None)
    np.asarray(next(iter(
        jax.tree_util.tree_leaves(gacc)))).ravel()[:1]
    t_b = (time.perf_counter() - t0) / reps
    lr_v = jnp.asarray(1e-3, jnp.float32)
    scale_v = jnp.asarray(1.0, jnp.float32)
    no_inf = jnp.asarray(False)
    # _opt_jit donates its grads arg, so each rep needs its own tree —
    # built OUTSIDE the timed loop so the allocation cost doesn't count
    # as optimizer compute (it would bias orchestration_fraction low)
    zgs = [jax.tree_util.tree_map(jnp.zeros_like,
                                  engine.stages[0].params)
           for _ in range(reps)]
    for leaf in jax.tree_util.tree_leaves(zgs[-1]):
        np.asarray(leaf).ravel()[:1]  # materialized before timing
    t0 = time.perf_counter()
    for zg in zgs:
        new_p, new_s = engine._opt_jit(
            engine.stages[0].params, zg, engine.opt_states[0], lr_v,
            scale_v, no_inf)
        engine.stages[0].params, engine.opt_states[0] = new_p, new_s
    np.asarray(next(iter(jax.tree_util.tree_leaves(new_p)))).ravel()[:1]
    t_opt = (time.perf_counter() - t0) / reps
    serial_compute = S * M * (t_f + t_b) + S * t_opt

    ideal = S * M / (M + S - 1)
    ideal_step = (M + S - 1) * (t_f + t_b)

    # -- spmd_1f1b engine: the tentpole. ONE jitted program per step -------
    paddle.seed(0)
    spmd_stages = [make_stage() for _ in range(S)]
    spmd = dist.PipelineParallel(
        spmd_stages, loss_fn, paddle.optimizer.SGD(learning_rate=1e-3),
        num_micro=M, mesh=mesh, exec_mode="spmd_1f1b")
    spmd.train_batch(x, y)            # compile
    float(spmd.train_batch(x, y).item())
    spmd_clock = profiler.StepClock()
    for _ in range(steps):
        with spmd_clock.step():
            loss = spmd.train_batch(x, y)
            float(loss.item())   # device-complete inside the bracket
    spmd_t = spmd_clock.step_ms(50) / 1e3
    compile_count = spmd.compile_count

    out = {
        # headline: the single-dispatch engine
        "spmd_1f1b_rows_per_sec": round(batch / spmd_t, 1),
        "single_chip_rows_per_sec": round(batch / single_t, 1),
        "speedup_vs_single": round(single_t / spmd_t, 3),
        "ideal_speedup": round(ideal, 3),
        "schedule_efficiency": round(ideal_step / spmd_t, 3),
        "orchestration_fraction": round(
            spmd_clock.orchestration_fraction(serial_compute), 4),
        "compile_count": compile_count,
        "dispatches_per_step": spmd.last_dispatch_count,
        "step_ms": round(spmd_t * 1e3, 1),
        "step_ms_p50": round(spmd_clock.step_ms(50), 3),
        "step_ms_p99": round(spmd_clock.step_ms(99), 3),
        # the host-driven dispatch engine it replaces on homogeneous
        # stages (kept measured so the orchestration win stays visible)
        "pipeline_rows_per_sec": round(batch / host_t, 1),
        "host_speedup_vs_single": round(single_t / host_t, 3),
        "host_schedule_efficiency": round(ideal_step / host_t, 3),
        "host_orchestration_fraction": round(
            host_clock.orchestration_fraction(serial_compute), 4),
        "host_dispatches_per_step": host_dispatches,
        "host_step_ms": round(host_t * 1e3, 1),
        "tick_ms_p50": round(host_clock.tick_ms(50), 4),
        "tick_ms_p99": round(host_clock.tick_ms(99), 4),
        # shared yardsticks
        "stage_micro_fwd_ms": round(t_f * 1e3, 3),
        "stage_micro_bwd_ms": round(t_b * 1e3, 3),
        "stage_opt_ms": round(t_opt * 1e3, 3),
        "serial_compute_ms": round(serial_compute * 1e3, 1),
        "stages": S, "num_micro": M, "batch": batch, "width": width,
        "depth_per_stage": depth_per_stage,
        # with host_cores == 1 every virtual device timeshares one
        # core, so NO pipeline form can beat single-chip rows/s here;
        # the transferable receipts are dispatches_per_step,
        # compile_count and the orchestration fractions
        "host_cores": os.cpu_count(),
    }

    if full:
        out.update(_full_legs(mesh, S, M, batch, width,
                              depth_per_stage, steps, rng, x, y,
                              loss_fn, make_stage))
    # ONE code path for the printed report and the exported series:
    # every field becomes a bench.pipeline.* gauge in the metrics
    # runtime, the JSONL record is written from the registry snapshot,
    # and the dict printed below is REBUILT from that same snapshot
    # (PD_OBS_JSONL names the series file; bench.py sets it when
    # collecting BENCH_r* artifacts). Guarded: an exporter failure
    # (unwritable PD_OBS_JSONL path) must not sink measured legs.
    try:
        from paddle_tpu.observability import exporters as obs_exporters
        out = obs_exporters.emit_report(
            out, jsonl_path=os.environ.get("PD_OBS_JSONL"),
            prefix="bench.pipeline")
    except Exception as e:  # pragma: no cover — the artifact survives
        out["obs_export_error"] = f"{type(e).__name__}: {e}"
    print(json.dumps(out))


def _full_legs(mesh, S, M, batch, width, depth_per_stage, steps, rng,
               x, y, loss_fn, make_stage):
    """Round-5 receipt legs (PD_PIPE_BENCH_FULL=1): raw gpipe and raw
    1F1B schedule forms plus the stacked SpmdPipelineParallel engine."""
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    import paddle_tpu.distributed.env as env
    from jax import lax, shard_map
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.distributed.pipeline import (gpipe_schedule,
                                                 one_f_one_b_schedule)

    rngk = np.random.RandomState(1)
    wg_params = {}
    for i in range(depth_per_stage):
        wg_params[f"w{i}"] = jnp.asarray(
            rngk.randn(S, width, width).astype(np.float32) * 0.02)
        wg_params[f"b{i}"] = jnp.zeros((S, width), jnp.float32)
    micro_b = batch // M
    xg = jnp.asarray(rng.randn(M, micro_b, width).astype(np.float32))
    yg = jnp.asarray(rng.randn(M, micro_b, width).astype(np.float32))

    def block_fn(p, xm):
        h = xm
        for i in range(depth_per_stage):
            h = jnp.maximum(h @ p[f"w{i}"] + p[f"b{i}"], 0.0)
        return h

    def spmd_wg(params, x, yy):
        local = {k: v[0] for k, v in params.items()}
        with env.axis_context("pp"):
            out = gpipe_schedule(block_fn, local, x, M, axis="pp")
        return ((out - yy) ** 2).mean()

    loss_g = shard_map(spmd_wg, mesh=mesh,
                       in_specs=(P("pp"), P(), P()), out_specs=P(),
                       check_vma=False)

    @jax.jit
    def wg_step(params, x, yy):
        g = jax.grad(lambda p: loss_g(p, x, yy))(params)
        return jax.tree_util.tree_map(
            lambda p, gg: p - 1e-3 * gg, params, g)

    wg_params = wg_step(wg_params, xg, yg)   # compile
    np.asarray(wg_params["w0"]).ravel()[:1]
    t0 = time.perf_counter()
    for _ in range(steps):
        wg_params = wg_step(wg_params, xg, yg)
    np.asarray(wg_params["w0"]).ravel()[:1]
    wg_t = (time.perf_counter() - t0) / steps

    f1b_params = {k: jnp.array(v) for k, v in wg_params.items()}

    def f1b_spmd(params, x, yy):
        local = {k: v[0] for k, v in params.items()}

        def lg(y, mb):
            t = lax.dynamic_index_in_dim(yy, mb, 0, keepdims=False)
            return jax.value_and_grad(
                lambda o: ((o - t) ** 2).mean())(y)
        with env.axis_context("pp"):
            loss, g = one_f_one_b_schedule(block_fn, lg, local, x, M,
                                           axis="pp")
        loss = lax.psum(loss, "pp") / M
        return loss, {k: v[None] / M for k, v in g.items()}

    f1b = shard_map(f1b_spmd, mesh=mesh,
                    in_specs=(P("pp"), P(), P()),
                    out_specs=(P(), P("pp")), check_vma=False)

    @jax.jit
    def f1b_step(params, x, yy):
        loss, g = f1b(params, x, yy)
        return jax.tree_util.tree_map(
            lambda p, gg: p - 1e-3 * gg, params, g), loss

    f1b_params, _ = f1b_step(f1b_params, xg, yg)   # compile
    np.asarray(f1b_params["w0"]).ravel()[:1]
    t0 = time.perf_counter()
    for _ in range(steps):
        f1b_params, _ = f1b_step(f1b_params, xg, yg)
    np.asarray(f1b_params["w0"]).ravel()[:1]
    f1b_t = (time.perf_counter() - t0) / steps

    paddle.seed(0)
    eng_stages = [make_stage() for _ in range(S)]
    spmd_engine = dist.SpmdPipelineParallel(
        eng_stages, loss_fn,
        paddle.optimizer.SGD(learning_rate=1e-3), num_micro=M,
        mesh=mesh)
    spmd_engine.train_batch(x, y)            # compile
    float(spmd_engine.train_batch(x, y).item())
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = spmd_engine.train_batch(x, y)
    float(loss.item())
    eng_t = (time.perf_counter() - t0) / steps
    return {
        "whole_graph_rows_per_sec": round(batch / wg_t, 1),
        "whole_graph_dispatches_per_step": 1,
        "raw_1f1b_rows_per_sec": round(batch / f1b_t, 1),
        "raw_1f1b_dispatches_per_step": 1,
        "spmd_engine_rows_per_sec": round(batch / eng_t, 1),
        "spmd_engine_dispatches_per_step":
            spmd_engine.last_dispatch_count,
    }


if __name__ == "__main__":
    main()
