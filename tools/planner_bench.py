"""Planner step-time receipt: ONE dp×tp×pp executable vs the composed
wrappers (runnable standalone; tier-1 smoke runs it tiny).

Prints ONE JSON line shaped for perf_ledger ingest — metric
``planner_step_time`` IS the ledger fingerprint. Headline ``value`` is
the planner engine's p50 train-step wall (ms): the whole dp×tp×pp
step — every microbatch forward/backward, grad accumulation, optimizer
update, dp/tp collectives — as ONE jitted program over the MeshPlan's
named mesh with donated state. Alongside it:

  composed_step_ms_p50     the pre-planner composition ceiling: the
                           same model on the manual pp-only spmd mesh
                           (dp/tp axes inexpressible without the plan)
  speedup_vs_composed      composed p50 / planner p50. On a virtual
                           CPU mesh every device timeshares the host's
                           cores, so the 4x wider planner mesh buys no
                           wall-clock — the transferable receipts are
                           the contracts below, and this ratio just
                           has to stay in-family run-to-run
  train_executables        XLA train programs built (contract: 1)
  dispatches_per_step      jit dispatches per train_batch (contract: 1)

Shapes are env-tunable so the tier-1 smoke stays cheap:
PD_PLANNER_BENCH_DEVICES, PD_PLANNER_BENCH_MICRO,
PD_PLANNER_BENCH_WIDTH, PD_PLANNER_BENCH_BATCH,
PD_PLANNER_BENCH_STEPS.

``--calibration`` (PR 18) appends a SECOND receipt line — metric
``planner_step_time_calibrated``, its own ledger fingerprint riding
side-by-side with the measured one — comparing the layout the ANALYTIC
cost model picks against the layout the calibrated table picks for the
bench model, both scored on the calibrated ruler (absolute ms from the
committed tools/cost_calibration.json). The smoke pins that the
calibrated pick is never worse than the analytic pick on that ruler —
true by construction when the table matches (the calibrated pick
minimizes it), so a violation means the table didn't load: a staleness
regression, not a modeling one.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

N_DEV = int(os.environ.get("PD_PLANNER_BENCH_DEVICES", 8))

# the CPU device count must be pinned BEFORE the backend exists
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", N_DEV)

import numpy as np  # noqa: E402


def main():
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.distributed as dist
    from paddle_tpu import profiler
    from paddle_tpu.distributed.sharding import MeshPlan
    from jax.sharding import PartitionSpec as P

    pp = 2
    dp = 2 if N_DEV >= 8 else 1
    tp = 2 if N_DEV >= 4 else 1
    M = int(os.environ.get("PD_PLANNER_BENCH_MICRO", 4))
    width = int(os.environ.get("PD_PLANNER_BENCH_WIDTH", 256))
    batch = int(os.environ.get("PD_PLANNER_BENCH_BATCH", 64))
    steps = int(os.environ.get("PD_PLANNER_BENCH_STEPS", 5))

    class Stage(nn.Layer):
        def __init__(self):
            super().__init__()
            self.lin = nn.Linear(width, width)
            self.lin.weight.sharding_spec = P(None, "tp")
            self.lin.bias.sharding_spec = P("tp")

        def forward(self, xx):
            return paddle.tanh(self.lin(xx))

    def loss_fn(out, y):
        return ((out - y) ** 2).mean()

    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(batch, width).astype(np.float32))
    y = paddle.to_tensor(rng.randn(batch, width).astype(np.float32))

    def measure(use_plan):
        paddle.seed(0)
        stages = [Stage() for _ in range(pp)]
        opt = paddle.optimizer.SGD(learning_rate=1e-3)
        if use_plan:
            plan = MeshPlan(dp=dp, tp=tp, pp=pp)
            eng = dist.PipelineParallel(
                stages, loss_fn, opt, num_micro=M,
                mesh=plan.build_mesh(), exec_mode="spmd_1f1b",
                plan=plan)
        else:
            mesh = dist.build_mesh({"pp": pp},
                                   devices=jax.devices()[:pp])
            eng = dist.PipelineParallel(
                stages, loss_fn, opt, num_micro=M, mesh=mesh,
                exec_mode="spmd_1f1b")
        eng.train_batch(x, y)                  # compile
        float(eng.train_batch(x, y).item())    # warm
        clock = profiler.StepClock()
        for _ in range(steps):
            with clock.step():
                loss = eng.train_batch(x, y)
                float(loss.item())  # device-complete inside bracket
        return clock, eng

    composed_clock, _ = measure(False)
    planner_clock, planner_eng = measure(True)
    planner_p50 = planner_clock.step_ms(50)
    composed_p50 = composed_clock.step_ms(50)

    out = {
        "metric": "planner_step_time",
        "unit": "ms",
        "value": round(planner_p50, 3),
        "platform": "cpu",
        "n_devices": jax.device_count(),
        "extras": {
            "step_ms_p50": round(planner_p50, 3),
            "step_ms_p99": round(planner_clock.step_ms(99), 3),
            "rows_per_sec": round(batch / (planner_p50 / 1e3), 1),
            "composed_step_ms_p50": round(composed_p50, 3),
            "speedup_vs_composed": round(
                composed_p50 / planner_p50, 3),
            "train_executables": planner_eng.compile_count,
            "dispatches_per_step": planner_eng.last_dispatch_count,
            "layout": {"dp": dp, "fsdp": 1, "tp": tp, "pp": pp},
            "num_micro": M, "batch": batch, "width": width,
            "host_cores": os.cpu_count(),
        },
    }
    # one code path for the printed report and the exported series
    # (PD_OBS_JSONL names the series file). Guarded: an exporter
    # failure must not sink measured legs.
    try:
        from paddle_tpu.observability import exporters as obs_exporters
        out = obs_exporters.emit_report(
            out, jsonl_path=os.environ.get("PD_OBS_JSONL"),
            prefix="bench.planner")
    except Exception as e:  # pragma: no cover — the artifact survives
        out["obs_export_error"] = f"{type(e).__name__}: {e}"
    print(json.dumps(out))

    if "--calibration" in sys.argv:
        print(json.dumps(calibration_receipt(width, batch, M)))


def calibration_receipt(width: int, batch: int, num_micro: int):
    """Analytic pick vs calibrated pick for the bench model, BOTH
    scored in absolute calibrated ms — the second ledger line
    --calibration appends."""
    from paddle_tpu.distributed.sharding import (ModelDims,
                                                 choose_layout,
                                                 estimate_layout)
    from paddle_tpu.observability import calibration as cal

    pp_stages = 2
    n_params = pp_stages * (width * width + width)
    dims = ModelDims(n_params=n_params, hidden=width,
                     n_layers=pp_stages, seq=1, batch=batch)
    hbm = float(2 ** 34)  # everything fits: ranking, not feasibility
    calib = cal.load_for(n_devices=jax.device_count())

    analytic_sizes, _ = choose_layout(jax.device_count(), dims, hbm,
                                      num_micro=num_micro)
    calib_sizes, _ = choose_layout(jax.device_count(), dims, hbm,
                                   num_micro=num_micro,
                                   calibration=calib)

    def on_ruler(sizes):
        # score on the calibrated ruler when the table matched,
        # analytic otherwise (then both picks coincide by definition)
        cost = estimate_layout(sizes, dims, hbm, num_micro=num_micro,
                               calibration=calib)
        return cost.calibrated_step_time_s if calib is not None \
            else cost.analytic_step_time_s

    analytic_pick_s = on_ruler(analytic_sizes)
    calib_pick_s = on_ruler(calib_sizes)
    out = {
        "metric": "planner_step_time_calibrated",
        "unit": "ms",
        "value": round(calib_pick_s * 1e3, 6),
        "platform": "cpu",
        "n_devices": jax.device_count(),
        "extras": {
            "analytic_pick": dict(analytic_sizes),
            "calibrated_pick": dict(calib_sizes),
            "analytic_pick_ms": round(analytic_pick_s * 1e3, 6),
            "calibrated_pick_ms": round(calib_pick_s * 1e3, 6),
            "calibration": {
                "match": 1 if calib is not None else 0,
                "n_devices": calib.n_devices if calib else -1,
            },
            "model_params": dims.n_params,
        },
    }
    try:
        from paddle_tpu.observability import exporters as obs_exporters
        out = obs_exporters.emit_report(
            out, jsonl_path=os.environ.get("PD_OBS_JSONL"),
            prefix="bench.planner_calibrated")
    except Exception as e:  # pragma: no cover
        out["obs_export_error"] = f"{type(e).__name__}: {e}"
    return out


if __name__ == "__main__":
    main()
