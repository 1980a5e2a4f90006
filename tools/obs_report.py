#!/usr/bin/env python
"""obs_report: pod telemetry rollup CLI (the operator surface of
paddle_tpu.observability).

Modes:
  --demo      stand up a 2-stage CPU mesh (virtual devices), train the
              spmd_1f1b pipeline engine for a few steps with the full
              telemetry stack on — per-op dispatch counters, collective
              bytes, step_ms percentiles, examples/sec + MFU from the
              lowered executable's cost_analysis FLOPs, recompile
              sentinel — then write the Prometheus text dump + JSONL
              series and print ONE JSON summary line. This is the
              zero-to-telemetry receipt the acceptance gate reads.
  --force-recompile   (with --demo) after the steady steps, feed one
              batch with a CHANGED shape: the sentinel must flip
              train_recompiles_total to exactly 1 and log the shape
              delta (printed in the summary as recompile_diff).
  --doctor DIR   forensics bridge: hand the flight-recorder dumps in
              DIR to tools/tpu_doctor.py and print its diagnosis
              (diverging rank + last mismatched collective seq,
              stragglers, recompile storms, goodput breakdown).
  --anatomy   step-anatomy bridge: build the CPU-smoke ERNIE TrainStep
              (tools/step_anatomy.py's config, PD_ANATOMY_* tunable),
              attribute its ONE executable by scope
              (observability.anatomy), publish anatomy.* gauges, and
              print the share table as ONE JSON line — the
              zero-to-attribution receipt (scope shares sum to ~1.0,
              sentinel stays at zero).
  --memory    memory-anatomy bridge (the HBM twin of --anatomy): build
              the CPU-smoke ERNIE TrainStep, attribute its ONE
              executable's buffer assignment by scope
              (observability.memory — temp-byte shares sum to ~1.0,
              peak-live-bytes reported), publish memory.* gauges +
              the live occupancy sample (device memory_stats or
              host RSS), and print ONE JSON line — the
              zero-to-memory-anatomy receipt (sentinel stays at zero:
              attribution never touches the train executable).
  --serving   request-anatomy bridge (the serving twin of --anatomy):
              stand up a tiny ServingFleet with metrics + request
              tracing on, replay a deterministic open-loop trace, and
              print ONE JSON line carrying the engine/fleet gauges
              (per-class queue depth, SLO burn rates), the
              explain_tail attribution (per-request components sum to
              ~1.0, dominant named) and the serving breach verdict —
              the zero-to-request-anatomy receipt. Shapes env-tunable
              (PD_SRV_REQUESTS/REPLICAS/RATE/HIDDEN/LAYERS).
  --plan-audit   cost-model truth-plane bridge (PR 18): build the
              standard planner leg (2-stage model under a dp×tp×pp
              MeshPlan), run sentinel-guarded live steps, join the
              measured planes onto the plan's PlanReceipt — step clock
              p50 vs predicted step time, buffer-assignment peak vs
              predicted HBM, compiled-HLO collective bytes + comm
              counter delta vs predicted wire — publish the always-on
              planner.prediction_error{metric=} gauges onto the pulse
              rings, and print ONE JSON line with the error-shares
              table, the worst-mispredicted component, and the
              planner_prediction_error ledger receipt.
  --pulse     fleet-pulse receipt (the live-telemetry acceptance
              surface): arm the time-series sampler + the localhost
              pulse server over a RUNNING ServingFleet leg, scrape
              /metrics MID-RUN (must parse as valid Prometheus text),
              prove post-run scrape parity (the HTTP body is byte-
              identical to to_prometheus(metrics.snapshot()) modulo
              the scrape's own odometer), check /healthz + /series
              ring contents, and render the committed perf ledger's
              cross-run trend (≥5 rounds). Shapes via PD_SRV_*.
  default     aggregate + export whatever the current process's
              registry holds (for embedding in training scripts).

Outputs: --prom PATH (Prometheus text), --jsonl PATH (time series),
--trace PATH (chrome trace with metric marks). Shapes are env-tunable
(PD_OBS_DEMO_WIDTH/DEPTH/BATCH/MICRO/STEPS) so the tier-1 smoke runs
tiny.

Reference mapping (DESIGN.md "Observability"): the Prometheus dump is
monitor.h's ExportedStatValue surface; the chrome trace merge is
tools/timeline.py; the JSONL series is the profiler report as a time
series instead of a one-shot sorted table.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

N_DEV = int(os.environ.get("PD_OBS_DEMO_DEVICES", 2))

jax = None  # bound by _jax_setup()
np = None


def _jax_setup():
    """Pin virtual CPU devices and import jax — lazily, so the
    --doctor forensics path (and a bare module import) stays
    stdlib-only: the runbook runs it on a triage host where jax may be
    wedged, broken, or absent."""
    global jax, np
    if jax is not None:
        return
    # virtual CPU devices must be pinned before the backend exists
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + f" --xla_force_host_platform_device_count={N_DEV}"
        ).strip()
    import jax as _jax
    _jax.config.update("jax_platforms", "cpu")
    _jax.config.update("jax_num_cpu_devices", N_DEV)
    import numpy as _np
    jax, np = _jax, _np


def run_demo(args):
    _jax_setup()
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    import paddle_tpu.nn as nn
    from paddle_tpu import profiler
    from paddle_tpu.observability import (exporters, fleet, metrics,
                                          mfu)

    S = N_DEV
    M = int(os.environ.get("PD_OBS_DEMO_MICRO", 4))
    width = int(os.environ.get("PD_OBS_DEMO_WIDTH", 256))
    depth = int(os.environ.get("PD_OBS_DEMO_DEPTH", 2))
    batch = int(os.environ.get("PD_OBS_DEMO_BATCH", 32))
    steps = int(os.environ.get("PD_OBS_DEMO_STEPS", 4))

    metrics.enable()

    def make_stage():
        layers = []
        for _ in range(depth):
            layers += [nn.Linear(width, width), nn.ReLU()]
        return nn.Sequential(*layers)

    def loss_fn(out, y):
        return ((out - y) ** 2).mean()

    rng = np.random.RandomState(0)
    # eager preprocessing on purpose: exercises the per-op dispatch
    # counters the acceptance gate looks for
    x = paddle.to_tensor(rng.randn(batch, width).astype(np.float32))
    x = x / paddle.to_tensor(np.float32(2.0)) * paddle.to_tensor(
        np.float32(2.0))
    y = paddle.to_tensor(rng.randn(batch, width).astype(np.float32))
    # a host-side collective (world-size-1 identity here, pod-real on a
    # multi-host launch): collective.calls/bytes must be non-zero
    dist.all_reduce(paddle.to_tensor(np.ones((8, 8), np.float32)))

    paddle.seed(0)
    mesh = dist.build_mesh({"pp": S}, devices=jax.devices()[:S])
    engine = dist.PipelineParallel(
        [make_stage() for _ in range(S)], loss_fn,
        paddle.optimizer.SGD(learning_rate=1e-3), num_micro=M,
        mesh=mesh, exec_mode="spmd_1f1b")

    engine.train_batch(x, y)  # compile step (sentinel baselines here)
    flops = engine.train_flops_per_step(x, y)
    meter = mfu.ThroughputMeter(examples_per_step=batch,
                                flops_per_step=flops,
                                n_devices=S)
    clock = profiler.StepClock()
    for _ in range(steps):
        t0 = time.perf_counter()
        with clock.step():
            loss = engine.train_batch(x, y)
            float(loss.item())  # device-complete inside the bracket
        meter.step(time.perf_counter() - t0)
    thr = meter.report()
    clock.publish("train")

    merged = fleet.aggregate()

    # exports are written from the STEADY-shape run (the contract dump:
    # train_recompiles_total must read 0 here); the forced-recompile
    # leg runs after, so one process proves both acceptance legs
    outdir = args.out
    os.makedirs(outdir, exist_ok=True)
    prom_path = args.prom or os.path.join(outdir, "metrics.prom")
    jsonl_path = args.jsonl or os.path.join(outdir, "metrics.jsonl")
    exporters.write_prometheus(prom_path)
    rec = exporters.JsonlExporter(jsonl_path).write(
        step=steps, extra={"phase": "demo"})
    trace_path = args.trace or os.path.join(outdir, "trace.json")
    profiler.export_chrome_tracing(trace_path)

    snap = metrics.snapshot()
    steady_recompiles = snap.get("train_recompiles_total",
                                 {"value": 0})["value"]

    recompile_diff = None
    recompiles = steady_recompiles
    if args.force_recompile:
        # half-batch: a changed leading dim — the sentinel must fire
        # ONCE with the shape delta, not silently retrace
        xs = paddle.to_tensor(
            rng.randn(batch // 2, width).astype(np.float32))
        ys = paddle.to_tensor(
            rng.randn(batch // 2, width).astype(np.float32))
        engine.train_batch(xs, ys)
        ev = engine.recompile_sentinel.events
        recompile_diff = ev[-1]["diff"] if ev else None
        recompiles = metrics.snapshot()["train_recompiles_total"]["value"]
    summary = {
        "ok": True,
        "stages": S, "num_micro": M, "batch": batch, "steps": steps,
        "examples_per_sec": thr["examples_per_sec"],
        "mfu": thr["mfu"],
        "model_flops_per_step": flops,
        "step_ms_p50": snap["pipeline.step_ms"].get("p50", -1.0),
        "step_ms_p99": snap["pipeline.step_ms"].get("p99", -1.0),
        "op_dispatch_counts": {
            k: v["value"] for k, v in snap.items()
            if k.startswith("op.dispatch.total")},
        "collective_bytes": {
            k: v["value"] for k, v in snap.items()
            if k.startswith("collective.bytes")},
        "train_recompiles_total": recompiles,
        "steady_recompiles_total": steady_recompiles,
        "recompile_diff": recompile_diff,
        "fleet_host_count": merged["fleet.host_count"]["value"],
        "prometheus": prom_path, "jsonl": jsonl_path,
        "trace": trace_path,
        "jsonl_metric_keys": len(rec["metrics"]),
    }
    # self-check the acceptance surface so a drive-by refactor that
    # un-wires a layer fails loudly here, not in a dashboard later
    problems = []
    if not summary["op_dispatch_counts"]:
        problems.append("no per-op dispatch counters")
    if not any(v > 0 for v in summary["collective_bytes"].values()):
        problems.append("no collective bytes")
    if summary["step_ms_p50"] <= 0:
        problems.append("no step_ms percentiles")
    if summary["examples_per_sec"] <= 0:
        problems.append("no examples/sec")
    if steady_recompiles != 0:
        problems.append(f"train_recompiles_total={steady_recompiles} "
                        "on a steady-shape run")
    if args.force_recompile and (recompiles != 1 or not recompile_diff):
        problems.append(
            f"sentinel: expected exactly 1 logged recompile, got "
            f"{recompiles} (diff={recompile_diff!r})")
    if problems:
        summary["ok"] = False
        summary["problems"] = problems
    print(json.dumps(summary))
    return 0 if summary["ok"] else 1


def run_anatomy(args):
    """Step-anatomy bridge: one process, one tiny ERNIE TrainStep, the
    per-scope share table of its single executable. Self-checks the
    acceptance surface (shares sum to 1, the head scope exists, zero
    recompiles) so a drive-by refactor that drops scope annotations
    fails loudly here."""
    # lighter setup than _jax_setup: anatomy needs ONE device, not a
    # pinned mesh — and must also run in-process next to an
    # already-initialized jax (the tier-1 smoke), where re-pinning
    # device counts would fight the live backend
    global jax, np
    if jax is None:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        import jax as _jax
        import numpy as _np
        jax, np = _jax, _np
    from paddle_tpu.observability import anatomy, exporters
    from tools.step_anatomy import build_step

    step, ids, lbl, shape = build_step(False)
    float(step(ids, lbl).item())  # compile (sentinel baselines here)
    float(step(ids, lbl).item())  # steady step: sentinel must stay 0
    res = anatomy.train_step_anatomy(step, (ids,), (lbl,),
                                     publish_gauges=True)
    if args.prom:
        exporters.write_prometheus(args.prom)
    if args.jsonl:
        exporters.JsonlExporter(args.jsonl).write(extra={
            "phase": "anatomy"})
    shares = {k: round(v["share"], 4) for k, v in res["scopes"].items()}
    summary = {
        "ok": True,
        "shape": shape,
        "scope_shares": shares,
        "share_sum": round(sum(shares.values()), 4),
        "unattributed_share": round(res["unattributed_share"], 4),
        "total_flops": res["total_flops"],
        "cost_analysis_flops": res["cost_analysis_flops"],
        "train_recompiles": step.recompile_sentinel.fired,
        "train_executables": int(step._step_fn._cache_size()),
        "prometheus": args.prom, "jsonl": args.jsonl,
    }
    problems = []
    if abs(summary["share_sum"] - 1.0) > 0.02:
        problems.append(f"shares sum to {summary['share_sum']}, not 1")
    if "mlm_head_ce" not in shares:
        problems.append("no mlm_head_ce scope in the lowered step")
    if summary["train_recompiles"] != 0 or \
            summary["train_executables"] != 1:
        problems.append(
            f"scope annotation must be metadata-only: "
            f"{summary['train_recompiles']} recompiles, "
            f"{summary['train_executables']} executables (want 0/1)")
    if problems:
        summary["ok"] = False
        summary["problems"] = problems
    print(json.dumps(summary))
    return 0 if summary["ok"] else 1


def run_memory(args):
    """Memory-anatomy bridge: one tiny ERNIE TrainStep, the per-scope
    byte share table of its single executable + the live occupancy
    sample. Self-checks the acceptance surface (shares sum to 1,
    unattributed bounded, peak > arguments > 0, zero recompiles) so a
    drive-by refactor that breaks the buffer attribution fails loudly
    here."""
    global jax, np
    if jax is None:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        import jax as _jax
        import numpy as _np
        jax, np = _jax, _np
    from paddle_tpu.observability import exporters, memory, metrics
    from tools.step_anatomy import build_step

    metrics.enable()
    step, ids, lbl, shape = build_step(False)
    float(step(ids, lbl).item())  # compile (sentinel baselines here)
    res = memory.train_step_memory(step, (ids,), (lbl,),
                                   publish_gauges=True)
    live = memory.sample()
    if args.prom:
        exporters.write_prometheus(args.prom)
    if args.jsonl:
        exporters.JsonlExporter(args.jsonl).write(extra={
            "phase": "memory"})
    shares = {k: round(v["share"], 4) for k, v in res["scopes"].items()}
    ma = res["memory"]
    summary = {
        "ok": True,
        "shape": shape,
        "temp_shares": shares,
        "share_sum": round(sum(shares.values()), 4),
        "unattributed_share": round(res["unattributed_share"], 4),
        "peak_bytes": ma["peak_bytes"],
        "argument_bytes": ma["argument_bytes"],
        "temp_bytes": ma["temp_bytes"],
        "peak_is_exact": ma["peak_is_exact"],
        "host_rss_bytes": (live or {}).get("host_rss_bytes"),
        "devices_reporting": len((live or {}).get("devices", [])),
        "train_recompiles": step.recompile_sentinel.fired,
        "train_executables": int(step._step_fn._cache_size()),
        "prometheus": args.prom, "jsonl": args.jsonl,
    }
    problems = []
    if abs(summary["share_sum"] - 1.0) > 0.02:
        problems.append(f"shares sum to {summary['share_sum']}, not 1")
    if summary["unattributed_share"] >= 0.25:
        problems.append(
            f"unattributed {summary['unattributed_share']} >= 0.25 — "
            "scope metadata is not reaching the buffer attribution")
    if not (summary["peak_bytes"] >= summary["argument_bytes"] > 0):
        problems.append("peak/argument bytes not positive-ordered")
    if not summary["host_rss_bytes"]:
        problems.append("no live-tier sample (host RSS missing)")
    if summary["train_recompiles"] != 0 or \
            summary["train_executables"] != 1:
        problems.append(
            f"attribution must never touch the train executable: "
            f"{summary['train_recompiles']} recompiles, "
            f"{summary['train_executables']} executables (want 0/1)")
    if problems:
        summary["ok"] = False
        summary["problems"] = problems
    print(json.dumps(summary))
    return 0 if summary["ok"] else 1


def run_serving(args):
    """Request-anatomy bridge: one tiny fleet, one deterministic
    trace, the per-request attribution + burn gauges + breach verdict
    as one receipt line. Self-checks the acceptance surface (every
    cohort request's components sum to 1.0 ± 0.02, the burn-rate and
    per-class queue-depth gauges exist, zero recompiles) so a drive-by
    refactor that un-wires a serving span site fails loudly here."""
    global jax, np
    if jax is None:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        import jax as _jax
        import numpy as _np
        jax, np = _jax, _np
    import paddle_tpu as paddle
    from paddle_tpu import profiler
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.observability import exporters, metrics, reqtrace
    from paddle_tpu.serving import (FleetConfig, ServingConfig,
                                    ServingFleet)
    from paddle_tpu.serving.loadgen import replay_fleet, synthetic_trace
    from tools.tpu_doctor import serving_breach_verdict

    n_req = int(os.environ.get("PD_SRV_REQUESTS", 8))
    replicas = int(os.environ.get("PD_SRV_REPLICAS", 2))
    rate = float(os.environ.get("PD_SRV_RATE", 300.0))
    hidden = int(os.environ.get("PD_SRV_HIDDEN", 32))
    layers = int(os.environ.get("PD_SRV_LAYERS", 2))

    metrics.enable()
    reqtrace.enable()
    reqtrace.reset()
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=97, hidden_size=hidden, num_layers=layers,
        num_heads=4, max_seq_len=64, dropout=0.0,
        use_flash_attention=False))
    model.eval()
    cfg = ServingConfig(max_slots=4, max_admit=2, block_size=4,
                        n_blocks=48, prefill_buckets=(24,),
                        max_total_tokens=24, decode_chunk=2,
                        dtype=None)
    fleet = ServingFleet(model, cfg, fleet=FleetConfig(
        replicas=replicas, min_replicas=1, max_replicas=replicas,
        autoscale=False))
    trace = synthetic_trace(
        n_req, vocab_size=97, seed=0, rate_rps=rate,
        prompt_len_choices=(2, 4, 6, 9),
        new_token_choices=(3, 4, 6),
        class_mix={"interactive": 0.75, "batch": 0.25})
    stats, _finished, _shed = replay_fleet(fleet, trace)
    tail = reqtrace.explain_tail()
    summ = stats["fleet"]
    verdict = serving_breach_verdict(tail, episodes=summ["episodes"],
                                     summary=summ)

    snap = metrics.snapshot()
    if args.prom:
        exporters.write_prometheus(args.prom)
    if args.jsonl:
        exporters.JsonlExporter(args.jsonl).write(
            extra={"phase": "serving"})
    trace_path = args.trace
    if trace_path:
        profiler.export_chrome_tracing(trace_path)  # request lanes
    reqtrace.disable()

    burn_gauges = {k: v["value"] for k, v in snap.items()
                   if k.startswith("serving.slo.burn_rate")}
    cls_depth = {k: v["value"] for k, v in snap.items()
                 if k.startswith("serving.fleet.queue_depth{")}
    summary = {
        "ok": True,
        "requests": stats.get("requests", 0),
        "replicas": replicas,
        "sustained_tokens_per_sec":
            stats.get("sustained_tokens_per_sec", 0.0),
        "ttft_ms": stats.get("ttft_ms"),
        "tail_attribution": tail,
        "breach_verdict": verdict,
        "slo_burn_gauges": burn_gauges,
        "queue_depth_by_class": cls_depth,
        "slo_burn": summ.get("slo_burn"),
        "recompile_events": summ["recompile_events"],
        "episodes": summ["episodes"],
        "prometheus": args.prom, "jsonl": args.jsonl,
        "trace": trace_path,
    }
    problems = []
    if stats.get("requests", 0) != n_req:
        problems.append(
            f"finished {stats.get('requests', 0)}/{n_req} requests")
    bad_sums = [c["rid"] for c in tail["cohort"]
                if abs(c["share_sum"] - 1.0) > 0.02]
    if not tail["cohort"]:
        problems.append("empty tail cohort (no request timelines)")
    if bad_sums:
        problems.append(f"attribution shares off 1.0 for {bad_sums}")
    if not all(c["dominant"] for c in tail["cohort"]):
        problems.append("cohort request without a dominant component")
    if not burn_gauges:
        problems.append("no serving.slo.burn_rate{window=} gauges")
    if not cls_depth:
        problems.append("no serving.fleet.queue_depth{cls=} gauges")
    if summ["recompile_events"] != 0:
        problems.append(
            f"{summ['recompile_events']} recompiles on a steady fleet")
    if problems:
        summary["ok"] = False
        summary["problems"] = problems
    print(json.dumps(summary))
    return 0 if summary["ok"] else 1


def run_pulse(args):
    """Fleet-pulse receipt: arm the time-series sampler and the live
    localhost /metrics endpoint over a RUNNING ServingFleet leg, then
    self-check the acceptance surface — a mid-run HTTP scrape parses
    as valid Prometheus text, the post-run scrape is BYTE-IDENTICAL to
    ``to_prometheus(metrics.snapshot())`` (one renderer: the pull and
    the file export cannot drift), /healthz answers ok, /series
    returns ring contents for a serving gauge, and the committed perf
    ledger renders a multi-round trend."""
    global jax, np
    if jax is None:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        import jax as _jax
        import numpy as _np
        jax, np = _jax, _np

    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.observability import (exporters, metrics,
                                          pulse_server, timeseries)
    from paddle_tpu.serving import (FleetConfig, ServingConfig,
                                    ServingFleet)
    from paddle_tpu.serving.loadgen import replay_fleet, synthetic_trace

    n_req = int(os.environ.get("PD_SRV_REQUESTS", 8))
    replicas = int(os.environ.get("PD_SRV_REPLICAS", 2))
    rate = float(os.environ.get("PD_SRV_RATE", 300.0))
    hidden = int(os.environ.get("PD_SRV_HIDDEN", 32))
    layers = int(os.environ.get("PD_SRV_LAYERS", 2))

    metrics.enable()
    timeseries.reset()
    # tick-driven cadence: the fleet samples at every _publish, the
    # throttle keeps it at ~20 Hz
    timeseries.enable(cadence_s=0.05, thread=False)
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=97, hidden_size=hidden, num_layers=layers,
        num_heads=4, max_seq_len=64, dropout=0.0,
        use_flash_attention=False))
    model.eval()
    cfg = ServingConfig(max_slots=4, max_admit=2, block_size=4,
                        n_blocks=48, prefill_buckets=(24,),
                        max_total_tokens=24, decode_chunk=2,
                        dtype=None)
    fleet = ServingFleet(model, cfg, fleet=FleetConfig(
        replicas=replicas, min_replicas=1, max_replicas=replicas,
        autoscale=False))
    trace = synthetic_trace(
        n_req, vocab_size=97, seed=0, rate_rps=rate,
        prompt_len_choices=(2, 4, 6, 9), new_token_choices=(3, 4, 6))

    srv = pulse_server.PulseServer(port=0).start()
    mid_scrapes = []

    # non-200 must land in the receipt's problems list, never a
    # traceback (urllib RAISES on 4xx/5xx — a stalled-verdict 503 or
    # an unsampled-series 404 is a finding, not a crash)
    def get(path: str):
        return get_status(srv, path)

    def on_tick(tick, _fleet):
        # the LIVE half of the receipt: scrape while the leg runs. A
        # malformed body is a FINDING (lines=-1 fails the self-check
        # below), never a crash that eats the receipt
        if tick in (3, 9):
            code, body = get("/metrics")
            try:
                lines = (exporters.validate_exposition(body)
                         if code == 200 else -1)
            except ValueError:
                lines = -1
            mid_scrapes.append((tick, code, lines))

    problems = []
    try:
        stats, _finished, _shed = replay_fleet(fleet, trace,
                                               on_tick=on_tick)
        timeseries.sample(force=True)   # final post-drain point

        # scrape-vs-export parity: the run is drained, nothing
        # mutates the registry between the pull and the snapshot
        _code, scrape_body = get("/metrics")
        local_body = exporters.to_prometheus(metrics.snapshot())
        # the scrape itself bumped pulse.scrapes_total — compare
        # modulo that one self-counting line
        drop = lambda t: "\n".join(
            l for l in t.splitlines()
            if "pulse_scrapes_total" not in l)
        parity = drop(scrape_body) == drop(local_body)
        scrape_lines = exporters.validate_exposition(scrape_body)

        hcode, hbody = get("/healthz")
        health = json.loads(hbody)
        scode, sbody = get("/snapshot")
        snap_doc = json.loads(sbody) if scode == 200 else {}

        series_key = "serving.fleet.queue_depth"
        qcode, qbody = get(f"/series?key={series_key}&window=600")
        series_doc = json.loads(qbody) if qcode == 200 else {}
        n_points = len(series_doc.get("points", []))
        bad_code, _ = get(f"/series?key=no.such.key")
    finally:
        srv.stop()
        timeseries.disable()
        metrics.disable()

    # trend leg: the committed cross-run ledger must render history
    ledger_path = os.environ.get(
        "PD_PERF_LEDGER",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "perf_ledger.jsonl"))
    from paddle_tpu.analysis import perf_ledger as pl
    records = pl.load_ledger(ledger_path)
    groups = pl.trend(records)
    trend_rounds = max((len(g["runs"]) for g in groups.values()),
                      default=0)

    summary = {
        "ok": True,
        "requests": stats.get("requests", 0),
        "mid_run_scrapes": [{"tick": t, "status": c, "lines": n}
                            for t, c, n in mid_scrapes],
        "scrape_parity": parity,
        "scrape_lines": scrape_lines,
        "healthz": {"status": hcode,
                    "verdict": health.get("verdict")},
        "snapshot_metrics": len(snap_doc.get("metrics", {})),
        "series_key": series_key,
        "series_points": n_points,
        "unknown_series_status": bad_code,
        "pulse_samples": (health.get("pulse") or {}).get("samples"),
        "ledger_records": len(records),
        "trend_rounds": trend_rounds,
    }
    if stats.get("requests", 0) != n_req:
        problems.append(
            f"finished {stats.get('requests', 0)}/{n_req} requests")
    if not mid_scrapes:
        problems.append("no mid-run scrape happened (leg too short?)")
    if any(c != 200 or n <= 0 for _, c, n in mid_scrapes):
        problems.append(f"mid-run scrape failed: {mid_scrapes}")
    if not parity:
        problems.append("/metrics body != to_prometheus(snapshot()) — "
                        "the one-renderer contract broke")
    if hcode != 200 or health.get("verdict") != "ok":
        problems.append(f"healthz {hcode}: {health.get('verdict')}")
    if not (health.get("pulse") or {}).get("samples"):
        problems.append("sampler recorded zero samples during the leg")
    if n_points < 2:
        problems.append(f"series {series_key}: {n_points} point(s) — "
                        "the per-tick sampling is not reaching rings")
    if bad_code != 404:
        problems.append(f"unknown series key returned {bad_code}")
    if trend_rounds < 5:
        problems.append(f"trend renders {trend_rounds} rounds (<5) "
                        f"from {ledger_path}")
    if problems:
        summary["ok"] = False
        summary["problems"] = problems
    print(json.dumps(summary))
    return 0 if summary["ok"] else 1


def run_plan_audit(args):
    """Plan-audit bridge (PR 18): zero-to-receipt drive of the
    cost-model truth plane. Builds the standard planner leg (2-stage
    model under a dp×tp×pp MeshPlan), runs live sentinel-guarded
    steps, joins the measured planes onto the plan's PlanReceipt —
    step time from the step clock, HBM peak from the memory plane's
    buffer assignment, wire bytes from the compiled HLO's collective
    inventory (compiler-placed collectives never reach the comm
    counters) plus the comm counter delta over the live steps — and
    publishes the always-on planner.prediction_error{metric=} gauges,
    the error-shares table naming the worst-mispredicted component,
    and the planner_prediction_error ledger receipt. Self-checks: all
    three planes joined, shares sum to 1, gauges landed on the pulse
    rings, zero recompiles, calibrated prediction used whenever the
    committed table matches this topology."""
    global jax, np, N_DEV
    if jax is None and "PD_OBS_DEMO_DEVICES" not in os.environ:
        N_DEV = 8   # the dp2×tp2×pp2 planner leg wants a full mesh
    _jax_setup()
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    import paddle_tpu.nn as nn
    from jax.sharding import PartitionSpec as P
    from paddle_tpu import profiler
    from paddle_tpu.distributed.sharding import MeshPlan, ModelDims
    from paddle_tpu.observability import (calibration as cal,
                                          exporters, memory as mem,
                                          metrics, timeseries)

    n = jax.device_count()
    dp = 2 if n >= 8 else 1
    tp = 2 if n >= 4 else 1
    pp = min(2, n)
    M = int(os.environ.get("PD_OBS_DEMO_MICRO", 2))
    width = int(os.environ.get("PD_OBS_DEMO_WIDTH", 32))
    batch = int(os.environ.get("PD_OBS_DEMO_BATCH", 16))
    steps = int(os.environ.get("PD_OBS_DEMO_STEPS", 3))

    metrics.enable()
    timeseries.reset()
    timeseries.enable(cadence_s=0.05, thread=False)

    class _Stage(nn.Layer):
        def __init__(self):
            super().__init__()
            self.lin = nn.Linear(width, width)
            self.lin.weight.sharding_spec = P(None, "tp")
            self.lin.bias.sharding_spec = P("tp")

        def forward(self, xx):
            return paddle.tanh(self.lin(xx))

    paddle.seed(0)
    plan = MeshPlan(dp=dp, tp=tp, pp=pp)
    eng = dist.PipelineParallel(
        [_Stage() for _ in range(2)],
        lambda o, y: ((o - y) ** 2).mean(),
        paddle.optimizer.SGD(learning_rate=1e-3),
        num_micro=M, mesh=plan.build_mesh(),
        exec_mode="spmd_1f1b", plan=plan)
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(batch, width).astype(np.float32))
    y = paddle.to_tensor(rng.randn(batch, width).astype(np.float32))

    eng.train_batch(x, y)   # compile (sentinel baselines here)
    counters_before = _wire_counter_total(metrics.snapshot())
    clock = profiler.StepClock()
    for _ in range(steps):
        with clock.step():
            loss = eng.train_batch(x, y)
            float(loss.item())   # device-complete inside the bracket
    counter_wire = _wire_counter_total(metrics.snapshot()) \
        - counters_before

    # the prediction: the plan's own receipt, re-scored against the
    # committed calibration table (SGD: no moment slots; the 2-layer
    # stack is 2 "layers" of width² — same dims memory_anatomy uses)
    dims = ModelDims(n_params=2 * (width * width + width),
                     hidden=width, n_layers=2, seq=1, batch=batch,
                     opt_slots=0)
    receipt = plan.predict(dims, num_micro=M, calibration="auto")

    # the measured planes. HBM: buffer-assignment peak of the SAME
    # lowered executable. Wire: compiled-HLO collective inventory
    # (per-shard shapes ≈ per-chip bytes) + the comm counter delta —
    # the two sides see disjoint traffic (compiler-placed vs explicit)
    lowered = eng.aot_lower_train(x, y)
    mem_res = mem.program_memory("plan_audit", lowered)
    hlo_wire = cal.compiled_collective_bytes(lowered=lowered)
    measured = {
        "step_time_s": clock.step_ms(50) / 1e3,
        "hbm_bytes": float(mem_res["memory"]["peak_bytes"]),
        "wire_bytes": hlo_wire["total_bytes"] + counter_wire,
    }
    report = cal.audit_report(receipt, measured,
                              platform="cpu", n_devices=n,
                              jsonl_path=args.jsonl)
    timeseries.sample(force=True)
    ring_keys = timeseries.keys(prefix="planner.prediction_error")
    ring_points = sum(
        len(timeseries.series(k)) for k in ring_keys)
    if args.prom:
        exporters.write_prometheus(args.prom)
    timeseries.disable()
    metrics.disable()

    extras = report.get("extras", {})
    errors = extras.get("prediction_error", {})
    shares = extras.get("error_share", {})
    table = cal.load_table()
    table_matches = bool(
        table and cal.Calibration(table).matches("cpu", n))
    summary = {
        "ok": True,
        "layout": dict(plan.sizes),
        "audit": report,
        "predicted": extras.get("predicted"),
        "measured": extras.get("measured"),
        "prediction_error": errors,
        "error_share": shares,
        "worst": extras.get("worst"),
        "used": receipt.used,
        "calibration_match": receipt.calibration_match,
        "hlo_collective_calls": hlo_wire["calls"],
        "counter_wire_bytes": counter_wire,
        "pulse_ring_keys": ring_keys,
        "pulse_ring_points": ring_points,
        "train_executables": eng.compile_count,
        "train_recompiles": eng.recompile_sentinel.fired,
        "prometheus": args.prom, "jsonl": args.jsonl,
    }
    problems = []
    if report.get("value") != 3 or len(errors) != 3:
        problems.append(
            f"joined {report.get('value')}/3 planes "
            f"(errors: {sorted(errors)}) — a dropped join hides "
            "future drift")
    if shares and abs(sum(shares.values()) - 1.0) > 0.02 \
            and sum(errors.values()) > 0:
        problems.append(f"error shares sum to {sum(shares.values())}")
    if errors and not extras.get("worst"):
        problems.append("no worst-mispredicted component named")
    if ring_points < 1:
        problems.append("planner.prediction_error gauges never "
                        "reached the pulse rings")
    if eng.recompile_sentinel.fired != 0 or eng.compile_count != 1:
        problems.append(
            f"audit must never touch the train executable: "
            f"{eng.recompile_sentinel.fired} recompiles, "
            f"{eng.compile_count} executables (want 0/1)")
    if table_matches and receipt.used != "calibrated":
        problems.append(
            "committed calibration table matches this topology but "
            "the prediction ran analytic — load_for is broken")
    if problems:
        summary["ok"] = False
        summary["problems"] = problems
    print(json.dumps(summary))
    return 0 if summary["ok"] else 1


def run_decisions(args):
    """Decision-ledger bridge: the zero-to-receipt drive of the
    control plane. Runs a canned incident end-to-end IN PROCESS — a
    crash evicted under allow_shrink, a budget-deferred then granted
    grow, a p99-breach scale_up, a shed, a hot swap, a certified
    rollback walk, an 8-chip layout pick — pushing the post-decision
    observations each actor would publish, so every record JOINS a
    measured outcome. Then cashes all three ledger contracts: replay
    (tools/incident_replay re-derives every action bit-identically
    from the dumped evidence), timeline (tools/ops_timeline merges
    decisions + flight events chronologically), and export (the
    always-on decision.total / decision.outcome series land in the
    Prometheus text dump). Prints ONE JSON line; ok=false on any gap."""
    import socket as _socket  # noqa: F401  (parity with other modes)
    from paddle_tpu.distributed import checkpoint as ckpt
    from paddle_tpu.distributed import elastic, sharding
    from paddle_tpu.observability import (decisions as dec, exporters,
                                          flight_recorder as fr,
                                          metrics)
    from tools import incident_replay, ops_timeline

    outdir = args.out
    os.makedirs(outdir, exist_ok=True)
    dec.reset()
    fr.enable()
    metrics.enable()

    class _SLO:
        p99_ttft_ms, queue_high, queue_low = 500.0, 4, 1

    # 1) remediate: doctor-confirmed crash -> evict_shrink; the
    #    healthy poll 6 s later is the joiner's proof it healed
    pol = elastic.SupervisorPolicy(world=4, allow_shrink=True,
                                   heal_after_s=5.0, backoff_base=1.0,
                                   grow_after_s=30.0,
                                   restart_window_s=60.0,
                                   restart_budget=2)
    fr.record("elastic.failure", rank=2, why="process exited 137")
    pol.decide([(2, "process exited 137")],
               {"kind": "crash", "rank": 2, "source": "doctor",
                "evidence": {"why": "exit 137"}},
               now=100.0, evidence_ts=99.5)
    dec.observe("supervisor.remediate", {"failures": 0}, clock=106.0)
    dec.join_outcomes(now=106.0)

    # 2) grow: vetoed while the restarts-per-window budget is spent
    #    (grow_deferred), granted once the window slides
    pol.record_scale_spawn(now=120.0)
    pol.record_scale_spawn(now=121.0)
    deferred_ok = pol.maybe_grow(now=135.0) is None
    grow = pol.maybe_grow(now=190.0)
    dec.observe("supervisor.grow", {"failures": 0}, clock=196.0)
    dec.join_outcomes(now=196.0)

    # 3) serving scale_up on a p99 breach; the queue drains
    spol = elastic.SupervisorPolicy(world=4, initial_world=2,
                                    scale_cooldown_s=5.0,
                                    backoff_base=1.0)
    spol.decide_scale(_SLO(), queued=40, p99_ttft_ms=900.0, now=200.0)
    dec.observe("supervisor.scale",
                {"queued": 4, "p99_ttft_ms": 300.0}, clock=206.0)
    dec.join_outcomes(now=206.0)

    # 4) shed + hot swap (the fleet's record shapes; the swap knows
    #    its outcome at commit time)
    dec.record("fleet.shed", "shed",
               rule="lowest class beyond shed_queue_depth",
               evidence={"inputs": {"cls": "batch", "queue_len": 64,
                                    "shed_queue_depth": 64,
                                    "lowest_class": "batch",
                                    "shed_enabled": True},
                         "decision": {"action": "shed"}},
               signals={"queued": 80}, settle_s=0.05, clock=210.0)
    dec.observe("fleet.shed", {"queued": 10}, clock=211.0)
    dec.join_outcomes(now=211.0)
    dec.record("fleet.swap", "weight_swap",
               rule="standby verified; flip per-replica at token "
                    "boundaries",
               evidence={"inputs": {"verify": True, "standby_ok": True,
                                    "version": 1},
                         "decision": {"action": "weight_swap"}},
               signals={"completed": 0}, post_signals={"completed": 1},
               clock=220.0)

    # 5) certified rollback walking past a decertified candidate
    cands = [{"name": "model.pdckpt", "step": 30, "healthy": False},
             {"name": "model.pdckpt.old", "step": 20, "healthy": True}]
    plan = ckpt.rollback_plan(cands, 25, best_effort=True,
                              require_healthy=True)
    chosen = next(a for a in plan if a["tag"] != "skip_unhealthy")
    dec.record("checkpoint.rollback", "rollback",
               rule="certified consistent-cut walk",
               evidence={"inputs": {"step": 25, "best_effort": True,
                                    "require_healthy": True,
                                    "candidates": cands, "failed": []},
                         "decision": {"action": "rollback",
                                      "chosen": chosen["cand"],
                                      "chosen_step": chosen["step"],
                                      "tag": chosen["tag"],
                                      "certified": True, "plan": plan}},
               signals={"restored": 0, "healthy": 0},
               post_signals={"restored": 1, "healthy": 1}, clock=230.0)

    # 6) layout pick; PR 18's audit gauge is the probe its joiner reads
    dims = sharding.ModelDims(n_params=124_000_000, hidden=768,
                              n_layers=12, seq=1024, batch=8,
                              opt_slots=2,
                              largest_layer_params=38_597_376)
    mesh_plan = sharding.MeshPlan.auto(8, dims, 16e9, calibration=None)
    metrics.gauge("planner.prediction_error", _always=True,
                  metric="step_time").set(0.07)
    dec.join_outcomes(force=True)

    # the paper trail: dump, replay, timeline, export
    doc = dec.dump(reason="obs_report", out_dir=outdir)
    fr.dump(path=os.path.join(
        outdir, "flight_obs_report_rank0_pid%d.json" % os.getpid()),
        reason="obs_report", stacks=False)
    replay = incident_replay.replay_doc(doc)
    replay.pop("results", None)
    events = ops_timeline.timeline_for_dir(outdir)
    trace_path = args.trace or os.path.join(outdir,
                                            "ops_timeline.json")
    with open(trace_path, "w") as f:
        json.dump(ops_timeline.to_chrome_trace(events), f)
    prom_path = args.prom or os.path.join(outdir, "metrics.prom")
    exporters.write_prometheus(prom_path)
    with open(prom_path) as f:
        prom_decision_lines = [
            ln for ln in f.read().splitlines()
            if "decision_" in ln and not ln.startswith("#")]
    metrics.disable()
    fr.disable()

    actors = sorted({r.actor for r in dec.records()})
    outcomes = dec.outcome_counts()
    summary = {
        "ok": True,
        "records": len(dec.records()),
        "actors": actors,
        "outcomes": outcomes,
        "layout": dict(mesh_plan.sizes),
        "replay": replay,
        "timeline_events": len(events),
        "chrome_trace": trace_path,
        "decisions_dump": doc.get("path"),
        "prom_decision_series": len(prom_decision_lines),
        "prometheus": prom_path,
    }
    problems = []
    want_actors = ["checkpoint.rollback", "fleet.shed", "fleet.swap",
                   "planner.layout", "supervisor.grow",
                   "supervisor.remediate", "supervisor.scale"]
    if actors != want_actors:
        problems.append(f"actor classes missing: expected "
                        f"{want_actors}, got {actors}")
    if not deferred_ok or grow is None:
        problems.append("grow budget gate broken: deferred="
                        f"{deferred_ok}, granted={grow is not None}")
    if not replay["ok"]:
        problems.append(f"incident replay diverged: "
                        f"{replay['mismatches']}")
    if outcomes.get("unjoined", 0) != 0:
        problems.append(f"{outcomes['unjoined']} decisions never "
                        "joined an outcome despite post-signals")
    if outcomes.get("improved", 0) < 5:
        problems.append(f"expected >=5 improved outcomes, got "
                        f"{outcomes.get('improved', 0)}")
    if len(events) < 2 * len(dec.records()):
        problems.append(f"timeline carries {len(events)} events for "
                        f"{len(dec.records())} joined decisions")
    if len(prom_decision_lines) < 5:
        problems.append("decision.* series missing from the "
                        "Prometheus export")
    if problems:
        summary["ok"] = False
        summary["problems"] = problems
    print(json.dumps(summary))
    return 0 if summary["ok"] else 1


def _wire_counter_total(snap) -> float:
    """Bytes the EXPLICIT comm paths counted: comm.wire_bytes (the
    compressed on-wire series) plus collective.bytes (trace-time
    recorded collectives). The planner executable's collectives are
    compiler-placed — invisible here, measured from the HLO instead."""
    return float(sum(
        v.get("value", 0.0) for k, v in snap.items()
        if k.startswith("comm.wire_bytes")
        or k.startswith("collective.bytes")))


def get_status(srv, path: str):
    """GET that tolerates non-200 (urllib raises on 404)."""
    import urllib.error
    import urllib.request
    try:
        with urllib.request.urlopen(f"{srv.url}{path}",
                                    timeout=10) as r:
            return r.status, r.read().decode("utf-8")
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode("utf-8")


def run_export(args):
    """Non-demo mode: export whatever the registry holds right now."""
    _jax_setup()
    from paddle_tpu.observability import exporters, fleet, metrics
    merged = fleet.aggregate()
    if args.prom:
        exporters.write_prometheus(args.prom, snap=merged)
    if args.jsonl:
        exporters.JsonlExporter(args.jsonl).write(snap=merged)
    print(json.dumps({"metrics": len(merged),
                      "prometheus": args.prom, "jsonl": args.jsonl}))
    return 0


def run_doctor(args):
    """One operator surface: obs_report is where pod telemetry is read,
    so the hang/divergence forensics bridge lives here too."""
    from tools import tpu_doctor
    argv = ["--dir", args.doctor]
    if args.doctor_json:
        argv.append("--json")
    return tpu_doctor.main(argv)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--demo", action="store_true")
    ap.add_argument("--anatomy", action="store_true")
    ap.add_argument("--memory", action="store_true")
    ap.add_argument("--serving", action="store_true")
    ap.add_argument("--pulse", action="store_true")
    ap.add_argument("--plan-audit", action="store_true",
                    dest="plan_audit",
                    help="measured-vs-predicted plan audit receipt "
                         "(cost-model truth plane)")
    ap.add_argument("--decisions", action="store_true",
                    help="decision-ledger receipt: canned incident -> "
                         "joined outcomes -> bit-identical replay -> "
                         "ops timeline -> exported decision.* series")
    ap.add_argument("--force-recompile", action="store_true")
    ap.add_argument("--doctor", default=None, metavar="DIR",
                    help="diagnose flight-recorder dumps in DIR "
                         "(tools/tpu_doctor.py bridge)")
    ap.add_argument("--doctor-json", action="store_true")
    ap.add_argument("--out", default="/tmp/pd_obs")
    ap.add_argument("--prom", default=None)
    ap.add_argument("--jsonl", default=None)
    ap.add_argument("--trace", default=None)
    args = ap.parse_args(argv)
    if args.doctor:
        return run_doctor(args)
    if args.decisions:
        return run_decisions(args)
    if args.plan_audit:
        return run_plan_audit(args)
    if args.pulse:
        return run_pulse(args)
    if args.serving:
        return run_serving(args)
    if args.memory:
        return run_memory(args)
    if args.anatomy:
        return run_anatomy(args)
    if args.demo:
        return run_demo(args)
    return run_export(args)


if __name__ == "__main__":
    sys.exit(main())
