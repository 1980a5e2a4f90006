"""Tier-1 smoke for tools/obs_report.py --demo (the observability
acceptance surface): a 2-stage CPU-mesh run must produce a Prometheus
text dump and JSONL series carrying per-op dispatch counts, collective
bytes, step_ms percentiles, examples/sec, an MFU estimate, and
train_recompiles_total == 0; the --force-recompile leg must flip the
recompile counter to exactly 1 with a logged shape diff."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

_ENV = {
    **os.environ,
    "JAX_PLATFORMS": "cpu",
    "PD_OBS_DEMO_DEVICES": "2",
    "PD_OBS_DEMO_MICRO": "4",
    "PD_OBS_DEMO_WIDTH": "64",
    "PD_OBS_DEMO_DEPTH": "1",
    "PD_OBS_DEMO_BATCH": "16",
    "PD_OBS_DEMO_STEPS": "2",
}
# the parent test process pins a different virtual device count; the
# demo subprocess must pick its own
_ENV.pop("XLA_FLAGS", None)


def _run(tmp_path, *extra):
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "obs_report.py"),
         "--demo", "--out", str(tmp_path), *extra],
        capture_output=True, text=True, timeout=300, env=_ENV,
        cwd=ROOT)
    assert p.returncode == 0, (p.stdout + "\n" + p.stderr)[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_demo_full_surface_and_forced_recompile(tmp_path):
    # ONE subprocess proves both acceptance legs: the exports are
    # written from the steady-shape run (train_recompiles_total == 0),
    # the forced shape change afterwards flips the sentinel to 1
    s = _run(tmp_path, "--force-recompile")
    assert s["ok"], s
    assert s["op_dispatch_counts"], s
    assert any(v > 0 for v in s["collective_bytes"].values()), s
    assert s["step_ms_p99"] >= s["step_ms_p50"] > 0
    assert s["examples_per_sec"] > 0
    # the demo runs on the CPU, which has no peak: no MFU is reported
    assert s["mfu"] is None and s["model_flops_per_step"] > 0
    assert s["fleet_host_count"] == 1

    # steady-shape leg: zero recompiles in the exported artifacts
    assert s["steady_recompiles_total"] == 0
    prom = open(s["prometheus"]).read()
    assert "train_recompiles_total 0" in prom
    assert "paddle_tpu_op_dispatch_total" in prom
    assert "paddle_tpu_collective_bytes" in prom
    assert 'paddle_tpu_pipeline_step_ms{quantile="0.5"}' in prom
    assert "paddle_tpu_throughput_examples_per_sec" in prom
    assert "paddle_tpu_throughput_mfu" not in prom
    rec = json.loads(open(s["jsonl"]).read().splitlines()[-1])
    m = rec["metrics"]
    assert m["train_recompiles_total"] == 0
    assert any(k.startswith("op.dispatch.total") for k in m)
    assert any(k.startswith("collective.bytes") for k in m)
    assert m["pipeline.step_ms"]["p50"] > 0
    assert m["throughput.examples_per_sec"] > 0
    assert "throughput.mfu" not in m
    # metric marks merged into the host chrome trace
    tr = json.load(open(s["trace"]))
    assert any(e.get("ph") == "C" for e in tr["traceEvents"])

    # forced-shape-change leg: counter flips to exactly 1, diff logged
    assert s["train_recompiles_total"] == 1
    assert s["recompile_diff"] and "->" in s["recompile_diff"], s


def test_serving_bridge_receipt(tmp_path):
    """--serving: the zero-to-request-anatomy receipt — tiny fleet,
    deterministic trace, tail attribution summing to ~1.0 per cohort
    request, SLO burn + per-class queue-depth gauges in the exports,
    request lanes merged into the chrome trace."""
    prom = tmp_path / "srv.prom"
    jsonl = tmp_path / "srv.jsonl"
    trace = tmp_path / "srv_trace.json"
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "obs_report.py"),
         "--serving", "--prom", str(prom), "--jsonl", str(jsonl),
         "--trace", str(trace)],
        capture_output=True, text=True, timeout=300, env=_ENV,
        cwd=ROOT)
    assert p.returncode == 0, (p.stdout + "\n" + p.stderr)[-2000:]
    s = json.loads(p.stdout.strip().splitlines()[-1])
    assert s["ok"], s
    assert s["requests"] == 8
    tail = s["tail_attribution"]
    assert tail["cohort"]
    for c in tail["cohort"]:
        assert abs(c["share_sum"] - 1.0) <= 0.02, c
        assert c["dominant"]
    assert s["breach_verdict"]["cause"]
    assert s["recompile_events"] == 0
    assert any(k.startswith("serving.slo.burn_rate{window=")
               for k in s["slo_burn_gauges"])
    assert any("cls=interactive" in k
               for k in s["queue_depth_by_class"])
    prom_text = prom.read_text()
    assert "paddle_tpu_serving_slo_burn_rate" in prom_text
    assert "paddle_tpu_serving_fleet_queue_depth" in prom_text
    tr = json.load(open(trace))
    lanes = [e for e in tr["traceEvents"]
             if e.get("cat") == "reqtrace"]
    assert any(e.get("ph") == "X" for e in lanes)
    assert any(e.get("ph") == "M"
               and "serving replica" in e["args"]["name"]
               for e in tr["traceEvents"])


def test_plan_audit_bridge_receipt(tmp_path):
    """--plan-audit: the zero-to-receipt drive of the cost-model truth
    plane (PR 18) — live sentinel-guarded steps, all three measured
    planes joined onto the PlanReceipt, error shares summing to ~1
    with the worst-mispredicted component named, the always-on
    prediction-error gauges on the pulse rings, and a ledgerable
    planner_prediction_error receipt on the JSONL stream."""
    jsonl = tmp_path / "audit.jsonl"
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "obs_report.py"),
         "--plan-audit", "--jsonl", str(jsonl)],
        capture_output=True, text=True, timeout=300,
        env={**_ENV, "PD_OBS_DEMO_DEVICES": "8",
             "PD_OBS_DEMO_STEPS": "2"}, cwd=ROOT)
    assert p.returncode == 0, (p.stdout + "\n" + p.stderr)[-2000:]
    s = json.loads(p.stdout.strip().splitlines()[-1])
    assert s["ok"], s
    assert s["audit"]["metric"] == "planner_prediction_error"
    assert s["audit"]["value"] == 3               # all planes joined
    errs = s["prediction_error"]
    assert set(errs) == {"step_time", "hbm_peak", "wire_bytes"}
    assert all(0.0 <= v <= 1.0 for v in errs.values()), errs
    assert abs(sum(s["error_share"].values()) - 1.0) <= 0.02
    assert s["worst"] in errs
    # the committed table matches the 8-device smoke: the prediction
    # must have ranked on it, and both absolute estimates must ride
    assert s["used"] == "calibrated" and s["calibration_match"]
    ex = s["audit"]["extras"]
    assert ex["analytic_step_time_s"] > 0
    assert ex["calibrated_step_time_s"] > 0
    # measured wire came from the compiled HLO's collective inventory
    # (compiler-placed collectives never hit the comm counters)
    assert s["hlo_collective_calls"] > 0
    assert s["measured"]["wire_bytes"] > 0
    # sentinel guards: observation never touched the train executable
    assert s["train_executables"] == 1
    assert s["train_recompiles"] == 0
    # always-on gauges landed on the pulse rings
    assert len(s["pulse_ring_keys"]) == 3
    assert s["pulse_ring_points"] >= 3
    # the JSONL stream carries the same receipt, ledger-ready
    rec = json.loads(jsonl.read_text().splitlines()[-1])
    from paddle_tpu.analysis import perf_ledger as pl
    led = pl.record_from_artifact(s["audit"], source="bench", run="t")
    assert led["label"] == "planner_prediction_error"
    assert led["metrics"]["extras.calibration.match"] == 1.0
    assert rec["metrics"], rec


@pytest.mark.slow  # 8.3 s; test_pulse_server's 14 tests + the three
#                    bridges above keep pulse + obs_report in tier-1
def test_pulse_bridge_receipt():
    """--pulse: THE live scrape-parity acceptance receipt — during a
    running fleet leg a mid-run HTTP /metrics pull parses as valid
    Prometheus text; the post-run pull is byte-identical to
    to_prometheus(metrics.snapshot()); /healthz answers ok with a
    nonzero sample count; /series returns >=2 ring points; and the
    committed perf ledger renders >=5 historical rounds."""
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "obs_report.py"),
         "--pulse"],
        capture_output=True, text=True, timeout=300,
        env={**_ENV, "PD_SRV_REQUESTS": "6"}, cwd=ROOT)
    assert p.returncode == 0, (p.stdout + "\n" + p.stderr)[-2000:]
    s = json.loads(p.stdout.strip().splitlines()[-1])
    assert s["ok"], s
    assert s["mid_run_scrapes"], s
    for sc in s["mid_run_scrapes"]:
        assert sc["status"] == 200 and sc["lines"] > 0, s
    assert s["scrape_parity"] is True, s
    assert s["healthz"]["status"] == 200
    assert s["healthz"]["verdict"] == "ok"
    assert s["pulse_samples"] > 0
    assert s["series_points"] >= 2
    assert s["unknown_series_status"] == 404
    assert s["trend_rounds"] >= 5
