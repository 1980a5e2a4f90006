"""Tier-1 pipeline-bench smoke: guards against reintroducing per-tick
dispatch into the train step.

Runs tools/pipeline_bench.py in a subprocess with small shapes and
fails if
  - compile_count exceeds the config count (exactly ONE train
    executable per config is the spmd_1f1b contract), or
  - dispatches_per_step leaves 1 (the single-program contract), or
  - the orchestration_fraction field disappears from the JSON.

Counts only: a CPU wall-clock ratio is not a speed, so no timing bar.
"""
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

_ENV = {
    **os.environ,
    "JAX_PLATFORMS": "cpu",
    "PD_PIPE_BENCH_DEVICES": "2",
    "PD_PIPE_BENCH_MICRO": "4",
    "PD_PIPE_BENCH_WIDTH": "512",
    "PD_PIPE_BENCH_DEPTH": "2",
    "PD_PIPE_BENCH_BATCH": "64",
    "PD_PIPE_BENCH_STEPS": "3",
}
# the parent test process pins a different virtual device count; the
# bench subprocess must pick its own
_ENV.pop("XLA_FLAGS", None)


def _run_bench(jsonl=None):
    env = _ENV if jsonl is None else {**_ENV, "PD_OBS_JSONL": jsonl}
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools",
                                      "pipeline_bench.py")],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=ROOT)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_pipeline_bench_single_dispatch(tmp_path):
    jsonl = str(tmp_path / "bench.jsonl")
    stats = _run_bench(jsonl=jsonl)

    # the printed report and the metrics-runtime JSONL export come from
    # ONE code path (observability.exporters.emit_report): the exported
    # series must carry exactly the printed fields, value-identical
    rec = json.loads(open(jsonl).read().splitlines()[-1])
    exported = {k[len("bench.pipeline."):]: v["value"] if isinstance(
        v, dict) and "value" in v else v
        for k, v in rec["metrics"].items()
        if k.startswith("bench.pipeline.")}
    assert exported == stats, (
        "JSONL export diverged from the printed bench report")

    # structural contracts — single shot, load-independent
    assert stats["compile_count"] == 1, stats
    assert stats["dispatches_per_step"] == 1, stats
    assert stats["host_dispatches_per_step"] > 1, stats
    assert "orchestration_fraction" in stats
    assert 0.0 <= stats["orchestration_fraction"] <= 1.0
    assert stats["tick_ms_p50"] >= 0.0      # host per-tick percentiles
    assert stats["tick_ms_p99"] >= stats["tick_ms_p50"]
    assert stats["step_ms_p99"] >= stats["step_ms_p50"] > 0.0
    assert stats["stages"] == 2 and stats["num_micro"] == 4
