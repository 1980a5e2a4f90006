"""Serving chaos drill receipts (tools/serving_chaos_drill.py).

Tier-1: the --smoke kill drill — 3 in-process replicas under open-loop
load, replica 1 killed mid-decode at a named fleet tick; the receipt
must show ZERO dropped requests, >= 1 evicted request replayed
BIT-IDENTICALLY (f32 greedy parity) and one remediation receipt naming
the replica (the ISSUE's serving twin of the goodput drill). The smoke
judges counts and identity only: it shares its CPU with the other test
workers, where a wall-clock TTFT says nothing, so `p99_recovery_s` is
reported there and judged by the full drills.

Slow tier: the kill / stall / swap / overload drills at full shapes,
p99 TTFT recovered inside the bound among their bars.
"""
import io
import json
from contextlib import redirect_stdout

import pytest

from tools import serving_chaos_drill


def _run(argv):
    from paddle_tpu.observability import metrics
    buf = io.StringIO()
    # the CLI enables the metrics gate; restore it so test order
    # can't leak an enabled gate into gate-down assertions elsewhere
    with metrics.enabled_scope(metrics.enabled()), redirect_stdout(buf):
        rc = serving_chaos_drill.main(argv)
    line = [l for l in buf.getvalue().splitlines()
            if l.startswith("serving_chaos_drill:")][-1]
    return rc, json.loads(line.split("serving_chaos_drill:", 1)[1])


class TestSmokeKillDrill:
    def test_smoke_kill_receipt(self, tmp_path):
        rc, rep = _run(["--smoke", "--check",
                        "--receipts-dir", str(tmp_path)])
        assert rc == 0
        x = rep["extras"]
        assert x["receipt_ok"] is True
        assert x["dropped"] == 0
        assert x["replay"]["replayed"] >= 1
        assert x["replay"]["bit_identical"] is True
        assert x["receipt_names_replica"] is True
        assert x["expected_verdict"] == "crash"
        assert x["recovery_judged"] is False
        # the trace-ALONE breach verdict names the evicted replica and
        # the requeue component (no receipts consulted)
        v = x["breach_verdict"]
        assert v["cause"] == "replica_kill"
        assert v["replica"] == 1
        assert v["component"] == "requeue"
        assert x["trace_verdict_ok"] is True
        assert x["tail_components_sum_ok"] is True
        assert all(abs(c["share_sum"] - 1.0) <= 0.02
                   for c in x["tail_attribution"]["cohort"])
        summ = x["stats"]["fleet"]
        assert summ["recompile_events"] == 0
        assert summ["requeued_total"] >= 1
        assert any(e["action"] == "evict_shrink" and e["ranks"] == [1]
                   for e in summ["episodes"])
        # the remediation receipt landed on disk too
        receipts = list(tmp_path.glob("receipt_ep*.json"))
        assert receipts, "no remediation receipt written"
        docs = [json.loads(p.read_text()) for p in receipts]
        assert any(d["action"] == "evict_shrink" and d["ranks"] == [1]
                   for d in docs)


@pytest.mark.slow  # ~8 s each at full shapes; the tier-1 smoke above
#   keeps the kill path + receipt contract covered
class TestFullDrills:
    @pytest.mark.parametrize("mode,verdict", [("kill", "crash"),
                                              ("stall", "hang")])
    def test_fault_drill(self, tmp_path, mode, verdict):
        rc, rep = _run(["--mode", mode, "--check",
                        "--receipts-dir", str(tmp_path)])
        assert rc == 0
        x = rep["extras"]
        assert x["receipt_ok"] is True
        assert x["expected_verdict"] == verdict
        assert x["recovery_judged"] is True
        assert 0.0 <= x["p99_recovery_s"] <= x["recovery_bound_s"]
        assert x["dropped"] == 0
        assert x["replay"]["bit_identical"] is True

    def test_swap_drill(self, tmp_path):
        rc, rep = _run(["--mode", "swap", "--check",
                        "--receipts-dir", str(tmp_path)])
        assert rc == 0
        x = rep["extras"]
        assert x["receipt_ok"] is True
        assert x["clean_swap_ok"] is True
        assert x["sabotaged_swap_aborted"] is True
        assert x["outputs_bit_identical"] is True
        assert x["zero_recompiles"] is True
        assert x["dropped"] == 0

    def test_overload_drill(self, tmp_path):
        rc, rep = _run(["--mode", "overload", "--replicas", "1",
                        "--max-replicas", "1", "--requests", "30",
                        "--shed-depth", "4", "--slo-p99-ms", "2500",
                        "--vocab", "97", "--hidden", "32",
                        "--layers", "2", "--heads", "4",
                        "--max-seq-len", "64", "--slots", "4",
                        "--admit", "2", "--block-size", "4",
                        "--n-blocks", "64", "--prefill-buckets", "24",
                        "--max-total", "24", "--decode-chunk", "2",
                        "--prompt-lens", "2,3,5,7",
                        "--new-tokens", "3,4,6", "--rate", "1000",
                        "--check", "--receipts-dir", str(tmp_path)])
        assert rc == 0
        x = rep["extras"]
        assert x["receipt_ok"] is True
        assert x["dropped"] == 0
        assert x["interactive"]["finished"] == \
            x["interactive"]["requests"]
        assert x["interactive"]["p99_ttft_ms"] <= \
            x["interactive"]["slo_p99_ms"]
        assert x["only_batch_shed"] is True
        assert x["low_priority_degraded"] is True
        # per-class TTFT histograms in the receipt
        assert "per_class_ttft_ms" in x["stats"]
