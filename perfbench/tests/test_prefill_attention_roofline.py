"""`prefill_attention_roofline.score` on a made-up reduced trace and
records: a known FLOP count over a known time. Runs on the CPU, no jax:

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/tests -q
"""
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.lib import common, flash_prefill  # noqa: E402

T_OPEN, T_CLOSE = 10.0, 20.0
PEAK = 197e12
CONFIG = {"n_embd": 1280, "n_head": 20}
OP = "flash_prefill_attention_bf16_4_1024_1280_"


def _record(rid, n):
    return SimpleNamespace(rid=rid, item=SimpleNamespace(ids=[0] * n))


def _prefill(tick, t0, rids, bucket=1024):
    """One dispatch: a `prefill` span a request, all with one start."""
    return [{"rid": rid, "comp": "prefill", "t0": t0, "t1": t0 + 0.08,
             "bucket": bucket, "width": 4, "tick": tick} for rid in rids]


def _flops(*lens):
    return sum(2.0 * 1280 * n * (n + 1) for n in lens)


# two dispatches inside the window (4 prompts and 2), one before it
RECORDS = [_record("a", 1024), _record("b", 256), _record("c", 700),
           _record("d", 513), _record("e", 300), _record("f", 1000),
           _record("early", 999)]
SPANS = (_prefill(1, 9.0, ["early"]) + _prefill(2, 11.0, "abcd")
         + _prefill(3, 12.0, "ef")
         + [{"rid": "a", "comp": "decode", "t0": 12.5, "t1": 12.6,
             "bucket": 32, "chunk": 4, "tokens": 1025, "tick": 4}])
MEAN = (_flops(1024, 256, 700, 513) + _flops(300, 1000)) / 2
LEAST_S = MEAN / PEAK


def _ctx(op_seconds, op_calls, spans=SPANS, records=RECORDS):
    trace = {"per_op_s": {"convolution_add_fusion_bf16_4_1024_5120_": 0.9},
             "per_op_calls": {"convolution_add_fusion_bf16_4_1024_5120_":
                              3276}}
    if op_calls:
        trace["per_op_s"][OP] = op_seconds
        trace["per_op_calls"][OP] = op_calls
    return {"trace": trace, "config": CONFIG,
            "peaks": {"bf16_flops_per_s": PEAK},
            "bench": {"spans": spans, "records": records,
                      "t_open": T_OPEN, "t_close": T_CLOSE}}


CASES = {
    "known_flops_over_known_time":
        (_ctx(3276 * 250e-6, 3276), 100.0 * LEAST_S / 250e-6),
    "at_the_peak": (_ctx(72 * LEAST_S, 72), 100.0),
    # a head of 64 on the 128-wide MXU over the whole bucket's block
    # diagonal does four times the triangle's work or more
    "padded_work_reads_low": (_ctx(72 * LEAST_S * 4.0, 72), 25.0),
    "no_such_op_in_the_trace": (_ctx(0.0, 0), None),
    "no_prefill_in_the_window":
        (_ctx(0.02, 72, _prefill(1, 9.0, ["early"])), None),
    "spans_of_unknown_requests":
        (_ctx(0.02, 72, SPANS, [_record("zz", 5)]), None),
}


@pytest.mark.parametrize("case", list(CASES))
def test_prefill_attention_roofline(case):
    ctx, want = CASES[case]
    got = common.metric_reader("prefill_attention_roofline.score")(ctx)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-9)
        assert 0.0 < got <= 100.0 + 1e-9


def test_flops_are_the_triangle_of_the_true_prompts():
    assert flash_prefill.attention_flops_mean(_ctx(0, 0)) == MEAN
    # four full rows of 1,024: 10.7 GFLOP, 54.5 us at the peak
    assert _flops(1024, 1024, 1024, 1024) == pytest.approx(10.75e9, rel=1e-3)
