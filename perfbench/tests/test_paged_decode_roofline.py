"""`paged_decode_roofline` on a made-up reduced trace and ring: a
known byte count over a known time. Runs on the CPU, no jax:

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/tests -q
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.lib import common, paged_decode  # noqa: E402

T_OPEN, T_CLOSE = 10.0, 20.0
BANDWIDTH = 819e9
CONFIG = {"n_embd": 1280, "n_head": 20,
          "precision": {"cache": "bfloat16"}}
# a token's K and V in every head: 2 x 1280 x 2 B
BYTES_A_TOKEN = 5120
OP = "paged_decode_attention_bf16_32_20_64_"


def _decode(tick, t0, tokens):
    """One dispatch: a `decode` span a slot, all with one start."""
    return [{"rid": f"r{tick}.{i}", "comp": "decode", "t0": t0,
             "t1": t0 + 0.06, "bucket": 32, "chunk": 4, "tokens": n,
             "replica": None, "tick": tick}
            for i, n in enumerate(tokens)]


def _ctx(op_seconds, op_calls, spans, op=OP):
    trace = {"per_op_s": {"fusion_bf16_32_3840_": 0.2},
             "per_op_calls": {"fusion_bf16_32_3840_": 144}}
    if op_calls:
        trace["per_op_s"][op] = op_seconds
        trace["per_op_calls"][op] = op_calls
    return {"trace": trace, "config": CONFIG,
            "peaks": {"hbm_bytes_per_s": BANDWIDTH},
            "bench": {"spans": spans, "t_open": T_OPEN,
                      "t_close": T_CLOSE}}


# two dispatches inside the window, 3,000 and 5,000 tokens held (mean
# 4,000 = 20.48 MB = 25.0061 us at the peak), one before it opened
SPANS = (_decode(1, 9.0, [9000]) + _decode(2, 11.0, [1000, 2000])
         + _decode(3, 12.0, [2500, 2500])
         + [{"rid": "a", "comp": "prefill", "t0": 11.5, "t1": 11.6,
             "bucket": 128, "width": 4, "tick": 3}])
LEAST_S = 4000 * BYTES_A_TOKEN / BANDWIDTH


def _padded(factor):
    """A kernel that moves `factor` times the logical bytes (a page
    layout padded so) at the full bandwidth: 288 calls."""
    return _ctx(288 * LEAST_S * factor, 288, SPANS), 100.0 / factor


CASES = {
    "known_bytes_over_known_time":
        (_ctx(288 * 100e-6, 288, SPANS), 100.0 * LEAST_S / 100e-6),
    "no_such_op_in_the_trace": (_ctx(0.0, 0, SPANS), None),
    "spans_without_the_field":
        (_ctx(288 * 100e-6, 288,
              [{k: v for k, v in ev.items() if k != "tokens"}
               for ev in SPANS]), None),
    "no_decode_in_the_window":
        (_ctx(288 * 100e-6, 288, _decode(1, 9.0, [9000])), None),
    "layout_unpadded": _padded(1.0),
    "layout_padded_2.4x": _padded(2.4),     # [16, 20, 64] in (8, 128) tiles
    "layout_padded_3.2x": _padded(3.2),     # the same in (16, 128) tiles
}


@pytest.mark.parametrize("case", list(CASES))
def test_paged_decode_roofline(case):
    ctx, want = CASES[case]
    got = common.metric_reader("paged_decode_roofline")(ctx)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-9)
        assert 0.0 < got <= 100.0 + 1e-9


def test_tokens_held_is_a_mean_over_dispatches():
    assert paged_decode.tokens_held_mean(
        {"bench": {"spans": SPANS, "t_open": T_OPEN,
                   "t_close": T_CLOSE}}) == 4000.0
    assert 100.0 * LEAST_S / 100e-6 == pytest.approx(25.0061, rel=1e-5)
