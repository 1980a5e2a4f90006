"""The readers of the expert model's cell on made-up reduced traces and
rings: known FLOPs and bytes, counted here by hand, over known times.
Runs on the CPU, no jax:

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/tests -q
"""
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.lib import common, moe  # noqa: E402

T_OPEN, T_CLOSE, WINDOW_S = 10.0, 20.0, 4.0
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
KINDS = ["sliding_attention"] * 3 + ["full_attention"]
CONFIG = {"hidden_size": 2304, "moe_intermediate_size": 896,
          "num_experts": 64, "num_experts_per_tok": 8,
          "num_attention_heads": 32, "num_key_value_heads": 4,
          "head_dim": 128, "sliding_window": 1024, "num_hidden_layers": 8,
          "layer_types": KINDS * 7,
          "precision": {"weights": "bfloat16", "compute": "bfloat16",
                        "cache": "bfloat16"}}
GMM = "gmm_f32_256_1792_"


def _step(n, t0, syncs):
    """One engine step at t0: a `step` span and, per dispatch, a
    `dispatch` and a `sync` carrying the expert fields."""
    out = [{"rid": None, "comp": "step", "t0": t0, "t1": t0 + 0.1,
            "step": n, "replica": None, "executables": 5}]
    t = t0
    for fields in syncs:
        out.append({"rid": None, "comp": "dispatch", "t0": t,
                    "t1": t + 0.01, "step": n, "replica": None,
                    "parent": "step", "kind": "decode"})
        out.append({"rid": None, "comp": "sync", "t0": t + 0.01,
                    "t1": t + 0.04, "step": n, "replica": None,
                    "parent": "step", "kind": "decode", "ready": t + 0.03,
                    **fields})
        t += 0.05
    return out


# a decode dispatch: 8 layers x 4 token-steps x 32 lanes x 8 rows, every
# expert hit in every layer and token-step, the busiest at 20 rows a layer
DECODE = {"expert_rows": 8 * 4 * 32 * 8, "expert_rows_peak": 8 * 20,
          "experts_hit": 8 * 4 * 64}
# a prefill dispatch of 2 x 3,000 true tokens: 8 layers x 6,000 x 8 rows
PREFILL = {"expert_rows": 8 * 6000 * 8, "expert_rows_peak": 8 * 1500,
           "experts_hit": 8 * 64}


def _trace(secs, calls, op=GMM):
    trace = {"per_op_s": {"fusion_bf16_32_2304_": 0.2},
             "per_op_calls": {"fusion_bf16_32_2304_": 100},
             "window_s": WINDOW_S, "busy_s": 3.0}
    if calls:
        trace["per_op_s"][op] = secs
        trace["per_op_calls"][op] = calls
    return trace


def _ctx(trace, spans, records=()):
    return {"trace": trace, "config": CONFIG, "peaks": PEAKS,
            "bench": {"spans": spans, "t_open": T_OPEN, "t_close": T_CLOSE,
                      "records": list(records), "max_slots": 32,
                      "pages_total": 11084}}


# inside the capture [16, 20]: one decode and one prefill dispatch; one
# decode dispatch in the window before the capture, one before the window
RING = (_step(1, 9.0, [DECODE]) + _step(2, 12.0, [DECODE])
        + _step(3, 17.0, [DECODE, PREFILL]))

# by hand: the decode dispatch reads 2,048 experts' weights (2,304 x
# 896 x 3 = 6,193,152 bf16 each: 12,386,304 B) and moves 8,192 rows in
# and out (2 x 2,304 x 2 B = 9,216 B a row): 25,367,150,592 +
# 75,497,472 B; its FLOPs, 2 x 8,192 x 2,304 x 896 x 3 = 1.01e11, take
# 0.515 ms, so it is bound by bytes
DECODE_LEAST = (2048 * 12_386_304 + 8192 * 9216) / 819e9
# the prefill dispatch: 384,000 rows, 1.9e12 FLOPs = 9.65 ms, against
# 512 x 12,386,304 + 384,000 x 9,216 B = 9.88 ms: bound by bytes too
PREFILL_FLOPS = 2.0 * 384_000 * 2304 * 896 * 3
PREFILL_BYTES = 512 * 12_386_304 + 384_000 * 9216
PREFILL_LEAST = max(PREFILL_FLOPS / 197e12, PREFILL_BYTES / 819e9)


def test_least_expert_time_by_hand():
    assert moe.expert_bytes(CONFIG) == 12_386_304
    assert moe.least_expert_s(CONFIG, PEAKS, **{
        "rows": 8192, "hit": 2048}) == pytest.approx(DECODE_LEAST)
    assert moe.least_expert_s(CONFIG, PEAKS, 384_000, 512) \
        == pytest.approx(PREFILL_LEAST)
    # few rows of few experts: the FLOPs of a dense row block bound it
    assert moe.least_expert_s(CONFIG, PEAKS, 10 ** 7, 1) == pytest.approx(
        2.0 * 10 ** 7 * 2304 * 896 * 3 / 197e12)


GMM_CASES = {
    "two_dispatches_in_the_capture_at_full_speed":
        (_ctx(_trace(DECODE_LEAST + PREFILL_LEAST, 64 + 16), RING), 100.0),
    "at_a_quarter": (_ctx(_trace(4 * (DECODE_LEAST + PREFILL_LEAST), 80),
                          RING), 25.0),
    "ragged_dot_by_its_name":
        (_ctx(_trace(DECODE_LEAST + PREFILL_LEAST, 80,
                     "ragged-dot-none_f32_81920_1792_"), RING), 100.0),
    "no_expert_kernel": (_ctx(_trace(1.0, 0), RING), None),
    "syncs_without_the_fields":
        (_ctx(_trace(1.0, 80), [{k: v for k, v in ev.items()
                                 if not k.startswith("expert")}
                                for ev in RING]), None),
    "no_capture": (_ctx(dict(_trace(1.0, 80), window_s=0.0), RING), None),
}


@pytest.mark.parametrize("case", list(GMM_CASES))
def test_expert_gmm_roofline(case):
    ctx, want = GMM_CASES[case]
    got = common.metric_reader("expert_gmm_roofline.code")(ctx)
    assert got == (None if want is None else pytest.approx(want))


def test_expert_device_share_and_load():
    ctx = _ctx(_trace(1.0, 80), RING)
    assert common.metric_reader("expert_device_share.code")(ctx) \
        == pytest.approx(25.0)
    # the window's three dispatches: 160 / (8,192 / 64) = 1.25 twice,
    # 12,000 / (384,000 / 64) = 2.0 once
    assert common.metric_reader("expert_load_peak_over_mean.code")(ctx) \
        == pytest.approx((1.25 + 1.25 + 2.0) / 3)
    assert common.metric_reader("expert_device_share.code")(
        _ctx(_trace(1.0, 0), RING)) is None
    assert common.metric_reader("expert_load_peak_over_mean.code")(
        _ctx(_trace(1.0, 80), [])) is None


def _decode(tick, t0, tokens):
    return [{"rid": f"r{tick}.{i}", "comp": "decode", "t0": t0,
             "t1": t0 + 0.06, "bucket": 32, "chunk": 4, "tokens": n,
             "replica": None, "tick": tick} for i, n in enumerate(tokens)]


# 2 K/V x 4 heads x 128 x 2 B = 2,048 B a token and layer. A dispatch
# of lanes at 500 and 3,000 tokens: the 2 full layers read 3,500 tokens
# each, the 6 sliding ones 500 + 1,024; 7,000 + 9,144 = 16,144 tokens,
# 33,062,912 B a token-step, 4,132,864 B a call (a layer)
KV_CALL = 16_144 * 2048 / 8
# a dispatch at 100 and 200 tokens: every layer reads them all
KV_CALL_SHORT = 300 * 2048


def test_decode_kv_bytes_by_hand():
    assert moe.decode_kv_bytes(CONFIG, [500, 3000]) == 16_144 * 2048
    assert moe.decode_kv_bytes(CONFIG, [100, 200]) == 8 * 300 * 2048


@pytest.mark.parametrize("spans,want_bytes", [
    (_decode(1, 11.0, [500, 3000]), KV_CALL),
    (_decode(1, 11.0, [500, 3000]) + _decode(2, 12.0, [100, 200]),
     (KV_CALL + KV_CALL_SHORT) / 2),
    (_decode(1, 9.0, [500, 3000]), None)])
def test_paged_decode_roofline_code(spans, want_bytes):
    ctx = _ctx(_trace(64 * 10e-6, 64, "paged_decode_attention_f32_32_32_128_"),
               spans)
    got = common.metric_reader("paged_decode_roofline.code")(ctx)
    if want_bytes is None:
        assert got is None
    else:
        assert got == pytest.approx(100.0 * want_bytes / 819e9 / 10e-6)


def test_prompt_attention_flops_by_hand():
    # 3,000 tokens: the triangle 3,000 x 3,001 / 2 = 4,501,500 links on
    # each full layer; the band 1,024 x 1,025 / 2 + 1,976 x 1,024 =
    # 2,548,224 on each sliding one; 4 x 32 x 128 = 16,384 FLOPs a link
    assert moe.prompt_attention_flops(CONFIG, 3000) == \
        16384.0 * (2 * 4_501_500 + 6 * 2_548_224)
    # under the window every layer is the triangle
    assert moe.prompt_attention_flops(CONFIG, 1000) == \
        16384.0 * 8 * 1000 * 1001 / 2


def test_prefill_attention_roofline_code():
    recs = [SimpleNamespace(rid="a", item=SimpleNamespace(ids=[0] * 3000)),
            SimpleNamespace(rid="b", item=SimpleNamespace(ids=[0] * 1000))]
    spans = [{"rid": r, "comp": "prefill", "t0": 11.0, "t1": 11.2,
              "bucket": 3072, "width": 2, "tick": 4} for r in ("a", "b")]
    ctx = _ctx(_trace(16 * 1e-3, 16, "flash_prefill_attention_bf16_2_3072_"
                      "4096_"), spans, recs)
    per_call = (moe.prompt_attention_flops(CONFIG, 3000)
                + moe.prompt_attention_flops(CONFIG, 1000)) / 8
    got = common.metric_reader("prefill_attention_roofline.code")(ctx)
    assert got == pytest.approx(100.0 * per_call / 197e12 / 1e-3)
    assert common.metric_reader("prefill_attention_roofline.code")(
        _ctx(_trace(1.0, 0), spans, recs)) is None
