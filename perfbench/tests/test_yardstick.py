"""The yardstick checked against what it must give: the FLOP count
against the issue's arithmetic, the trace reduction against a small
capture recorded on the v5e, the traffic generator against its promise
that every seed gets the same work. Runs on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/tests -q
"""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.lib import common, trace_reduce, traffic  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data")


def test_gpt2_forward_flops_per_prompt_token():
    _, _, cfg, _, _, reference, _ = common.cell_files("gpt2-large.score")
    fl = reference.forward_flops(cfg, 0, 640, 1) / 640
    assert fl == pytest.approx(1.48e9, rel=0.01)
    # a decode token at position p: the blocks, p + 1 keys, one head
    one = reference.forward_flops(cfg, 100, 1, 1)
    assert one == pytest.approx(
        2 * 12 * 1280 ** 2 * 36 + 4 * 1280 * 36 * 101
        + 2 * 1280 * 50257)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(SystemExit):
        common.peaks_for("TPU v9 imaginary")


def test_every_seed_gets_the_same_work_in_another_order():
    mix = common.load_json("traffic", "chat-steady.json")
    a = traffic.serve_items(mix, 50257, 1, 40.0)
    b = traffic.serve_items(mix, 50257, 3_000_000_019, 40.0)
    assert len(a) == len(b)
    for pick in (lambda it: len(it.ids), lambda it: it.max_new_tokens):
        assert sorted(map(pick, a)) == sorted(map(pick, b))
    assert [len(i.ids) for i in a] != [len(i.ids) for i in b]
    gaps = [np.diff([0.0] + [i.due_s for i in x]) for x in (a, b)]
    np.testing.assert_allclose(np.sort(gaps[0]), np.sort(gaps[1]),
                               atol=1e-9)
    assert all(len(i.ids) + i.max_new_tokens <= mix["max_total"]
               for i in a)
    assert all(mix["prompt"]["min"] <= len(i.ids) <= mix["prompt"]["max"]
               for i in a)
    # the same seed gives the same inputs
    again = traffic.serve_items(mix, 50257, 1, 40.0)
    assert all((x.ids == y.ids).all() for x, y in zip(a, again))


def test_closed_loop_pool_holds_the_same_lengths_block_by_block():
    mix = common.load_json("traffic", "score-closed.json")
    a = traffic.serve_items(mix, 50257, 2, 0.0)
    b = traffic.serve_items(mix, 50257, 2_147_483_659, 0.0)
    block = mix["stratify_block"]
    assert len(a) == len(b) == mix["pool_requests"]
    for k in range(0, 4 * block, block):
        assert sorted(len(i.ids) for i in a[k:k + block]) \
            == sorted(len(i.ids) for i in b[k:k + block])
    assert [len(i.ids) for i in a[:block]] \
        != [len(i.ids) for i in b[:block]]
    assert all(i.due_s is None and i.max_new_tokens == 1 for i in a)


def test_quantile_is_numpys():
    v = [3.0, 1.0, 4.0, 1.5, 9.0, 2.6]
    for q in (0.5, 0.9, 0.25):
        assert common.quantile(v, q) == pytest.approx(np.quantile(v, q))


def test_op_label():
    lab = trace_reduce.op_label
    assert lab("%copy.17 = bf16[2048,16,20,64]{3,2,1,0} copy(bf16[] %x)") \
        == "copy_bf16_2048_16_20_64_"
    assert lab("%flash_fwd_dropout.3 = (bf16[576,512,128]{2,1,0}, "
               "f32[576,512]{1,0}) custom-call(%a)") \
        == "flash_fwd_dropout_bf16_576_512_128_"
    assert lab("%all-reduce-done.5 = f32[768]{0} all-reduce-done(%s)") \
        == "all-reduce-done_f32_768_"
    assert trace_reduce.is_comm(lab(
        "%all-reduce-done.5 = f32[768]{0} all-reduce-done(%s)"))


def test_reduce_on_a_made_up_capture():
    """Interval arithmetic on numbers that can be checked by hand."""
    trace = {
        "device": {"/device:TPU:0": [
            ("a", 0.0, 1.0),          # before the window: clipped away
            ("a", 9.5, 11.0),         # half in: 1.0 s counts
            ("b", 11.0, 12.0),
            ("all-reduce-done_f32_4_", 13.0, 13.5),   # exposed
            ("c", 15.0, 21.0),        # 5.0 s inside
            ("while_s32__", 15.0, 19.0),   # holds c: busy, not an op
        ]},
        "host": [("bench:window", 10.0, 20.0),
                 ("bench:step_dispatch", 12.0, 12.6),
                 ("bench:wait_lagged_loss", 12.5, 15.0),
                 ("bench:inner", 13.6, 14.0)],
    }
    r = trace_reduce.reduce(trace)
    assert r["window_s"] == pytest.approx(10.0)
    assert r["busy_s"] == pytest.approx(1.0 + 1.0 + 0.5 + 5.0)
    assert r["per_op_s"]["a"] == pytest.approx(1.0)
    assert r["per_op_calls"]["a"] == 1
    assert "while_s32__" not in r["per_op_s"]
    assert r["comm_s"] == pytest.approx(0.5)
    assert r["comm_exposed_s"] == pytest.approx(0.5)
    gaps = dict(r["idle_gaps"])
    # idle: 12.0-13.0 and 13.5-15.0. 12.0-12.5 is under step_dispatch
    # alone; 12.5-12.6 under both, the shorter (dispatch) takes it;
    # 13.6-14.0 goes to the innermost span
    assert gaps["bench:step_dispatch"] == pytest.approx(0.6)
    assert gaps["bench:inner"] == pytest.approx(0.4)
    assert gaps["bench:wait_lagged_loss"] == pytest.approx(
        (13.0 - 12.6) + (15.0 - 13.5) - 0.4)
    assert sum(gaps.values()) == pytest.approx(10.0 - r["busy_s"])


@pytest.mark.skipif(
    not os.path.exists(os.path.join(DATA, "small.xplane.pb")),
    reason="no recorded capture beside the test")
def test_reduce_on_the_recorded_capture():
    """tests/data/small.xplane.pb was recorded on the v5e by
    tools/record_small_trace.py; small.expected.json holds what the
    reduction gave there, checked by hand against the capture's
    listing (describe_trace.py)."""
    with open(os.path.join(DATA, "small.expected.json")) as f:
        want = json.load(f)
    got = trace_reduce.reduce(trace_reduce.load(
        os.path.join(DATA, "small.xplane.pb")))
    assert got["chips"] == want["chips"]
    assert got["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < got["busy_s"] < got["window_s"]
    for name, secs in want["device_ops"]:
        assert got["per_op_s"][name] == pytest.approx(secs, rel=1e-9)
    assert [g[0] for g in got["idle_gaps"]] == [g[0] for g in
                                                want["idle_gaps"]]
