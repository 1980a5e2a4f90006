"""`retention_decode_roofline.complete` and its neighbours on a made-up
reduced trace and ring: a known byte count over a known time; and the
new cell's CPU rehearsal, driven to its end. Runs on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/tests -q
"""
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.lib import common, retention  # noqa: E402

T_OPEN, T_CLOSE = 10.0, 20.0
BANDWIDTH = 819e9
OP = "retention_decode_f32_32_8_8_128_"
PUBLISHED = {"head_dim": 128, "num_key_value_heads": 8}


def test_state_bytes_are_the_symmetric_form_s():
    """D = 128 x 129 / 2 = 8,256 features; S [D, 128] and z [D] in
    float32 for each of 8 key-value heads: 34.08 MB a slot and layer,
    as ISSUE 35 states it. Not the 65 x 128 = 8,320 rows the program's
    layout holds, and not the full outer product's 16,384."""
    assert retention.state_features(PUBLISHED) == 8256
    assert retention.state_bytes_per_slot(PUBLISHED) == \
        8 * (8256 * 128 + 8256) * 4 == 34_080_768
    assert retention.state_features({"head_dim": 16}) == 136
    assert retention.state_bytes_per_slot(
        {"head_dim": 16, "num_key_value_heads": 2}) == 2 * 136 * 17 * 4


def _decode(tick, t0, n_live):
    """One dispatch: a `decode` span a live slot, all with one start."""
    return [{"rid": f"r{tick}.{i}", "comp": "decode", "t0": t0,
             "t1": t0 + 0.08, "bucket": 32, "chunk": 4, "tokens": 100 + i,
             "replica": None, "tick": tick} for i in range(n_live)]


def _step(n, t0, rows):
    ev = {"rid": None, "comp": "step", "t0": t0, "t1": t0 + 0.1, "step": n,
          "replica": None, "executables": 4}
    if rows is not None:
        ev["state_rows_live"] = rows
    return ev


# two dispatches inside the window, 32 and 24 live (mean 28), one
# before it opened
SPANS = _decode(1, 9.0, 5) + _decode(2, 11.0, 32) + _decode(3, 12.0, 24)
LEAST_S = 28 * 34_080_768 / BANDWIDTH


def _ctx(op_seconds, op_calls, spans, window_s=8.0, config=PUBLISHED):
    trace = {"per_op_s": {"fusion_bf16_32_17408_": 0.2},
             "per_op_calls": {"fusion_bf16_32_17408_": 144},
             "window_s": window_s}
    if op_calls:
        trace["per_op_s"][OP] = op_seconds
        trace["per_op_calls"][OP] = op_calls
    return {"trace": trace, "config": config,
            "peaks": {"hbm_bytes_per_s": BANDWIDTH},
            "bench": {"spans": spans, "t_open": T_OPEN, "t_close": T_CLOSE,
                      "pages_total": 32}}


@pytest.mark.parametrize("factor,share", [(1.0, 100.0), (2.0, 50.0),
                                          (2.53, 100.0 / 2.53)])
def test_roofline_is_the_state_read_once_over_the_kernels_time(factor,
                                                               share):
    """A kernel that moves `factor` times the live slots' state at the
    full bandwidth: one that writes every row back each step moves
    twice the count and reads 50%."""
    ctx = _ctx(640 * LEAST_S * factor, 640, SPANS)
    assert retention.live_slots_mean(ctx) == 28
    assert retention.roofline(ctx) == pytest.approx(share)
    reader = common.metric_reader("retention_decode_roofline.complete")
    assert reader(ctx) == pytest.approx(share)


def test_device_share_is_the_kernels_time_of_the_window():
    ctx = _ctx(3.2, 640, SPANS)
    assert retention.device_share(ctx) == pytest.approx(40.0)
    reader = common.metric_reader("retention_decode_device_share.complete")
    assert reader(ctx) == pytest.approx(40.0)


def test_rows_live_share_reads_the_step_spans():
    spans = SPANS + [_step(1, 9.5, 3), _step(2, 11.0, 32), _step(3, 12.0, 30)]
    ctx = _ctx(3.2, 640, spans)
    assert retention.rows_live_share(ctx) == pytest.approx(100 * 31 / 32)
    reader = common.metric_reader("state_rows_live_share.complete")
    assert reader(ctx) == pytest.approx(100 * 31 / 32)


def test_a_program_without_the_kernel_or_the_field_reads_nothing():
    """The parent of the PR that added them: no op of that name, no
    `state_rows_live` on its step spans. None, and no exception."""
    spans = SPANS + [_step(2, 11.0, None)]
    ctx = _ctx(0.0, 0, spans)
    assert retention.roofline(ctx) is None
    assert retention.device_share(ctx) is None
    assert retention.rows_live_share(ctx) is None
    assert retention.roofline(_ctx(3.2, 640, [])) is None   # no spans
    ctx = _ctx(3.2, 640, SPANS)
    ctx["peaks"] = None                                     # a rehearsal
    assert retention.roofline(ctx) is None


def test_the_new_cell_s_rehearsal_runs_to_its_end(capfd):
    """`--rehearse-on-cpu` of `brumby-14b-base.complete`: toy widths,
    the same control flow, a traced run's readers over the program's
    own records; `correct` under the cell's own limit."""
    from perfbench import run
    rc = run.main(["--workload", "brumby-14b-base.complete", "--seed",
                   "2147483659", "--seconds", "2", "--trace", "1",
                   "--rehearse-on-cpu"])
    assert rc == 0
    err = capfd.readouterr().err
    assert re.findall(r"perfbench correct=(true|false)", err)[-1] == "true"
    said = re.search(r"with metrics (\[.*\])", err).group(1)
    for name in ("state_rows_live_share.complete",
                 "decode_batch_occupancy.complete",
                 "prefill_bucket_fill.complete",
                 "decode_chunk_host_ms_p50.complete",
                 "prefill_host_ms_p50.complete",
                 "engine_host_gap_share.complete",
                 "recompiles_in_window.complete", "compile_cache_hits"):
        assert f"'{name}'" in said, (name, said)


def test_an_altered_token_is_not_correct_in_the_new_cell(monkeypatch, capfd):
    """The one fault a serving cell can have, planted where the token
    is produced: `correct` comes out false under the cell's own limit
    (test_broken_path.py does the same for the cells it lists)."""
    from paddle_tpu.serving import programs
    from perfbench import run
    orig = programs._pick
    monkeypatch.setattr(
        programs, "_pick",
        lambda logits, *a, **k: (orig(logits, *a, **k) + 1)
        % logits.shape[-1])
    assert run.main(["--workload", "brumby-14b-base.complete", "--seed",
                     "11", "--seconds", "1", "--trace", "0",
                     "--rehearse-on-cpu"]) == 0
    err = capfd.readouterr().err
    assert re.findall(r"perfbench correct=(true|false)", err)[-1] == "false"
