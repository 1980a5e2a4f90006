"""`state_token_steps_per_write.complete` on made-up `step` and `decode`
spans, and `retention_decode_roofline.complete` on made-up calls of a
decode program that writes its rows every token-step and of one that
writes them once a chunk. Runs on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/tests -q
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.lib import common, retention  # noqa: E402

T_OPEN, T_CLOSE = 10.0, 20.0
BANDWIDTH = 819e9
OP = "retention_decode_f32_32_8_8_128_"
CONFIG = {"head_dim": 128, "num_key_value_heads": 8, "num_hidden_layers": 4}
READER = "state_token_steps_per_write.complete"


def _dispatch(step, t0, n_live, writes_per_dispatch, chunk=4, field=True):
    """One engine step that decodes: its `step` span and a `decode` span
    a live lane."""
    ev = {"rid": None, "comp": "step", "t0": t0, "t1": t0 + 0.13,
          "step": step, "replica": None, "executables": 4,
          "state_rows_live": n_live}
    if field:
        ev["state_row_writes"] = n_live * 4 * writes_per_dispatch
    return [ev] + [
        {"rid": f"r{step}.{i}", "comp": "decode", "t0": t0 + 0.05,
         "t1": t0 + 0.12, "bucket": 32, "chunk": chunk, "tokens": 100 + i,
         "replica": None, "tick": step} for i in range(n_live)]


def _ctx(spans, op_seconds=0.0, op_calls=0):
    trace = {"per_op_s": {}, "per_op_calls": {}, "window_s": 8.0}
    if op_calls:
        trace["per_op_s"][OP] = op_seconds
        trace["per_op_calls"][OP] = op_calls
    return {"trace": trace, "config": CONFIG,
            "peaks": {"hbm_bytes_per_s": BANDWIDTH},
            "bench": {"spans": spans, "t_open": T_OPEN, "t_close": T_CLOSE,
                      "pages_total": 32}}


def _window(writes_per_dispatch, field=True):
    """A dispatch before the window opened, three inside it (32, 31
    and 30 live), and a step inside that only admits."""
    spans = _dispatch(1, 9.0, 7, writes_per_dispatch, field=field)
    for n, (t0, live) in enumerate([(11.0, 32), (12.0, 31), (13.0, 30)]):
        spans += _dispatch(n + 2, t0, live, writes_per_dispatch,
                           field=field)
    spans.append({"rid": None, "comp": "step", "t0": 14.0, "t1": 14.05,
                  "step": 5, "replica": None, "executables": 4,
                  "state_rows_live": 30})
    return spans


@pytest.mark.parametrize("writes_per_dispatch,reads", [(1, 4.0), (4, 1.0),
                                                       (2, 2.0)])
def test_token_steps_per_write(writes_per_dispatch, reads):
    """Chunks of 4 written once read 4.0; a program that writes every
    token-step reads 1.0."""
    reader = common.metric_reader(READER)
    assert reader(_ctx(_window(writes_per_dispatch))) == pytest.approx(reads)


def test_a_program_without_the_field_reads_nothing():
    """The parent of the PR that added `state_row_writes`: None, and no
    exception; nor from a window with no step spans at all."""
    reader = common.metric_reader(READER)
    assert reader(_ctx(_window(1, field=False))) is None
    assert reader(_ctx([])) is None


@pytest.mark.parametrize("writes_per_dispatch", [4, 1])
def test_roofline_of_either_program_stays_under_100(writes_per_dispatch):
    """The kernel's calls at the chip's bandwidth, moving the bytes the
    program's layout holds (8,320 state rows a head where 8,256 are
    counted, and 32 lanes where 31 are live): a call that reads and
    writes its rows moves twice the read, a read-only call once. Four
    calls a chunk, `writes_per_dispatch` of them writing: the reading
    stays under 100% (about 47% and 76%)."""
    live = 31
    spans = _window(writes_per_dispatch)
    spans = [ev for ev in spans if ev.get("comp") != "decode"] + \
        [ev for ev in _dispatch(2, 11.0, live, writes_per_dispatch)
         if ev.get("comp") == "decode"]
    held = 32 * 8 * (65 * 128 * 128 + 72 * 128) * 4
    per_chunk = (4 * held + writes_per_dispatch * held) / BANDWIDTH
    calls = 4 * 4 * 100                           # 4 layers, 100 chunks
    ctx = _ctx(spans, 100 * 4 * per_chunk, calls)
    share = common.metric_reader("retention_decode_roofline.complete")(ctx)
    assert share == pytest.approx(
        100 * live * retention.state_bytes_per_slot(CONFIG) / BANDWIDTH
        / (4 * per_chunk / 16))
    assert 40 < share < 100
    assert (share > 50) == (writes_per_dispatch == 1)
