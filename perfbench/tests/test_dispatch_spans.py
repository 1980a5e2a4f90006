"""The readers of a dispatch's `ready` stamp and of the `step` spans'
`lanes` and `pages_live`, against a made-up ring and reduced trace whose
numbers are known by hand. Runs on the CPU, no jax:

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/tests -q
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.lib import common, dispatch_spans, step_spans  # noqa: E402
from perfbench.lib.trace_reduce import EMPTY  # noqa: E402

T_OPEN, T_CLOSE = 10.0, 20.0
WINDOW_S, BUSY_S = 8.0, 2.4          # the capture: [12, 20] on the ring
MAX_SLOTS, PAGES_TOTAL = 8, 100


def _step(n, t0, phases, ready=(), **counts):
    """One step's events as the program writes them: contiguous phases
    from `t0` on, then the step span with `counts`. `phases` is (name,
    seconds); the i-th `sync` carries `ready` = its start + `ready[i]`
    where `ready` has an i-th entry."""
    out = []
    t = t0
    syncs = 0
    for name, dur in phases:
        ev = {"rid": None, "comp": name, "t0": t, "t1": t + dur,
              "step": n, "replica": None, "parent": "step"}
        if name == "sync":
            if syncs < len(ready):
                ev["ready"] = t + ready[syncs]
            syncs += 1
        out.append(ev)
        t += dur
    out.append({"rid": None, "comp": "step", "t0": t0, "t1": t,
                "step": n, "replica": None, "executables": 4, **counts})
    return out


def _ring(with_ready=True):
    def r(*x):
        return x if with_ready else ()

    head = [("retire", 0.01), ("admit", 0.04), ("keys", 0.05)]
    evs = []
    # 0: before the window opens; counts that no reader may take
    evs += _step(0, 8.0, head + [("build", 0.1), ("dispatch", 0.1),
                                 ("sync", 0.5), ("accept", 0.1)],
                 r(0.3), lanes=7, pages_live=90)
    # 1: inside the window, before the capture: the capture's start at
    # 12.0 cuts its dispatch [11.1, 12.3] to 0.3 s and its fetch
    # [12.3, 12.5] stays whole
    evs += _step(1, 11.0, head + [("dispatch", 0.1), ("sync", 1.3),
                                  ("accept", 0.1)],
                 r(1.1), lanes=3, pages_live=10)
    # 2: two dispatches in one step, a prefill (no lanes of its own)
    # and a decode: [13.1, 13.8] + [14.1, 14.9] in flight, fetches 0.2
    # and 0.1 s
    evs += _step(2, 13.0, head + [("dispatch", 0.1), ("sync", 0.8),
                                  ("accept", 0.05), ("build", 0.05),
                                  ("dispatch", 0.1), ("sync", 0.8),
                                  ("accept", 0.1)],
                 r(0.6, 0.7), lanes=5, pages_live=40)
    # 3: a prefill alone: 0.5 s in flight, a fetch of 0.4 s; no lanes
    evs += _step(3, 16.0, head + [("dispatch", 0.1), ("sync", 0.8),
                                  ("accept", 0.1)],
                 r(0.4), pages_live=25)
    # 4: runs past the close, which cuts [19.1, 20.6] to 0.9 s in
    # flight and leaves nothing of its fetch [20.6, 21.1]
    evs += _step(4, 19.0, head + [("dispatch", 0.1), ("sync", 1.9),
                                  ("accept", 0.1)],
                 r(1.4), lanes=4, pages_live=30)
    # what else the ring holds: requests' spans and marks
    evs += [{"rid": "a", "comp": "decode", "t0": 14.05, "t1": 15.05,
             "replica": None, "bucket": 8, "chunk": 4, "tokens": 9,
             "tick": 2},
            {"rid": "a", "mark": "retire", "t": 17.0, "replica": None}]
    return [dict(e, i=i) for i, e in enumerate(evs)]


def _ctx(spans, trace=None):
    return {"trace": dict(EMPTY, window_s=WINDOW_S, busy_s=BUSY_S)
            if trace is None else trace,
            "bench": {"spans": spans, "t_open": T_OPEN, "t_close": T_CLOSE,
                      "max_slots": MAX_SLOTS, "pages_total": PAGES_TOTAL}}


EXPECTED = {
    # in flight 0.3 + 0.7 + 0.8 + 0.5 + 0.9 = 3.2 s, of which the device
    # ran 2.4: 0.8 of 8 s
    "inflight_idle_share": 10.0,
    # fetches 0.2 + 0.2 + 0.1 + 0.4 = 0.9 of 8 s
    "fetch_tail_share": 11.25,
    # steps 1, 2 and 4 decoded: (3 + 5 + 4) / 3 of 8 slots
    "step_lanes_live_share.chat": 50.0,
    "step_lanes_live_share.complete": 50.0,
    # 40 pages at step 2, of 100 (step 0's 90 is before the window)
    "step_pages_live_peak_share.chat": 40.0,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_the_hand_computed_number(name):
    got = common.metric_reader(name)(_ctx(_ring()))
    assert got == pytest.approx(EXPECTED[name])


def test_the_capture_window_is_mapped_back_from_the_close():
    assert dispatch_spans.captured_window(_ctx([])) == (12.0, 20.0)
    assert dispatch_spans.captured_window(_ctx([], EMPTY)) is None


def test_the_three_parts_add_up_to_the_captures_idle_share():
    """The host's share over the captured seconds (outside every
    dispatch-to-sync interval), the in-flight idle share and the fetch
    tail together are the device's idle share: 48.75 + 10 + 11.25 =
    100 - 100 x 2.4 / 8."""
    ring = _ring()
    lo, hi = dispatch_spans.captured_window(_ctx(ring))
    host = step_spans.host_gap_share(
        {"bench": {"spans": ring, "t_open": lo, "t_close": hi}})
    assert host == pytest.approx(48.75)
    parts = host + dispatch_spans.inflight_idle_share(_ctx(ring)) \
        + dispatch_spans.fetch_tail_share(_ctx(ring))
    assert parts == pytest.approx(100.0 - 100.0 * BUSY_S / WINDOW_S)


def test_two_dispatches_of_one_step_are_two_fetches():
    two = [s for s in step_spans.steps(_ctx(_ring()))
           if s["span"]["step"] == 2]
    assert dispatch_spans.fetches(two[0]) == [
        (pytest.approx(13.1), pytest.approx(13.8), pytest.approx(14.0)),
        (pytest.approx(14.1), pytest.approx(14.9), pytest.approx(15.0))]


def test_a_sync_without_ready_is_not_read():
    """The parent's `sync` carries no `ready`: both shares read None,
    and a `sync` without it among others that carry it is left out."""
    bare = _ring(with_ready=False)
    assert dispatch_spans.inflight_idle_share(_ctx(bare)) is None
    assert dispatch_spans.fetch_tail_share(_ctx(bare)) is None
    mixed = [dict(e) for e in _ring()]
    for e in mixed:
        if e.get("comp") == "sync" and e["step"] == 3:
            del e["ready"]
    # step 3's 0.5 s in flight and 0.4 s of fetch are no longer read
    assert dispatch_spans.inflight_idle_share(_ctx(mixed)) == \
        pytest.approx(100.0 * (2.7 - BUSY_S) / WINDOW_S)
    assert dispatch_spans.fetch_tail_share(_ctx(mixed)) == \
        pytest.approx(100.0 * 0.5 / WINDOW_S)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_program_without_the_fields_reads_none_not_zero(name):
    """The parent of the PR that added `ready`, `lanes` and
    `pages_live`; a ring with no step spans; a rehearsal's empty
    capture for the two shares."""
    reader = common.metric_reader(name)
    parent = [{k: v for k, v in e.items()
               if k not in ("ready", "lanes", "pages_live")}
              for e in _ring()]
    assert reader(_ctx(parent)) is None
    assert reader(_ctx([])) is None
    if name in ("inflight_idle_share", "fetch_tail_share"):
        assert reader(_ctx(_ring(), EMPTY)) is None


def test_every_new_reader_is_declared_for_its_cells():
    declared = {m["name"]: m for m in common.benchmark()["per_layer"]}
    cells = {
        "inflight_idle_share": ["gpt2-large.score",
                                "brumby-14b-base.complete"],
        "fetch_tail_share": ["gpt2-large.score",
                             "brumby-14b-base.complete"],
        "step_lanes_live_share.chat": ["gpt2-large.chat"],
        "step_lanes_live_share.complete": ["brumby-14b-base.complete"],
        "step_pages_live_peak_share.chat": ["gpt2-large.chat"],
    }
    assert set(cells) == set(EXPECTED)
    for name, workloads in cells.items():
        assert declared[name]["workloads"] == workloads
        assert declared[name]["unit"] == "%"
