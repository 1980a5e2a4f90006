"""The readers of the program's engine-step spans against a made-up
ring whose gaps are known by hand. Runs on the CPU, no jax:

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/tests -q
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.lib import common, step_spans  # noqa: E402

T_OPEN, T_CLOSE = 10.0, 20.0


def _step(n, t0, phases, executables):
    """One step's events as the program writes them: contiguous phases
    from `t0` on, then the step span. `phases` is (name, seconds)."""
    out = []
    t = t0
    for name, dur in phases:
        out.append({"rid": None, "comp": name, "t0": t, "t1": t + dur,
                    "step": n, "replica": None, "parent": "step"})
        t += dur
    out.append({"rid": None, "comp": "step", "t0": t0, "t1": t,
                "step": n, "replica": None,
                "executables": executables})
    return out


def _ring():
    head = [("retire", 0.01), ("admit", 0.01), ("keys", 0.01)]
    evs = []
    # 1: over before the window opens
    evs += _step(1, 8.0, head + [("build", 0.1), ("dispatch", 0.1),
                                 ("sync", 0.4), ("accept", 0.05),
                                 ("observe", 0.05)], 5)
    # 2: began before the opening, which cuts its in-flight interval
    # [9.6, 10.5] down to 0.5 s; the last step before the window
    evs += _step(2, 9.5, [("retire", 0.1), ("dispatch", 0.1),
                          ("sync", 0.8), ("observe", 0.1)], 5)
    # 3: one prefill. engine 0.19, cache 0.09, in flight 2.1
    evs += _step(3, 11.0, [("retire", 0.01), ("admit", 0.02),
                           ("keys", 0.03), ("alloc", 0.04),
                           ("build", 0.05), ("dispatch", 0.10),
                           ("sync", 2.0), ("accept", 0.06),
                           ("observe", 0.07)], 5)
    # 4: prefill, then decode. engine 0.15, cache 0.12, in flight
    # 1.2 + 2.3
    evs += _step(4, 14.0, head + [("alloc", 0.02), ("build", 0.03),
                                  ("dispatch", 0.2), ("sync", 1.0),
                                  ("accept", 0.05), ("build", 0.07),
                                  ("dispatch", 0.3), ("sync", 2.0),
                                  ("accept", 0.05), ("observe", 0.02)],
                 6)
    # 5: began inside, runs past the close, which cuts [19.1, 21.0]
    # down to 0.9 s. engine 0.23, cache 0.07
    evs += _step(5, 19.0, head + [("build", 0.07), ("dispatch", 0.4),
                                  ("sync", 1.5), ("accept", 0.1),
                                  ("observe", 0.1)], 7)
    # what else the ring holds: requests' spans and marks
    evs += [{"rid": "a", "comp": "prefill", "t0": 11.06, "t1": 13.25,
             "replica": None, "bucket": 128, "width": 4, "tick": 3},
            {"rid": "a", "mark": "retire", "t": 14.0, "replica": None},
            {"rid": "a", "mark": "dispatch", "t": 10.9, "replica": None}]
    return [dict(e, i=i) for i, e in enumerate(evs)]


def _ctx(spans):
    return {"bench": {"spans": spans, "t_open": T_OPEN,
                      "t_close": T_CLOSE}}


EXPECTED = {
    # held 0.5 + 2.1 + 1.2 + 2.3 + 0.9 = 7.0 of 10 s
    "engine_host_gap_share": 30.0,
    # steps 3, 4, 5: 190, 150, 230 ms
    "engine_phases_host_ms_p50": 190.0,
    # 90, 120, 70 ms
    "cache_phases_host_ms_p50": 90.0,
    # 100, 200, 300, 400 ms
    "dispatch_call_ms_p50": 250.0,
    # 7 at step 5, 5 at step 2
    "recompiles_in_window": 2.0,
}
NAMES = [f"{n}.{cell}" for n in EXPECTED for cell in ("chat", "score")]


@pytest.mark.parametrize("name", NAMES)
def test_reader_gives_the_hand_computed_number(name):
    got = common.metric_reader(name)(_ctx(_ring()))
    assert got == pytest.approx(EXPECTED[name.rsplit(".", 1)[0]])


@pytest.mark.parametrize("name", NAMES)
def test_no_step_spans_reads_none_not_zero(name):
    """A program that writes none (the parent): only the requests'
    spans are in the ring."""
    only_requests = [e for e in _ring() if e["rid"] is not None]
    assert only_requests
    assert common.metric_reader(name)(_ctx(only_requests)) is None
    assert common.metric_reader(name)(_ctx([])) is None


def test_every_new_reader_is_declared_and_only_additions():
    declared = {m["name"]: m for m in common.benchmark()["per_layer"]}
    for name in NAMES:
        m = declared[name]
        cell = "gpt2-large." + name.rsplit(".", 1)[1]
        assert m["workloads"] == [cell] and m["better"] == "lower"


def test_a_dispatch_nothing_fetched_runs_into_the_next_interval():
    step = step_spans.steps(_ctx(_step(
        9, 1.0, [("build", 0.1), ("dispatch", 0.2), ("build", 0.1),
                 ("dispatch", 0.2), ("sync", 1.0), ("accept", 0.1)],
        3)))[0]
    assert step_spans.in_flight(step) == [
        (pytest.approx(1.1), pytest.approx(2.6))]


def test_no_step_before_the_window_counts_from_its_first_step():
    ring = [e for e in _ring() if e.get("step") not in (1, 2)]
    assert step_spans.recompiles(_ctx(ring)) == 2.0     # 7 - 5
