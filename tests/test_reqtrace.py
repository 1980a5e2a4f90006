"""Request anatomy (observability.reqtrace): the serving fleet's
per-request span plane.

Receipts pinned here:
- cost discipline: a DISABLED record_span()/mark() stays under ~1 µs
  (the flight-recorder bar — the span sites live in the serving token
  boundaries permanently);
- attribution math: per-request latency components are clipped,
  union-merged, and sum to 1.0 with "other" as the explicit closure;
  explain_tail picks the p-th percentile cohort and aggregates by
  component SECONDS;
- trace-export determinism: the same deterministic trace through two
  fresh engines yields the same span structure (components, buckets,
  order) — timestamps differ, anatomy does not;
- BurnMeter: burn rate = breach_fraction / error_budget per rolling
  window, -1 on no data, multi-window alert only when EVERY window
  burns past the bar;
- serving_breach_verdict priorities: replica death (kill > covert
  stall) > recompile > overload shed > swap flip > dominant component;
- engine steps: every working step is one ``step`` span over
  contiguous, ordered phases that share its ``step``; a ``sync``'s
  ``ready`` lies inside it; a step's ``lanes`` are the requests its
  decode dispatch carried and its ``pages_live`` the cache's live pages
  at its close; the requests' spans keep the fields and stamps they
  had; step events stay out of the request readers; one ``serve:``
  annotation per ring phase, and a ``serve:fetch`` inside each
  ``sync``. Structure only: no duration is asserted.
"""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.observability import reqtrace as rt
from paddle_tpu.serving import ServingConfig, ServingEngine
from tools.tpu_doctor import serving_breach_verdict


@pytest.fixture(autouse=True)
def _clean_tracer():
    rt.reset()
    yield
    rt.disable()
    rt.reset()


@pytest.fixture(scope="module")
def model():
    paddle.seed(7)
    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                    num_heads=4, max_seq_len=64, dropout=0.0,
                    use_flash_attention=False)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def engine_config(**kw):
    base = dict(max_slots=4, max_admit=2, block_size=4, n_blocks=48,
                prefill_buckets=(8, 16), max_total_tokens=24,
                decode_chunk=2, dtype=None)
    base.update(kw)
    return ServingConfig(**base)


# -- cost discipline ----------------------------------------------------------

def test_disabled_record_under_one_microsecond():
    """CI guard (the flight-recorder harness): span sites are wired
    into the serving token boundaries unconditionally; with tracing
    off one call must stay under ~1 µs median."""
    assert not rt.enabled()
    n = 10000
    medians = []
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n):
            rt.record_span(1, "decode", 0.0, 1.0, replica=0)
        medians.append((time.perf_counter() - t0) / n)
    med = sorted(medians)[len(medians) // 2]
    assert med < 1e-6, f"disabled record_span costs {med * 1e9:.0f}ns"
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n):
            rt.mark(1, "retire")
        medians.append((time.perf_counter() - t0) / n)
    med = sorted(medians)[len(medians) // 2]
    assert med < 1e-6, f"disabled mark costs {med * 1e9:.0f}ns"
    assert rt.get_tracer().events() == []   # and stored nothing


def test_disabled_step_helpers_under_one_microsecond():
    """The step writer's sites are in ``ServingEngine.step()`` for
    good: off, ``open_step`` is a bool read and hands out ``NO_STEP``,
    whose ``phase``, ``ready`` and ``close`` are empty methods."""
    assert not rt.enabled()
    n = 10000
    off = rt.open_step(1, 0)
    assert off is rt.NO_STEP
    for name, call in (("open_step", lambda: rt.open_step(1, 0)),
                       ("phase", lambda: off.phase("build", "decode")),
                       ("ready", off.ready),
                       ("close", lambda: off.close(3))):
        medians = []
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(n):
                call()
            medians.append((time.perf_counter() - t0) / n)
        med = sorted(medians)[len(medians) // 2]
        assert med < 1e-6, f"disabled {name} costs {med * 1e9:.0f}ns"
    assert rt.get_tracer().events() == []


def test_ring_wraps_newest_wins_and_reset():
    rt.enable(capacity=8)
    try:
        for i in range(20):
            rt.record_span(i, "decode", float(i), float(i + 1))
        evs = rt.get_tracer().events()
        assert len(evs) == 8
        assert [e["rid"] for e in evs] == list(range(12, 20))
        rt.reset()
        assert rt.get_tracer().events() == []
    finally:
        rt.enable(capacity=rt._DEFAULT_CAPACITY)


# -- attribution math ---------------------------------------------------------

def test_attribution_components_sum_to_one_with_closure():
    rt.enable()
    rt.mark("r", "submit", t=10.0)
    rt.record_span("r", "queue", 10.0, 12.0)
    rt.record_span("r", "prefill", 12.0, 13.0)
    # overlapping decode dispatches must union-merge, not double-count
    rt.record_span("r", "decode", 13.0, 15.0)
    rt.record_span("r", "decode", 14.0, 16.0)
    rt.mark("r", "retire", t=20.0)
    tl = rt.timelines()["r"]
    att = rt.attribute(tl)
    c = att["components"]
    assert att["wall_ms"] == pytest.approx(10000.0)
    assert c["queue"] == pytest.approx(0.2)
    assert c["prefill"] == pytest.approx(0.1)
    assert c["decode"] == pytest.approx(0.3)
    assert c["other"] == pytest.approx(0.4)
    assert att["share_sum"] == pytest.approx(1.0)
    assert att["dominant"] == "other"


def test_attribution_clips_spans_to_wall_window():
    rt.enable()
    rt.mark("r", "submit", t=10.0)
    rt.record_span("r", "queue", 8.0, 12.0)     # 2s before arrival
    rt.record_span("r", "decode", 13.0, 25.0)   # runs past done
    rt.mark("r", "retire", t=20.0)
    att = rt.attribute(rt.timelines()["r"])
    assert att["components"]["queue"] == pytest.approx(0.2)
    assert att["components"]["decode"] == pytest.approx(0.7)
    assert att["share_sum"] == pytest.approx(1.0)


def test_explain_tail_cohort_and_incident_evidence():
    rt.enable()
    # fast request: decode-bound; slow request: queue-bound
    rt.mark("fast", "submit", t=0.0)
    rt.record_span("fast", "decode", 0.0, 1.0, replica=0)
    rt.mark("fast", "retire", t=1.0)
    rt.mark("slow", "submit", t=0.0)
    rt.record_span("slow", "queue", 0.0, 8.0, replica=1)
    rt.record_span("slow", "decode", 8.0, 10.0, replica=1)
    rt.mark("slow", "retire", t=10.0)
    rt.mark("slow", "evict", t=5.0, replica=1, kind="crash")
    rt.mark("other", "shed")
    tail = rt.explain_tail(p=99.0)
    assert tail["requests"] == 2
    assert [c["rid"] for c in tail["cohort"]] == ["slow"]
    assert tail["cohort"][0]["dominant"] == "queue"
    assert tail["cohort"][0]["replicas"] == [1]
    assert tail["dominant_overall"] == "queue"
    assert tail["cohort_components"]["queue"] == pytest.approx(0.8)
    assert tail["evictions"] == [
        {"rid": "slow", "replica": 1, "kind": "crash", "t": 5.0}]
    assert tail["shed"] == 1
    # p=0: every request is cohort, slowest first
    tail0 = rt.explain_tail(p=0.0)
    assert [c["rid"] for c in tail0["cohort"]] == ["slow", "fast"]


# -- chrome export ------------------------------------------------------------

def test_chrome_trace_events_lanes_and_colors():
    rt.enable()
    rt.record_span("a", "decode", 1.0, 2.0, replica=1, tick=3)
    rt.record_span("b", "requeue", 2.0, 3.0, replica=0,
                   replica_from=1, kind="crash")
    rt.mark("a", "retire", t=2.5, replica=1)
    evs = rt.chrome_trace_events()
    spans = [e for e in evs if e["ph"] == "X"]
    assert {e["tid"] for e in spans} == {0, 1}
    dec = next(e for e in spans if e["name"] == "decode:a")
    assert dec["ts"] == pytest.approx(1e6)
    assert dec["dur"] == pytest.approx(1e6)
    assert dec["cname"] == "good"
    assert dec["args"]["tick"] == 3
    req = next(e for e in spans if e["name"] == "requeue:b")
    assert req["cname"] == "terrible"
    assert any(e["ph"] == "i" and e["name"] == "retire:a"
               for e in evs)
    names = {e["args"]["name"] for e in evs if e["ph"] == "M"}
    assert names == {"serving replica 0", "serving replica 1"}


def test_export_chrome_tracing_merges_request_lanes(tmp_path):
    import json
    from paddle_tpu import profiler
    rt.enable()
    rt.record_span("a", "prefill", 1.0, 2.0, replica=0, bucket=16)
    out = profiler.export_chrome_tracing(str(tmp_path / "t.json"))
    with open(out) as f:
        data = json.load(f)
    assert any(e.get("cat") == "reqtrace"
               and e.get("name") == "prefill:a"
               for e in data["traceEvents"])
    # and OFF means off: no lanes in a fresh export
    rt.disable()
    out2 = profiler.export_chrome_tracing(str(tmp_path / "t2.json"))
    with open(out2) as f:
        data2 = json.load(f)
    assert not any(e.get("cat") == "reqtrace"
                   for e in data2["traceEvents"])


# -- burn meter ---------------------------------------------------------------

class TestBurnMeter:
    def test_rates_per_window_and_no_data(self):
        bm = rt.BurnMeter(budget=0.01, windows=(5.0, 60.0))
        assert bm.rates(now=100.0) == {5.0: -1.0, 60.0: -1.0}
        assert not bm.alert(now=100.0)      # no data is not a burn
        # 50 old requests, 1 breach: only the slow window burns
        for i in range(50):
            bm.record(41.0 + i * 0.1, breached=(i == 0))
        # fast window (95..100): 10 clean finishes
        for i in range(10):
            bm.record(95.0 + i * 0.4, breached=False)
        r = bm.rates(now=100.0)
        assert r[5.0] == pytest.approx(0.0)
        assert r[60.0] == pytest.approx((1 / 60) / 0.01)
        assert not bm.alert(now=100.0)      # fast window is clean

    def test_multiwindow_alert_needs_every_window_burning(self):
        bm = rt.BurnMeter(budget=0.1, windows=(5.0, 60.0),
                          alert_rate=1.0)
        # sustained 50% breach rate -> burn 5x in both windows
        for i in range(60):
            bm.record(40.0 + i, breached=(i % 2 == 0))
        assert bm.rates(now=100.0)[5.0] > 1.0
        assert bm.rates(now=100.0)[60.0] > 1.0
        assert bm.alert(now=100.0)
        # a quiet fast window clears the page even while the slow
        # window still carries the incident
        for i in range(20):
            bm.record(100.0 + i * 0.2, breached=False)
        assert not bm.alert(now=104.0)

    def test_events_pruned_beyond_slowest_window(self):
        bm = rt.BurnMeter(budget=0.01, windows=(1.0, 10.0))
        for i in range(1000):
            bm.record(float(i), breached=False)
        assert len(bm._events) < 20


# -- serving breach verdict priorities ---------------------------------------

def _tail(dominant="queue", comps=None, cohort=1, **kw):
    t = {"p": 99.0, "requests": 4, "threshold_ms": 50.0,
         "cohort": [{"rid": "r", "e2e_ms": 50.0, "dominant": dominant,
                     "share_sum": 1.0, "components": comps or {},
                     "replicas": []}] * cohort,
         "dominant_overall": dominant,
         "cohort_components": comps or {dominant: 0.9, "other": 0.1},
         "evictions": [], "shed": 0, "swap_flips": 0}
    t.update(kw)
    return t


class TestServingBreachVerdict:
    def test_eviction_outranks_everything(self):
        tail = _tail(dominant="decode",
                     evictions=[{"rid": "a", "replica": 2,
                                 "kind": "crash", "t": 1.0}],
                     shed=5, swap_flips=3)
        v = serving_breach_verdict(
            tail, summary={"recompile_events": 9})
        assert v["cause"] == "replica_kill"
        assert v["replica"] == 2
        assert v["component"] == "requeue"

    def test_hang_eviction_is_covert_stall(self):
        tail = _tail(evictions=[{"rid": "a", "replica": 1,
                                 "kind": "hang", "t": 1.0}])
        v = serving_breach_verdict(tail)
        assert v["cause"] == "covert_stall"
        assert v["replica"] == 1

    def test_kill_outranks_stall_on_same_replica(self):
        tail = _tail(evictions=[
            {"rid": "a", "replica": 1, "kind": "hang", "t": 1.0},
            {"rid": "b", "replica": 1, "kind": "crash", "t": 2.0}])
        assert serving_breach_verdict(tail)["cause"] == "replica_kill"

    def test_recompile_next(self):
        v = serving_breach_verdict(
            _tail(), summary={"recompile_events": 2})
        assert v["cause"] == "recompile"

    def test_overload_shed_then_swap_then_dominant(self):
        assert serving_breach_verdict(
            _tail(dominant="queue", shed=3))["cause"] == \
            "overload_shed"
        v = serving_breach_verdict(
            _tail(dominant="swap_flip", swap_flips=2))
        assert v["cause"] == "swap_flip"
        assert serving_breach_verdict(
            _tail(dominant="prefill"))["cause"] == "slow_prefill"
        assert serving_breach_verdict(
            _tail(dominant="decode"))["cause"] == "slow_decode"

    def test_clean_trace_is_none(self):
        v = serving_breach_verdict(_tail(cohort=0, dominant=None))
        assert v["cause"] == "none"


# -- live engine: span structure + determinism -------------------------------

def _run_traced(model, rids):
    """One fresh engine over a FIXED request set; returns the
    per-request (component, bucket) sequences."""
    eng = ServingEngine(model, engine_config()).warmup()
    rng = np.random.RandomState(0)
    specs = [(3, 4), (7, 6), (5, 5), (12, 4)]
    prompts = [rng.randint(0, 97, (L,)).astype(np.int32)
               for L, _ in specs]
    rt.reset()
    for rid, p, (_, n) in zip(rids, prompts, specs):
        eng.submit(p, n, rid=rid, arrival=time.perf_counter())
    eng.run_to_completion()
    tls = rt.timelines()
    seqs = {}
    for rid in rids:
        seqs[rid] = [(s["comp"], s.get("bucket"))
                     for s in tls[rid]["spans"]]
    return seqs, tls


def test_engine_spans_and_export_determinism(model):
    """Two fresh engines over the same deterministic request set emit
    the SAME span anatomy (components, buckets, order); every request
    attributes to shares summing to ~1.0."""
    rt.enable()
    rids = ["q0", "q1", "q2", "q3"]
    seqs_a, tls = _run_traced(model, rids)
    for rid in rids:
        tl = tls[rid]
        marks = [m["mark"] for m in tl["marks"]]
        assert marks[0] == "submit" and marks[-1] == "retire"
        assert "dispatch" in marks
        comps = {s["comp"] for s in tl["spans"]}
        assert {"admission", "prefill", "decode"} <= comps
        att = rt.attribute(tl)
        assert abs(att["share_sum"] - 1.0) <= 0.02
        # the prefill's width is the packed row's that holds the admit
        # batch (widths 16 and 32 for buckets 8 and 16, two admits)
        pf = [s for s in tl["spans"] if s["comp"] == "prefill"]
        assert len(pf) == 1 and pf[0]["bucket"] in (16, 32)
    seqs_b, _ = _run_traced(model, rids)
    assert seqs_a == seqs_b


# -- engine steps: the step span and its phases --------------------------------

def _steps_of(evts):
    """{step: (step span, [phase spans in ring order])}."""
    out = {}
    for e in evts:
        if e.get("comp") == "step":
            out.setdefault(e["step"], [None, []])[0] = e
        elif e.get("parent") == "step":
            out.setdefault(e["step"], [None, []])[1].append(e)
    return out


def _drive(eng, specs=((3, 4), (7, 6), (5, 5), (12, 4)), seed=0):
    """The fixed request set of `_run_traced` through `eng`, one
    step() at a time; returns how many steps had work."""
    rng = np.random.RandomState(seed)
    for i, (L, n) in enumerate(specs):
        eng.submit(rng.randint(0, 97, (L,)).astype(np.int32), n,
                   rid=f"q{i}")
    working = 0
    while eng.has_work():
        working += 1
        eng.step()
    return working


def _engine(model, lever):
    if lever == "speculative":
        return ServingEngine(model, engine_config(speculative_k=2),
                             draft_model=model).warmup()
    kw = {"prefix_sharing": True} if lever == "prefix_sharing" else {}
    return ServingEngine(model, engine_config(**kw)).warmup()


@pytest.mark.parametrize("lever", ["plain", "prefix_sharing",
                                   "speculative"])
def test_every_working_step_is_one_step_span_over_ordered_phases(
        model, lever):
    rt.enable()
    eng = _engine(model, lever)
    rt.reset()
    working = _drive(eng)
    steps = _steps_of(rt.get_tracer().events())
    assert len(steps) == working
    order = {n: i for i, n in enumerate(rt.STEP_PHASES)}
    kinds_seen = set()
    for n, (whole, phases) in steps.items():
        assert whole is not None and whole["rid"] is None
        decodes = any(p.get("kind") in ("decode", "verify")
                      for p in phases)
        assert set(whole) == {"i", "rid", "comp", "t0", "t1", "step",
                              "replica", "executables", "pages_live"} \
            | ({"lanes"} if decodes else set())
        names = [p["comp"] for p in phases]
        assert names[:3] == ["retire", "admit", "keys"]
        assert names[-1] == "observe" and names.count("observe") == 1
        # per dispatch: [alloc] build dispatch [sync accept], in the
        # stated order; a new `build` (or `alloc`) starts the next one
        body = names[3:-1]
        for a, b in zip(body, body[1:]):
            assert order[b] > order[a] or b in ("alloc", "build"), names
        assert body.count("build") == body.count("dispatch") >= \
            body.count("sync") >= body.count("accept")
        # contiguous, inside the step, sharing its `step`
        assert phases[0]["t0"] >= whole["t0"]
        assert phases[-1]["t1"] == whole["t1"]
        for a, b in zip(phases, phases[1:]):
            assert a["t1"] == b["t0"]
        for ph in phases:
            assert ph["step"] == n and ph["t1"] >= ph["t0"]
            fields = {"i", "rid", "comp", "t0", "t1", "step", "replica",
                      "parent"}
            if ph["comp"] in ("build", "dispatch", "sync", "accept"):
                fields.add("kind")
                kinds_seen.add(ph["kind"])
            if ph["comp"] == "sync":
                fields.add("ready")
                assert ph["t0"] <= ph["ready"] <= ph["t1"]
            assert set(ph) == fields
    assert kinds_seen == ({"draft", "verify", "prefill"}
                          if lever == "speculative"
                          else {"prefill", "decode"})
    # the count of a step is taken at its own boundary
    assert all(w["executables"] == eng.executable_count()
               for w, _ in steps.values())


def test_request_spans_keep_their_fields_and_stamps(model):
    """`admission`, `prefill` and `decode` spans are field for field
    what the engine wrote before the step spans existed (rid, t0, t1,
    bucket, width|chunk, replica, tick); their stamps are the
    phases' of the step their `tick` names."""
    rt.enable()
    eng = ServingEngine(model, engine_config()).warmup()
    rt.reset()
    _drive(eng)
    evts = rt.get_tracer().events()
    steps = _steps_of(evts)
    base = {"i", "rid", "comp", "t0", "t1", "replica", "tick", "bucket"}
    admits = [ph for _, phases in steps.values() for ph in phases
              if ph["comp"] == "admit"]
    n_prefill = n_decode = n_admission = 0
    prompt = {"q0": 3, "q1": 7, "q2": 5, "q3": 12}
    seen = dict.fromkeys(prompt, 0)
    for e in evts:
        if e.get("comp") == "admission":
            n_admission += 1
            assert set(e) == {"i", "rid", "comp", "t0", "t1"}
            assert sum(ph["t0"] <= e["t1"] <= ph["t1"]
                       for ph in admits) == 1
        if e.get("comp") not in ("prefill", "decode"):
            continue
        assert e["replica"] is None
        phases = {}
        for ph in steps[e["tick"]][1]:
            if ph.get("kind") == e["comp"]:
                phases.setdefault(ph["comp"], ph)
            elif ph["comp"] == "alloc":
                phases["alloc"] = ph
        if e["comp"] == "prefill":
            n_prefill += 1
            assert set(e) == base | {"width"}
            # two to a batch, packed into one row from page boundaries
            # (pages of 4): 4 + 8 rows in a width of 16, 8 + 12 in 32
            assert (e["bucket"], e["width"]) == (
                {"q0": 16, "q1": 16, "q2": 32, "q3": 32}[e["rid"]], 1)
            assert e["t0"] == phases["alloc"]["t0"]
            assert e["t1"] == phases["sync"]["t1"]
        else:
            n_decode += 1
            assert set(e) == base | {"chunk", "tokens"}
            assert (e["bucket"], e["chunk"]) == (4, 2)
            # what the request held at the dispatch: its prompt, and
            # a chunk more with every decode before this one
            assert e["tokens"] == prompt[e["rid"]] + 2 * seen[e["rid"]]
            seen[e["rid"]] += 1
            assert e["t0"] == phases["build"]["t0"]
            assert phases["accept"]["t0"] <= e["t1"] <= \
                phases["accept"]["t1"]
    assert n_admission == n_prefill == 4 and n_decode > 4


def test_a_steps_lanes_are_the_requests_its_decode_dispatch_carried(
        model):
    """`lanes` counts where the work happens: after the step's retire
    and admit, so a step that retires one request and admits another
    still names exactly the `decode` spans of its dispatch; and
    `pages_live` is the cache's count at the step's close."""
    rt.enable()
    eng = ServingEngine(model, engine_config()).warmup()
    rt.reset()
    rng = np.random.RandomState(3)
    for i, (L, n) in enumerate([(3, 2), (5, 6), (4, 3), (6, 5), (7, 4),
                                (3, 6), (9, 2)]):
        eng.submit(rng.randint(0, 97, (L,)).astype(np.int32), n,
                   rid=f"q{i}")
    turnover = {}          # step -> (rids retired, rids admitted)
    pages = {}             # step -> cache.n_live after it
    while eng.has_work():
        before = set(eng.sched.running)
        eng.step()
        after = set(eng.sched.running)
        turnover[eng._step_no] = (before - after, after - before)
        pages[eng._step_no] = eng.cache.n_live
    evts = rt.get_tracer().events()
    carried = {}
    for e in evts:
        if e.get("comp") == "decode":
            carried[e["tick"]] = carried.get(e["tick"], 0) + 1
    steps = {n: w for n, (w, _) in _steps_of(evts).items()}
    assert set(steps) == set(pages)
    assert {n for n, w in steps.items() if "lanes" in w} == set(carried)
    for n, w in steps.items():
        assert w["pages_live"] == pages[n]
        if n in carried:
            assert w["lanes"] == carried[n]
    both = [n for n, (gone, new) in turnover.items() if gone and new]
    assert both and all(n in carried for n in both)
    assert max(w["pages_live"] for w in steps.values()) > 0
    assert steps[max(steps)]["pages_live"] == 0 == eng.cache.n_live


def test_a_fetch_annotation_nests_inside_its_sync(model, monkeypatch):
    """`serve:fetch` opens after its `sync` and closes before it, and
    the ring's `ready` stamp is what divides the two."""
    stack, fetches = [], []

    class Nesting:
        def __init__(self, name, **kw):
            self.name, self.kw = name, kw

        def __enter__(self):
            if self.name == "serve:fetch":
                fetches.append((stack[-1], self.kw))
            stack.append(self.name)

        def __exit__(self, *exc):
            assert stack.pop() == self.name

    eng = ServingEngine(model, engine_config()).warmup()
    rt.enable()
    monkeypatch.setattr(rt, "_TraceAnnotation", Nesting)
    rt.reset()
    _drive(eng)
    assert stack == []
    syncs = [e for e in rt.get_tracer().events() if e.get("comp") == "sync"]
    assert len(fetches) == len(syncs) > 0
    assert all(outer == "serve:sync" for outer, _ in fetches)
    assert sorted((kw["step"], kw["kind"]) for _, kw in fetches) == \
        sorted((e["step"], e["kind"]) for e in syncs)


def _fixed_request_events():
    return [
        {"i": 0, "rid": "fast", "mark": "submit", "t": 0.0},
        {"i": 1, "rid": "fast", "comp": "decode", "t0": 0.0, "t1": 1.0,
         "replica": 0},
        {"i": 2, "rid": "fast", "mark": "retire", "t": 1.0},
        {"i": 3, "rid": "slow", "mark": "submit", "t": 0.0},
        {"i": 4, "rid": "slow", "comp": "queue", "t0": 0.0, "t1": 8.0,
         "replica": 1},
        {"i": 5, "rid": "slow", "comp": "decode", "t0": 8.0, "t1": 10.0,
         "replica": 1},
        {"i": 6, "rid": "slow", "mark": "retire", "t": 10.0},
    ]


def test_explain_tail_unchanged_by_interleaved_step_events():
    """Step events are not requests: a `step` span a hundred seconds
    long and phases named like marks (`retire`, `dispatch`) move
    nothing in timelines, attribute or explain_tail."""
    plain = _fixed_request_events()
    mixed = []
    for e in plain:
        mixed.append(e)
        mixed.append({"i": 100 + e["i"], "rid": None, "comp": "step",
                      "t0": -50.0, "t1": 50.0, "step": e["i"],
                      "replica": 1, "executables": 3})
        for name in rt.STEP_PHASES:
            mixed.append({"i": 200 + e["i"], "rid": None, "comp": name,
                          "t0": -5.0, "t1": 20.0, "step": e["i"],
                          "replica": 1, "parent": "step"})
    assert rt.timelines(mixed) == rt.timelines(plain)
    assert set(rt.timelines(mixed)) == {"fast", "slow"}
    for p in (99.0, 0.0):
        assert rt.explain_tail(mixed, p=p) == rt.explain_tail(plain, p=p)


def test_executables_rises_by_one_on_a_new_bucket_shape(model):
    """`executables` on the step span is the engine's compile count at
    that boundary: a cold engine's first prefill (a packed row of 16)
    and its decode make 2, the first admit batch of two prompts that
    needs a row of 32 one more, and a step that only decodes none."""
    rt.enable()
    eng = ServingEngine(model, engine_config())      # no warmup
    rng = np.random.RandomState(1)
    eng.submit(rng.randint(0, 97, (5,)).astype(np.int32), 6, rid="a")
    eng.step()
    eng.step()
    eng.submit(rng.randint(0, 97, (12,)).astype(np.int32), 2, rid="b")
    eng.submit(rng.randint(0, 97, (5,)).astype(np.int32), 2, rid="c")
    eng.step()
    eng.step()
    counts = [w["executables"] for _, (w, _) in
              sorted(_steps_of(rt.get_tracer().events()).items())]
    assert counts == [2, 2, 3, 3]


def test_serve_annotation_entered_once_per_ring_phase(model,
                                                      monkeypatch):
    """One writer, two clocks: each ring phase (and each step) entered
    and left exactly one `serve:<name>` profiler annotation carrying
    the same `step`, and each `sync` one `serve:fetch` besides; the
    requests' spans entered none."""
    entered, left = [], []

    class Counting:
        def __init__(self, name, **kw):
            self.key = (name, kw.get("step"))

        def __enter__(self):
            entered.append(self.key)
            return self

        def __exit__(self, *exc):
            left.append(self.key)

    eng = ServingEngine(model, engine_config()).warmup()
    rt.enable()
    monkeypatch.setattr(rt, "_TraceAnnotation", Counting)
    rt.reset()
    _drive(eng)
    ring = [("serve:" + e["comp"], e["step"])
            for e in rt.get_tracer().events() if e.get("rid") is None
            and "comp" in e]
    ring += [("serve:fetch", n) for name, n in ring if name == "serve:sync"]
    assert ring and sorted(entered) == sorted(ring) == sorted(left)
    # and with tracing off the engine enters none
    rt.disable()
    del entered[:]
    _drive(eng, seed=2)
    assert entered == []


def test_a_step_that_raises_leaves_no_annotation_entered(
        model, monkeypatch):
    """The engine closes the step in a ``finally``: a dispatch that
    raises still leaves every ``serve:`` annotation it entered, and the
    ring holds the step up to the phase that failed."""
    depth = []

    class Counting:
        def __init__(self, name, **kw):
            pass

        def __enter__(self):
            depth.append(1)

        def __exit__(self, *exc):
            depth.pop()

    eng = ServingEngine(model, engine_config()).warmup()
    rt.enable()
    monkeypatch.setattr(rt, "_TraceAnnotation", Counting)
    rt.reset()

    def boom(*a, **kw):
        assert len(depth) == 2      # serve:step over serve:dispatch
        raise RuntimeError("device lost")

    monkeypatch.setattr(eng, "_prefill", boom)
    eng.submit(np.arange(5, dtype=np.int32), 3, rid="x")
    with pytest.raises(RuntimeError, match="device lost"):
        eng.step()
    assert depth == []
    (whole, phases), = _steps_of(rt.get_tracer().events()).values()
    assert [p["comp"] for p in phases] == [
        "retire", "admit", "keys", "alloc", "build", "dispatch"]
    assert whole["executables"] is None
    assert phases[-1]["t1"] == whole["t1"]


def test_chrome_trace_draws_steps_on_lanes_of_their_own(model):
    rt.enable()
    eng = ServingEngine(model, engine_config()).warmup()
    rt.reset()
    _drive(eng)
    evs = rt.chrome_trace_events()
    names = {e["tid"]: e["args"]["name"] for e in evs if e["ph"] == "M"}
    assert sorted(names.values()) == ["engine steps 0",
                                      "serving replica 0"]
    step_tid = next(t for t, n in names.items() if n.startswith("eng"))
    on_lane = {e["name"] for e in evs
               if e["ph"] == "X" and e["tid"] == step_tid}
    assert on_lane == {"step"} | set(rt.STEP_PHASES)
    assert all(":" in e["name"] for e in evs
               if e["ph"] == "X" and e["tid"] != step_tid)


def test_tpu_doctor_serving_cli_reads_receipt(tmp_path, capsys):
    """`tpu_doctor --serving RECEIPT.json` triages a serving receipt
    (drill/obs_report output shape: tail_attribution + episodes) and
    exits 1 on a named cause."""
    import json
    from tools import tpu_doctor
    doc = {"tail_attribution": _tail(
        evictions=[{"rid": "a", "replica": 1, "kind": "crash",
                    "t": 1.0}]),
        "episodes": [{"action": "evict_shrink", "ranks": [1]}]}
    p = tmp_path / "receipt.json"
    p.write_text(json.dumps(doc))
    rc = tpu_doctor.main(["--serving", str(p)])
    out = json.loads(capsys.readouterr().out.strip())
    assert rc == 1
    assert out["cause"] == "replica_kill" and out["replica"] == 1
    assert out["evidence"]["receipt_corroborates"] is True
    # a clean receipt exits 0
    p2 = tmp_path / "clean.json"
    p2.write_text(json.dumps({"tail_attribution":
                              _tail(cohort=0, dominant=None)}))
    assert tpu_doctor.main(["--serving", str(p2)]) == 0


def test_tpu_doctor_serving_cli_parses_drill_receipt(tmp_path,
                                                     capsys):
    """Review regression: drill/bench receipts nest everything under
    ``extras`` (tail at extras.tail_attribution, fleet summary at
    extras.stats.fleet) — the CLI must still name the kill, not
    report 'none'."""
    import json
    from tools import tpu_doctor
    doc = {"metric": "serving_chaos_kill", "extras": {
        "tail_attribution": _tail(
            evictions=[{"rid": "a", "replica": 1, "kind": "crash",
                        "t": 1.0}]),
        "remediation": [{"action": "evict_shrink", "ranks": [1]}],
        "stats": {"fleet": {"recompile_events": 0}}}}
    p = tmp_path / "drill.json"
    p.write_text(json.dumps(doc))
    rc = tpu_doctor.main(["--serving", str(p)])
    out = json.loads(capsys.readouterr().out.strip())
    assert rc == 1
    assert out["cause"] == "replica_kill" and out["replica"] == 1
    assert out["evidence"]["receipt_corroborates"] is True
    # obs_report shape: top-level recompile_events reaches the
    # 'recompile' cause
    p2 = tmp_path / "obs.json"
    p2.write_text(json.dumps({"tail_attribution": _tail(),
                              "recompile_events": 2}))
    assert tpu_doctor.main(["--serving", str(p2)]) == 1
    out2 = json.loads(capsys.readouterr().out.strip())
    assert out2["cause"] == "recompile"


def test_training_chaos_inject_not_a_serving_incident():
    """Review regression: chaos.inject is shared with the TRAINING
    chaos hook — only serving-scoped injections belong in the
    serving_incidents section."""
    from tools import tpu_doctor
    dump = {"rank": 0, "events": [
        {"k": "chaos.inject", "mode": "kill", "step": 3, "rank": 0,
         "t": 1.0},                               # training hook
        {"k": "chaos.inject", "mode": "kill", "step": 3, "rank": 1,
         "scope": "serving", "t": 2.0}]}          # serving hook
    inc = tpu_doctor.diagnose([dump])["serving_incidents"]
    assert len(inc) == 1 and inc[0]["scope"] == "serving"
    training_only = {"rank": 0, "events": [
        {"k": "chaos.inject", "mode": "stall", "step": 3, "rank": 0,
         "t": 1.0}]}
    diag = tpu_doctor.diagnose([training_only])
    assert diag["serving_incidents"] == []
    assert "serving incidents" not in tpu_doctor.format_report(diag)


def test_bench_restores_tracing_gate_on_error(monkeypatch):
    """Review regression: the tools flip the process-global tracing
    gate; a raising replay must not leave it on for whatever runs
    next in this process."""
    from tools import serving_bench

    calls = {"n": 0}

    def boom(model, args, trace, **kw):
        calls["n"] += 1
        if calls["n"] == 2:      # the TRACED leg
            raise RuntimeError("wedged")
        return {"sustained_tokens_per_sec": 1.0,
                "ttft_ms": {"p50": 1.0, "p99": 1.0}}
    monkeypatch.setattr(serving_bench, "run_engine_leg", boom)
    monkeypatch.setattr(serving_bench, "build_model",
                        lambda args: object())
    from paddle_tpu.observability import metrics
    with metrics.enabled_scope(metrics.enabled()):
        with pytest.raises(RuntimeError, match="wedged"):
            serving_bench.main(["--requests", "2"])
    assert not rt.enabled()
