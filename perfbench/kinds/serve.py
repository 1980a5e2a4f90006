"""The `serve` kind: build the engine from the seed, warm it, drive it
open loop (arrivals on a schedule) or closed loop (clients that wait),
time each request with the benchmark's own clock, and compare what the
window served with the reference. run.py finds this file by the
configuration's `kind`; the model's recipe (`<config>.program.py`) and
its reference (`<config>.reference.py`) are found by the
configuration's name.
"""
from __future__ import annotations

import gc
import itertools
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from perfbench.lib import correct
from perfbench.lib import traffic as _traffic
from perfbench.lib.common import annot as _annot, compile_cache_hits
from perfbench.lib.common import quantile as _quantile, say as _say


@dataclass
class Rec:
    """One request as the benchmark saw it (host clock, seconds)."""
    item: object
    rid: object = None
    due: Optional[float] = None        # when it was due / handed over
    first_token: Optional[float] = None
    done: Optional[float] = None
    n_out: int = 0
    counted: bool = False              # belongs to the window
    failed: bool = False
    out: List[int] = field(default_factory=list)
    admitted: Optional[float] = None   # the engine's own stamp


class GcClock:
    """Times the interpreter's garbage collections (a `gc.callbacks`
    entry): one candidate for a window that loses a second."""

    def __init__(self):
        self.pauses = []        # (start, seconds, generation)
        self._t = None
        gc.callbacks.append(self)

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((self._t, time.perf_counter() - self._t,
                                info["generation"]))

    def inside(self, t0: float, t1: float) -> dict:
        got = [(s, g) for t, s, g in self.pauses if t0 <= t <= t1]
        worst = max(got, default=(0.0, None))
        return {"collections": len(got),
                "total_ms": round(1e3 * sum(s for s, _ in got), 1),
                "longest_ms": round(1e3 * worst[0], 1),
                "longest_generation": worst[1]}


class Driver:
    """Feeds one engine and keeps the benchmark's own records. After
    every engine step it looks at the running requests and stamps first
    tokens and completions itself."""

    def __init__(self, eng, config: dict, forward_flops):
        self.eng = eng
        self.config = config
        self.forward_flops = forward_flops      # the reference's count
        self.by_rid: Dict[object, Rec] = {}
        self.live: Dict[object, object] = {}    # rid -> program Request
        self.steps: List[dict] = []             # per engine step
        self.flops_done = 0.0
        self.pages_live_peak = 0

    def submit(self, rec: Rec, now: float):
        try:
            rec.rid = self.eng.submit(rec.item.ids,
                                      rec.item.max_new_tokens,
                                      arrival=rec.due)
        except Exception as e:  # a refused request is a failed one
            rec.failed = True
            rec.done = now
            _say(f"request refused: {e!r}")
            return
        self.by_rid[rec.rid] = rec

    def step(self) -> List[Rec]:
        """One engine step; returns the records whose last token came
        out of it."""
        eng = self.eng
        active_before = len(eng.sched.active())
        with _annot("bench:engine_step"):
            t0, cpu0 = time.perf_counter(), time.process_time()
            eng.step()
            now, cpu1 = time.perf_counter(), time.process_time()
        completed = []
        new_tokens = 0
        prefilled = 0
        cfg = self.config
        for rid, req in list(eng.sched.running.items()):
            rec = self.by_rid.get(rid)
            if rec is None:
                continue
            n = len(req.out)
            if n > rec.n_out:
                if rec.n_out == 0:
                    rec.first_token = now
                    rec.admitted = req.admitted_ts
                    prefilled += 1
                    p = len(rec.item.ids)
                    self.flops_done += self.forward_flops(
                        cfg, 0, p, 1)
                    fed = n - 1
                    first_pos = p
                else:
                    fed = n - rec.n_out
                    first_pos = len(rec.item.ids) + rec.n_out - 1
                if fed:
                    self.flops_done += self.forward_flops(
                        cfg, first_pos, fed, fed)
                new_tokens += n - rec.n_out
                rec.n_out = n
            if req.done and rec.done is None:
                rec.done = now
                rec.out = list(req.out)
                completed.append(rec)
        self.pages_live_peak = max(self.pages_live_peak,
                                   int(eng.cache.n_live))
        self.steps.append({"t0": t0, "t1": now, "cpu_s": cpu1 - cpu0,
                           "active": active_before,
                           "new_tokens": new_tokens,
                           "prefilled": prefilled,
                           "queue": int(eng.sched.queue_depth),
                           "in_system": int(eng.sched.queue_depth)
                           + int(eng.sched.n_running)})
        return completed


def run_open(driver: Driver, items, warm_s: float, seconds: float,
             drain_s: float, on_open=None, on_close=None, later=None):
    """Open loop. Arrivals keep their schedule whatever the engine
    does; those due in [warm_s, warm_s + seconds) are the window's and
    each is followed to its end. `later` is (seconds after the opening,
    call): made once, at the first look at the clock past it. Returns
    (records, t_open, t_close, lateness)."""
    eng = driver.eng
    recs = [Rec(item=it) for it in items]
    t0 = time.perf_counter()
    t_open = t0 + warm_s
    t_close = t_open + seconds
    for r in recs:
        r.due = t0 + r.item.due_s
        r.counted = t_open <= r.due < t_close
    lateness = []
    nxt = 0
    opened = closed = False
    while True:
        now = time.perf_counter()
        if not opened and now >= t_open:
            opened = True
            if on_open:
                on_open()
        if later and now >= t_open + later[0]:
            later[1]()
            later = None
        if not closed and now >= t_close:
            closed = True
            if on_close:
                on_close()
        while nxt < len(recs) and recs[nxt].due <= now:
            driver.submit(recs[nxt], now)
            if recs[nxt].counted:
                lateness.append(now - recs[nxt].due)
            nxt += 1
        left = [r for r in recs if r.counted and r.done is None]
        if nxt >= len(recs) and (not left or now > t_close + drain_s):
            break
        if eng.has_work():
            driver.step()
        else:
            with _annot("bench:idle_until_next_arrival"):
                wake = recs[nxt].due if nxt < len(recs) else now + 0.01
                time.sleep(max(min(wake - time.perf_counter(), 0.05), 0))
    if on_close and not closed:
        on_close()
    for r in recs:
        if r.counted and r.done is None:
            r.failed = True
    return recs, t_open, t_close, lateness


def run_closed(driver: Driver, items, clients: int, warm_s: float,
               seconds: float, on_open=None, on_close=None, later=None):
    """Closed loop: `clients` callers, each handing over its next
    request when its last one is done. The window opens at the first
    completion at or after warm_s and closes at the first completion at
    or after `seconds` later; requests completed between the two stamps
    are the window's. `later` as in `run_open`. Returns (records,
    t_open, t_close, [])."""
    eng = driver.eng
    pool = itertools.cycle(items)     # never runs dry
    recs: List[Rec] = []

    def hand_over(now):
        rec = Rec(item=next(pool), due=now)
        recs.append(rec)
        driver.submit(rec, now)

    t0 = time.perf_counter()
    for _ in range(clients):
        hand_over(t0)
    t_open = t_close = None
    while t_close is None:
        done = driver.step()
        if not done:
            continue
        now = done[0].done          # one stamp for the whole step
        if t_open is None:
            if now - t0 >= warm_s:
                t_open = now
                if on_open:
                    on_open()
        else:
            for rec in done:
                rec.counted = True
            if later and now - t_open >= later[0]:
                later[1]()
                later = None
            if now - t_open >= seconds:
                t_close = now
        for rec in done:
            hand_over(now)
    if on_close:
        on_close()
    # what is still in flight is neither counted nor failed; let the
    # engine finish it so that its pages come back
    while eng.has_work():
        driver.step()
    return recs, t_open, t_close, []


def build_engine(config: dict, seed: int, reference, program,
                 engine_overrides=None):
    """The configuration's recipe over the reference's weights."""
    weights = reference.make_params(config, _traffic.jax_key(seed),
                                    config["precision"]["weights"])
    return program.build(config, seed, weights, engine_overrides)


def measure(config: dict, traffic: dict, seed: int, seconds: float,
            reference, program, tracer=None, spans: bool = False,
            engine_overrides=None, eng=None) -> dict:
    """Build, warm and drive one engine over one window of the traffic
    file's mix. Everything the result line, the readers and the
    comparison need; the engine itself is returned so that the caller
    decides when its memory goes. A tracer captures the window's last
    `trace_seconds` (the traffic file's): the window keeps its length,
    so that `correct` compares as many requests as in any other run,
    and the capture is written once the drain is over."""
    from paddle_tpu.observability import reqtrace
    warm_s = float(traffic["warm_seconds"])
    if eng is None:
        eng = build_engine(config, seed, reference, program,
                           engine_overrides)
    hits = compile_cache_hits()
    if spans:
        reqtrace.enable(True, capacity=1 << 16)
    driver = Driver(eng, config, reference.forward_flops)
    gc_clock = GcClock()
    items = _traffic.serve_items(traffic, config["vocab_size"], seed,
                                 warm_s + seconds)
    marks = {}

    def on_open():
        marks["step0"] = len(driver.steps)
        marks["flops0"] = driver.flops_done

    def on_close():
        if tracer is not None:
            tracer.close()
        marks["step1"] = len(driver.steps)
        marks["flops1"] = driver.flops_done

    later = None if tracer is None else (
        max(seconds - float(traffic["trace_seconds"]), 0.0), tracer.open)
    if traffic["loop"] == "open":
        recs, t_open, t_close, lateness = run_open(
            driver, items, warm_s, seconds,
            float(traffic["drain_seconds"]), on_open, on_close, later)
    else:
        recs, t_open, t_close, lateness = run_closed(
            driver, items, int(traffic["clients"]), warm_s, seconds,
            on_open, on_close, later)
    if tracer is not None:
        tracer.stop()       # the drain is over: nothing timed is left
    gc.callbacks.remove(gc_clock)
    counted = [r for r in recs if r.counted]
    done = [r for r in counted if not r.failed and r.done is not None]
    elapsed = t_close - t_open
    end_to_end = {}
    multi = [r for r in done if len(r.out) > 1]
    if traffic["loop"] == "open":
        # a rate of the window's requests, each followed to its end
        if multi:
            end_to_end["tpot_p90_ms"] = 1e3 * _quantile(
                [(r.done - r.first_token) / (len(r.out) - 1)
                 for r in multi], 0.9)
    else:
        end_to_end["served_tokens_per_s"] = sum(
            len(r.item.ids) + len(r.out) for r in done) / elapsed
    e = {**config["engine"], **(engine_overrides or {})}
    bench = {
        "records": done,
        "compile_cache_hits": hits,
        "lateness": lateness, "t_open": t_open, "t_close": t_close,
        "elapsed_s": elapsed,
        "steps": driver.steps[marks.get("step0", 0):
                              marks.get("step1", len(driver.steps))],
        "flops_in_window": marks.get("flops1", driver.flops_done)
        - marks.get("flops0", 0.0),
        "pages_live_peak": driver.pages_live_peak,
        "pages_total": e["n_blocks"] - 1,
        "max_slots": e["max_slots"],
        "spans": reqtrace.get_tracer().events() if spans else [],
        "gc": gc_clock.inside(t_open, t_close),
    }
    if spans:
        reqtrace.disable()
    return {
        "engine": eng, "bench": bench, "end_to_end": end_to_end,
        "t_open": t_open, "attempted": len(counted),
        "failed": len(counted) - len(done),
        "n_short": sum(1 for r in done
                       if len(r.out) != r.item.max_new_tokens),
        "finished": [{"ids": r.item.ids, "out": r.out} for r in done],
    }


def slow_steps(steps: List[dict], n_top: int = 6) -> dict:
    """Where a window lost time, as far as the host's clock can tell
    without the profiler: the engine steps that took over 1.5 times the
    median of their kind (prefill only, decode only, both), each with
    the process's CPU seconds inside it (all threads: a step that waits
    for the device or for a core burns little, one that computes on the
    host burns its length or more), and the longest times the loop
    spent between two engine steps. Milliseconds."""
    def kind(s):
        decoded = s["new_tokens"] > s["prefilled"]
        return ("both" if decoded else "prefill") if s["prefilled"] \
            else "decode"

    by_kind: Dict[str, List[float]] = {}
    for s in steps:
        by_kind.setdefault(kind(s), []).append(s["t1"] - s["t0"])
    median = {k: _quantile(v, 0.5) for k, v in by_kind.items()}
    slow = [(i, s) for i, s in enumerate(steps)
            if s["t1"] - s["t0"] > 1.5 * median[kind(s)]]
    slow.sort(key=lambda x: x[1]["t0"] - x[1]["t1"])
    between = sorted(((b["t0"] - a["t1"], i + 1) for i, (a, b) in
                      enumerate(zip(steps, steps[1:]))), reverse=True)
    return {
        "steps": len(steps),
        "median_ms": {k: round(1e3 * v, 1) for k, v in median.items()},
        "over_1.5x": len(slow),
        "slowest_index_kind_ms_cpu_ms": [
            [i, kind(s), round(1e3 * (s["t1"] - s["t0"]), 1),
             round(1e3 * s["cpu_s"], 1)] for i, s in slow[:n_top]],
        "longest_between_steps_ms_index": [
            [round(1e3 * g, 1), i] for g, i in between[:3]],
    }


def run(a, config, traffic, limits, reference, program, tracer, say,
        t_start: float, memory_peak) -> dict:
    """One run of a serve cell: the window, then the peak is read, the
    engine freed, and the reference run over a sample of what the
    window finished."""
    m = measure(config, traffic, a.seed, a.seconds, reference, program,
                tracer=tracer if tracer.on else None, spans=bool(a.trace))
    say("engine steps of the window, slow ones: "
        f"{slow_steps(m['bench']['steps'])}; garbage collections "
        f"inside it: {m['bench']['gc']}")
    mem = memory_peak()
    sample = correct.pick_sample(m["finished"], a.seed,
                                 int(traffic["check_requests"]))
    m.pop("engine")
    gc.collect()
    params = reference.make_params(config, _traffic.jax_key(a.seed),
                                   config["precision"]["weights"])
    t_ref = time.perf_counter()
    numbers = correct.compare_serve(reference, params, config, sample,
                                    m["n_short"], limits["limits"])
    say(f"reference over {len(sample)} requests in "
        f"{time.perf_counter() - t_ref:.1f} s")
    end_to_end = dict(m["end_to_end"], setup_s=m["t_open"] - t_start)
    return dict(end_to_end=end_to_end, bench=m["bench"], numbers=numbers,
                attempted=m["attempted"], failed=m["failed"],
                memory_peak=mem)
