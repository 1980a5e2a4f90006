#!/usr/bin/env python
"""Serving chaos drill: prove the SLO-aware self-healing fleet
end-to-end — the serving twin of tools/chaos_drill.py.

One process, N in-process ServingEngine replicas behind a
ServingFleet, an open-loop trace, and ONE deterministic fault injected
mid-load (PD_CHAOS_* plan through chaos.maybe_inject_serving). Modes:

  kill      kill replica PD_CHAOS_RANK at fleet tick PD_CHAOS_STEP
            (engine object gone, in-flight state lost except what was
            already streamed). Bars: ZERO dropped requests, every
            evicted request's stitched output BIT-IDENTICAL to an
            uninterrupted engine run (f32 greedy parity), rolling p99
            TTFT recovered by drain time, one remediation receipt
            naming the replica — AND the request-trace breach verdict
            (tpu_doctor.serving_breach_verdict over reqtrace's
            explain_tail, no receipts consulted) must name the evicted
            replica and the ``requeue`` component from the trace
            alone.
  stall     wedge the replica's step loop instead (hung-but-alive);
            the progress clock evicts it. Same bars, verdict=hang.
  swap      hot weight swap under load: one clean swap (flip
            per-replica at token boundaries; zero recompiles, zero
            drops, outputs still bit-identical because the snapshot is
            re-loaded from the SAME checkpoint) plus one SABOTAGED
            swap (corrupt_swap chaos poisons the standby) that must
            ABORT with a receipt while the old weights keep serving.
  overload  2x-sustained-overload with two priority classes: the
            interactive class must hold its p99 TTFT SLO while the
            batch class is shed/queued; per-class TTFT histograms land
            in the receipt.

Prints ONE ``serving_chaos_drill: {json}`` receipt line through
exporters.emit_report; --check exits 1 unless the mode's bars hold.
--smoke shrinks shapes to the tier-1 budget (<15 s) and is registered
as a tier-1 test (tests/test_serving_chaos_drill.py).
"""
import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def build_model(args):
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=args.vocab, hidden_size=args.hidden,
                    num_layers=args.layers, num_heads=args.heads,
                    max_seq_len=args.max_seq_len, dropout=0.0,
                    use_flash_attention=False)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def serving_config(args):
    from paddle_tpu.serving import ServingConfig
    return ServingConfig(
        max_slots=args.slots, max_admit=args.admit,
        block_size=args.block_size, n_blocks=args.n_blocks,
        prefill_buckets=tuple(
            int(b) for b in args.prefill_buckets.split(",")),
        decode_chunk=args.decode_chunk,
        max_total_tokens=args.max_total,
        dtype=args.dtype or None)


def build_fleet(model, args, autoscale=False):
    from paddle_tpu.serving import (FleetConfig, ServingFleet,
                                    ServingSLO)
    slo = ServingSLO(p99_ttft_ms=args.slo_p99_ms,
                     queue_high=args.queue_high,
                     queue_low=args.queue_low,
                     shed_queue_depth=args.shed_depth)
    fc = FleetConfig(replicas=args.replicas,
                     min_replicas=1,
                     max_replicas=max(args.replicas,
                                      args.max_replicas),
                     autoscale=autoscale,
                     scale_cooldown_s=args.scale_cooldown,
                     stall_ticks=args.stall_ticks,
                     receipts_dir=args.receipts_dir)
    return ServingFleet(model, serving_config(args), slo, fc)


def arm_chaos(mode, step, rank):
    from paddle_tpu.distributed import chaos
    os.environ["PD_CHAOS_MODE"] = mode
    os.environ["PD_CHAOS_STEP"] = str(step)
    os.environ["PD_CHAOS_RANK"] = str(rank)
    chaos.reset_plan_cache()


def disarm_chaos():
    from paddle_tpu.distributed import chaos
    for k in ("PD_CHAOS_MODE", "PD_CHAOS_STEP", "PD_CHAOS_RANK"):
        os.environ.pop(k, None)
    chaos.reset_plan_cache()


def verify_exact_replay(model, args, finished):
    """The replay receipt: every request that survived an eviction
    must have emitted a stream BIT-IDENTICAL to an uninterrupted run
    of the same engine shape (f32 greedy parity — which PR 9 pinned
    against the dense generation.py path)."""
    import numpy as np
    from paddle_tpu.serving import ServingEngine
    evicted = [fr for fr in finished if fr.evictions > 0]
    if not evicted:
        return {"replayed": 0, "bit_identical": None}
    ref = ServingEngine(model, serving_config(args)).warmup()
    outs = ref.generate_tokens([fr.ids for fr in evicted],
                               [fr.max_new_tokens for fr in evicted])
    ok = all(list(fr.emitted) == [int(t) for t in o]
             for fr, o in zip(evicted, outs))
    mism = [fr.rid for fr, o in zip(evicted, outs)
            if list(fr.emitted) != [int(t) for t in o]]
    return {"replayed": len(evicted),
            "bit_identical": bool(ok),
            "mismatched_rids": mism}


def p99_recovery(finished, fault_ts, bound_ms, window=8):
    """Seconds from the fault until the rolling p99 TTFT over
    `window` consecutive POST-FAULT COMPLETIONS is back under
    `bound_ms` and stays there. Completions (not first tokens) are
    the evidence base: the disrupted set — requeued requests and
    everything queued behind the dead replica — finishes after the
    fault, and a ruined fleet shows up as their inflated TTFTs. -1.0
    when it never recovers OR there is zero post-fault evidence
    (an empty set must not read as instant recovery)."""
    import numpy as np
    pts = sorted(((fr.done_ts, (fr.first_token_ts - fr.arrival)
                   * 1e3) for fr in finished
                  if fr.first_token_ts is not None
                  and fr.done_ts is not None
                  and fr.done_ts >= fault_ts))
    if not pts:
        return -1.0     # zero post-fault evidence is NOT recovery
    if len(pts) < window:
        return 0.0 if all(p[1] <= bound_ms for p in pts) else -1.0
    recovered_at = None
    for i in range(len(pts) - window + 1):
        p99 = float(np.percentile([p[1] for p in pts[i:i + window]],
                                  99))
        if p99 <= bound_ms:
            if recovered_at is None:
                recovered_at = pts[i + window - 1][0]
        else:
            recovered_at = None
    if recovered_at is None:
        return -1.0
    return max(0.0, recovered_at - fault_ts)


# every autonomous fleet move in this family must ship its ledger
# audit: a decision_id in the episode receipt AND a JOINED outcome
# (anything still "unjoined" means the fleet acted and nobody measured
# whether it helped — the drill fails the receipt)
AUDITED_ACTIONS = ("evict_shrink", "respawn_rank", "scale_up",
                   "scale_down", "grow", "weight_swap", "swap_aborted")


def _ledger_audit(episodes, require=1):
    """Cross-check fleet episode receipts against the decision ledger:
    every AUDITED action must carry a decision_id whose outcome joined
    (require = minimum number of audited episodes expected)."""
    audited = [e for e in episodes
               if e.get("action") in AUDITED_ACTIONS]
    unaudited = [
        {"action": e.get("action"), "episode": e.get("episode"),
         "decision_id": e.get("decision_id"),
         "outcome": e.get("outcome")}
        for e in audited
        if not e.get("decision_id")
        or e.get("outcome") in (None, "unjoined")]
    return {"ok": len(audited) >= require and not unaudited,
            "audited": len(audited), "unaudited": unaudited}


def run_fault_drill(args, mode):
    """kill / stall: one replica faulted mid-load."""
    from paddle_tpu.observability import reqtrace
    from paddle_tpu.serving.loadgen import replay_fleet, synthetic_trace
    from tools.tpu_doctor import serving_breach_verdict
    model = build_model(args)
    trace = synthetic_trace(
        args.requests, vocab_size=args.vocab, seed=args.seed,
        rate_rps=args.rate,
        prompt_len_choices=tuple(
            int(x) for x in args.prompt_lens.split(",")),
        new_token_choices=tuple(
            int(x) for x in args.new_tokens.split(",")))
    arm_chaos(mode, args.chaos_tick, args.chaos_replica)
    reqtrace.enable()
    reqtrace.reset()
    try:
        try:
            fleet = build_fleet(model, args, autoscale=args.autoscale)
            fault_box = {}

            def on_tick(tick, fl):
                if fault_box.get("ts") is None and fl.episodes:
                    fault_box["ts"] = time.perf_counter()
            stats, finished, _shed = replay_fleet(fleet, trace,
                                                  on_tick=on_tick)
        finally:
            disarm_chaos()
        # the "why was p99 slow" half of the receipt: the breach
        # verdict comes from the REQUEST TRACES ALONE (no remediation
        # receipts, no fleet summary) and must still name the evicted
        # replica + the requeue component
        tail = reqtrace.explain_tail()
        breach = serving_breach_verdict(tail)
    finally:
        # the gate is process-global: a raising drill must not leave
        # tracing on for whatever runs next in this process
        reqtrace.disable()
    replay = verify_exact_replay(model, args, finished)
    fault_ts = fault_box.get("ts")
    rec_s = (p99_recovery(finished, fault_ts, args.slo_p99_ms)
             if fault_ts is not None else -1.0)
    summ = stats["fleet"]
    remediations = [e for e in summ["episodes"]
                    if e["action"] in ("evict_shrink", "respawn_rank")]
    receipt_names_replica = any(
        args.chaos_replica in e["ranks"] for e in remediations)
    ledger_audited = _ledger_audit(summ["episodes"])
    dropped = args.requests - stats.get("requests", 0) - stats["shed"]
    expected_verdict = "crash" if mode == "kill" else "hang"
    expected_cause = ("replica_kill" if mode == "kill"
                      else "covert_stall")
    trace_verdict_ok = (breach["cause"] == expected_cause
                        and breach["replica"] == args.chaos_replica
                        and breach["component"] == "requeue")
    tail_sums_ok = bool(
        tail["cohort"]
        and all(abs(c["share_sum"] - 1.0) <= 0.02
                for c in tail["cohort"]))
    # --smoke is tier-1's drill and runs on a CPU it shares with the
    # other test workers: its receipt holds counts and identity and
    # reports p99_recovery_s without judging it. A wall-clock TTFT is
    # judged by the full drills only.
    recovered = args.smoke or 0.0 <= rec_s <= args.recovery_bound_s
    ok = (dropped == 0
          and replay["replayed"] >= 1
          and replay["bit_identical"] is True
          and receipt_names_replica
          and any(e["verdict"] == expected_verdict
                  for e in remediations)
          and summ["recompile_events"] == 0
          and recovered
          and trace_verdict_ok
          and tail_sums_ok
          and ledger_audited["ok"])
    return {
        "metric": f"serving_chaos_{mode}",
        "value": stats.get("requests", 0),
        "unit": "requests_completed",
        "extras": {
            "mode": mode, "stats": stats,
            "dropped": dropped,
            "replay": replay,
            "p99_recovery_s": round(rec_s, 3),
            "recovery_bound_s": args.recovery_bound_s,
            "recovery_judged": not args.smoke,
            "remediation": remediations,
            "receipt_names_replica": receipt_names_replica,
            "expected_verdict": expected_verdict,
            "tail_attribution": tail,
            "breach_verdict": breach,
            "trace_verdict_ok": trace_verdict_ok,
            "tail_components_sum_ok": tail_sums_ok,
            "ledger_audited": ledger_audited,
            "receipt_ok": ok,
        },
    }


def run_swap_drill(args):
    """Hot weight swap under load + a sabotaged swap that must abort."""
    from paddle_tpu.distributed import checkpoint as ckpt
    from paddle_tpu.models.generation import _gpt_params
    from paddle_tpu.observability import reqtrace
    from paddle_tpu.serving.loadgen import replay_fleet, synthetic_trace
    import tempfile
    model = build_model(args)
    # the async-checkpoint plane is the swap source: what training
    # publishes is what serving flips to
    ckpt_dir = tempfile.mkdtemp(prefix="pd_swap_drill_")
    ckpt_path = os.path.join(ckpt_dir, "weights")
    ckpt.save_sharded({"params": _gpt_params(model)}, ckpt_path)
    trace = synthetic_trace(
        args.requests, vocab_size=args.vocab, seed=args.seed,
        rate_rps=args.rate,
        prompt_len_choices=tuple(
            int(x) for x in args.prompt_lens.split(",")),
        new_token_choices=tuple(
            int(x) for x in args.new_tokens.split(",")))
    swap_state = {"clean": None, "sabotaged": None}
    reqtrace.enable()
    reqtrace.reset()
    fleet = build_fleet(model, args, autoscale=False)

    def on_tick(tick, fl):
        # the UNDER-LOAD half: stage the clean swap mid-replay
        # STRAIGHT from the checkpoint plane ({"params": ...} wrapper
        # unwrapped by the fleet); one replica flips per subsequent
        # token boundary
        if tick == args.chaos_tick and swap_state["clean"] is None:
            swap_state["clean"] = fl.swap_weights(
                checkpoint_path=ckpt_path)
    try:
        stats, finished, _shed = replay_fleet(fleet, trace,
                                              on_tick=on_tick)
        # flips land one-per-tick; finish any still pending (empty
        # token boundaries — a real fleet keeps ticking between
        # arrivals)
        for _ in range(2 * args.replicas):
            if fleet._standby is None:
                break
            fleet.step()
        # the SABOTAGED half: arm corrupt_swap chaos on the NEXT
        # tick, tick once so the fleet polls it, then attempt the
        # swap — the standby verification must abort it while old
        # weights serve on
        arm_chaos("corrupt_swap", fleet._tick + 1, 0)
        try:
            fleet.step()
            swap_state["sabotaged"] = fleet.swap_weights(
                checkpoint_path=ckpt_path)
        finally:
            disarm_chaos()
        stats["fleet"] = fleet.summary()  # incl. post-drain swaps
        tail = reqtrace.explain_tail()
    finally:
        reqtrace.disable()
    # same-weights swap => greedy outputs must STILL be bit-identical
    import numpy as np
    from paddle_tpu.serving import ServingEngine
    ref = ServingEngine(model, serving_config(args)).warmup()
    outs = ref.generate_tokens([fr.ids for fr in finished],
                               [fr.max_new_tokens for fr in finished])
    identical = all(list(fr.emitted) == [int(t) for t in o]
                    for fr, o in zip(finished, outs))
    summ = stats["fleet"]
    dropped = args.requests - stats.get("requests", 0) - stats["shed"]
    # BOTH swap halves must be in the ledger: the completed flip and
    # the sabotaged abort each carry a joined decision record
    ledger_audited = _ledger_audit(summ["episodes"], require=2)
    ok = (dropped == 0
          and swap_state["clean"] is True
          and swap_state["sabotaged"] is False
          and summ["weight_swaps"] == 1
          and summ["weight_swaps_aborted"] == 1
          and summ["recompile_events"] == 0
          and identical
          and ledger_audited["ok"])
    return {
        "metric": "serving_chaos_swap",
        "value": summ["weight_swaps"],
        "unit": "swaps_completed",
        "extras": {
            "mode": "swap", "stats": stats,
            "dropped": dropped,
            "clean_swap_ok": swap_state["clean"],
            "sabotaged_swap_aborted": swap_state["sabotaged"] is False,
            "outputs_bit_identical": bool(identical),
            "zero_recompiles": summ["recompile_events"] == 0,
            # the flip pauses are visible per request in the trace
            "swap_flip_spans": tail["swap_flips"],
            "ledger_audited": ledger_audited,
            "receipt_ok": ok,
        },
    }


def run_overload_drill(args):
    """2x sustained overload, two priority classes."""
    from paddle_tpu.observability import reqtrace
    from paddle_tpu.serving.loadgen import replay_fleet, synthetic_trace
    from tools.tpu_doctor import serving_breach_verdict
    model = build_model(args)
    trace = synthetic_trace(
        args.requests, vocab_size=args.vocab, seed=args.seed,
        rate_rps=args.rate * 2.0,     # the overload
        prompt_len_choices=tuple(
            int(x) for x in args.prompt_lens.split(",")),
        new_token_choices=tuple(
            int(x) for x in args.new_tokens.split(",")),
        class_mix={"interactive": 0.5, "batch": 0.5})
    reqtrace.enable()
    reqtrace.reset()
    try:
        fleet = build_fleet(model, args, autoscale=args.autoscale)
        stats, finished, shed = replay_fleet(fleet, trace)
        tail = reqtrace.explain_tail()
        breach = serving_breach_verdict(tail, summary=stats["fleet"])
    finally:
        reqtrace.disable()
    summ = stats["fleet"]
    per_cls = stats.get("per_class_ttft_ms", {})
    hi = per_cls.get("interactive", {"p99": -1.0})
    lo = per_cls.get("batch", {"p99": -1.0})
    n_hi = sum(1 for it in trace if it.cls == "interactive")
    hi_done = sum(1 for fr in finished if fr.cls == "interactive")
    dropped = (args.requests - stats.get("requests", 0)
               - stats["shed"])
    batch_shed = all(fr.cls == "batch" for fr in shed)
    # "shed OR queued by class": either real shedding happened, or the
    # batch class paid the queueing (p99 well above interactive)
    degraded = (stats["shed"] > 0
                or (lo["p99"] > 0 and hi["p99"] > 0
                    and lo["p99"] >= 2.0 * hi["p99"]))
    # autoscale off => no audited episodes expected (require=0 keeps
    # the check vacuous); any scale/evict that DID fire must be joined
    ledger_audited = _ledger_audit(summ["episodes"], require=0)
    ok = (dropped == 0
          and hi_done == n_hi
          and 0 < hi["p99"] <= args.slo_p99_ms
          and batch_shed
          and degraded
          and summ["recompile_events"] == 0
          and ledger_audited["ok"])
    return {
        "metric": "serving_chaos_overload",
        "value": hi["p99"],
        "unit": "interactive_p99_ttft_ms",
        "extras": {
            "mode": "overload", "stats": stats,
            "offered_rate_rps": args.rate * 2.0,
            "dropped": dropped,
            "interactive": {"requests": n_hi, "finished": hi_done,
                            "p99_ttft_ms": hi["p99"],
                            "slo_p99_ms": args.slo_p99_ms},
            "batch": {"shed": stats["shed"],
                      "p99_ttft_ms": lo["p99"]},
            "only_batch_shed": batch_shed,
            "low_priority_degraded": degraded,
            # informational: the trace-side view of the overload (the
            # kill-mode bars are the acceptance surface)
            "breach_verdict": breach,
            "tail_dominant": tail["dominant_overall"],
            "slo_burn": summ.get("slo_burn"),
            "ledger_audited": ledger_audited,
            "receipt_ok": ok,
        },
    }


SMOKE = ["--requests", "10", "--rate", "2000", "--replicas", "3",
         "--vocab", "97", "--hidden", "32", "--layers", "2",
         "--heads", "4", "--max-seq-len", "64",
         "--slots", "4", "--admit", "2", "--block-size", "4",
         "--n-blocks", "48", "--prefill-buckets", "24",
         "--max-total", "24", "--decode-chunk", "2",
         "--prompt-lens", "2,3,5,7", "--new-tokens", "3,4,6",
         "--chaos-tick", "4", "--slo-p99-ms", "2000"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--mode", default="kill",
                    choices=("kill", "stall", "swap", "overload"))
    ap.add_argument("--smoke", action="store_true",
                    help="tier-1 shapes (<15 s): tiny model, 3 "
                         "replicas, kill drill unless --mode given")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 unless the mode's bars hold")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--rate", type=float, default=800.0,
                    help="open-loop arrival rate. The default is a "
                         "near-burst: the fault tick's load then "
                         "depends on token budgets, not host speed — "
                         "deterministic drills on any machine")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prompt-lens", default="2,4,6,9,12")
    ap.add_argument("--new-tokens", default="3,4,6,8")
    # fleet shape
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--max-replicas", type=int, default=0,
                    help="slot budget for autoscale (default: "
                         "replicas)")
    ap.add_argument("--autoscale", action="store_true")
    ap.add_argument("--scale-cooldown", type=float, default=1.0)
    ap.add_argument("--stall-ticks", type=int, default=8)
    ap.add_argument("--queue-high", type=int, default=8)
    ap.add_argument("--queue-low", type=int, default=0)
    ap.add_argument("--shed-depth", type=int, default=6)
    ap.add_argument("--receipts-dir", default=None)
    # SLO + chaos plan
    ap.add_argument("--slo-p99-ms", type=float, default=1500.0)
    ap.add_argument("--recovery-bound-s", type=float, default=10.0)
    ap.add_argument("--chaos-tick", type=int, default=6,
                    help="fleet tick the fault fires at (kill/stall; "
                         "the CLEAN swap tick for --mode swap — the "
                         "sabotaged swap runs post-drain on its own "
                         "chaos tick)")
    ap.add_argument("--chaos-replica", type=int, default=1)
    # engine shape
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--admit", type=int, default=2)
    ap.add_argument("--block-size", type=int, default=4)
    ap.add_argument("--n-blocks", type=int, default=64)
    ap.add_argument("--prefill-buckets", default="32")
    ap.add_argument("--decode-chunk", type=int, default=2)
    ap.add_argument("--max-total", type=int, default=32)
    ap.add_argument("--dtype", default="",
                    help="''=f32 parity mode (the exact-replay bar "
                         "needs it)")
    # model shape
    ap.add_argument("--vocab", type=int, default=151)
    ap.add_argument("--hidden", type=int, default=48)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--max-seq-len", type=int, default=64)
    if argv is None:
        argv = sys.argv[1:]
    if "--smoke" in argv:
        argv = SMOKE + list(argv)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if args.receipts_dir is None:
        import tempfile
        args.receipts_dir = tempfile.mkdtemp(prefix="pd_serving_drill_")

    from paddle_tpu.observability import exporters, metrics
    metrics.enable()
    t0 = time.perf_counter()
    if args.mode in ("kill", "stall"):
        report = run_fault_drill(args, args.mode)
    elif args.mode == "swap":
        report = run_swap_drill(args)
    else:
        report = run_overload_drill(args)
    report["extras"]["wall_s"] = round(time.perf_counter() - t0, 2)
    report["extras"]["receipts_dir"] = args.receipts_dir
    report = exporters.emit_report(
        report, jsonl_path=os.environ.get("PD_OBS_JSONL"),
        prefix="serving_chaos")
    print("serving_chaos_drill:", json.dumps(report), flush=True)
    if args.check and not report["extras"]["receipt_ok"]:
        print("RECEIPT FAILED:", json.dumps(
            {k: v for k, v in report["extras"].items()
             if k != "stats"}), flush=True)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
