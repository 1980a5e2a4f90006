#!/usr/bin/env python
"""Serving SLO bench: sustained tokens/s at p99 latency, continuous
batching vs the static-batch baseline, on one open-loop trace.

The receipt the ISSUE names: replay a synthetic mixed-length arrival
trace (open-loop — arrivals follow the trace clock, not the server)
through

  engine   paddle_tpu.serving.ServingEngine — paged KV cache,
           bucketed prefill, chunked decode; ladder compiled at
           startup (``warmup_s``), steady state runs a FIXED
           executable set (RecompileSentinel-pinned: executables ==
           bucket count, zero growth);
  static   today's per-call path — fixed batches through
           model.generate's dense cache: head-of-line batch forming,
           pad-to-batch-max decode, and one XLA compile per new
           (prompt_pad, new_tokens) signature MID-STREAM. Measured
           twice: cold (the real first-window behavior — the baseline
           the acceptance bar is against) and warm (second pass, all
           signatures pre-compiled — the kindest steady-state
           comparison, reported for transparency).

Prints ONE ``serving_bench: {json}`` line routed through
``exporters.emit_report`` (prefix ``serving``), so the artifact and
the Prometheus/JSONL series are provably the same numbers, and rolls
the serving.* metrics up through ``fleet.aggregate()`` (single-host
shape here; the same call is the pod rollup under
jax.distributed). ``--replicas N`` runs N data-parallel engine
replicas over disjoint shards of the trace in one process —
a topology receipt for the rollup math, not a perf claim.

Request anatomy rides along: the engine leg is replayed once with
request tracing OFF (the headline numbers) and once with it ON — the
traced replay yields the tail-attribution receipt
(``extras.tail_attribution``: per-request latency components summing
to 1.0 ± 0.02 for the p99 cohort, dominant component named, plus a
``breach_verdict``) and the measured tracing overhead
(``extras.tracing_overhead.penalty`` — the ≤3% bar). ``--trace PATH``
writes the chrome trace with one request lane per replica.

CPU receipt bars (--check): engine >= 2x cold-static sustained
tokens/s at equal-or-better p99 TTFT, zero steady-state recompiles,
tail components sum to 1.0 ± 0.02, tracing penalty <= 3%.

Raw-speed mode (ISSUE 16): any of ``--quant int8|bf16|f32``,
``--speculative K`` (with ``--draft-layers``), or ``--prefix-sharing``
(paired with ``--shared-prefix LEN --shared-frac F`` on the trace)
switches the headline metric to ``serving_raw_speed_tokens_per_sec``
(its own ledger fingerprint) and adds an ENGINE baseline leg: the same
trace through a plain engine at ``--baseline-dtype`` (default
bfloat16 — the PR 9 fingerprint). The --check bar then ALSO requires
>= 2x sustained tokens/s over that engine baseline at equal-or-better
p99 TTFT. ``--quant int8`` attaches the int8 parity receipt
(``extras.int8_parity``: top-1 agreement + logit drift vs f32/bf16);
speculative legs report the measured acceptance rate; sharing legs
report prefix_hits / shared pages / COW copies.

Tensor-parallel mode (ISSUE 20): ``--tp N`` serves the measured leg
through ONE engine whose decode/prefill are shard_map programs over a
``MeshPlan(tp=N)`` axis (paged pools sharded over heads, N virtual
CPU devices forced). The headline metric becomes
``serving_tp_tokens_per_sec`` (its own ledger fingerprint) and the
receipt attaches ``extras.tp_serving``: an f32 greedy parity pin
(same prompts through the tp engine and its tp=1 twin must match
token-for-token), the tp engine's executable count vs
``expected_executables``, and the per-chip paged-pool bytes (the 1/tp
receipt). On CPU the pins are the claim — the MXU speed claim stays
staged in PERF_PLAN round-10.
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


def build_draft(args):
    """The tiny proposer for --speculative: same vocab (a protocol
    requirement), half the width, --draft-layers deep."""
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(1)
    cfg = GPTConfig(vocab_size=args.vocab,
                    hidden_size=max(8, args.hidden // 2),
                    num_layers=args.draft_layers,
                    num_heads=max(1, args.heads // 2),
                    max_seq_len=args.max_seq_len, dropout=0.0,
                    use_flash_attention=False)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def raw_speed_on(args) -> bool:
    return bool(args.quant or args.speculative or args.prefix_sharing)


def serving_config(args, fast=True):
    """``fast=True`` is the measured leg (raw-speed levers applied);
    ``fast=False`` is the plain engine baseline at --baseline-dtype —
    the PR 9 fingerprint the >=2x raw-speed bar gates against."""
    from paddle_tpu.serving import ServingConfig
    kw = {}
    dtype = args.dtype
    if fast:
        if args.quant == "int8":
            kw["quant"] = "int8"
        elif args.quant == "bf16":
            dtype = "bfloat16"
        elif args.quant == "f32":
            dtype = None
        if args.speculative:
            kw["speculative_k"] = args.speculative
        if args.prefix_sharing:
            kw["prefix_sharing"] = True
        if getattr(args, "tp", 1) > 1:  # hand-built Namespaces omit it
            from paddle_tpu.distributed.sharding import MeshPlan
            kw["plan"] = MeshPlan(tp=args.tp)
    else:
        dtype = args.baseline_dtype or None
    return ServingConfig(
        max_slots=args.slots, max_admit=args.admit,
        block_size=args.block_size, n_blocks=args.n_blocks,
        prefill_buckets=tuple(
            int(b) for b in args.prefill_buckets.split(",")),
        decode_chunk=args.decode_chunk,
        max_total_tokens=args.max_total, dtype=dtype, **kw)


def _counter_value(name: str) -> float:
    from paddle_tpu.observability import metrics
    try:
        return float(metrics.get(name).value())
    except Exception:
        return 0.0


def run_engine_leg(model, args, trace, fast=True, draft_model=None):
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.serving.loadgen import replay_continuous
    eng = ServingEngine(model, serving_config(args, fast=fast),
                        draft_model=draft_model if fast else None)
    t0 = time.perf_counter()
    eng.warmup()
    warmup_s = time.perf_counter() - t0
    spec0 = (_counter_value("serving.spec_proposed_total"),
             _counter_value("serving.spec_accepted_total"))
    stats = replay_continuous(eng, trace)
    stats["warmup_s"] = round(warmup_s, 3)
    stats["decode_chunk"] = args.decode_chunk
    if fast and args.speculative:
        prop = _counter_value("serving.spec_proposed_total") - spec0[0]
        acc = _counter_value("serving.spec_accepted_total") - spec0[1]
        stats["speculative"] = {
            "k": args.speculative,
            "proposed": int(prop), "accepted": int(acc),
            "acceptance_rate": round(acc / prop, 4) if prop else -1.0}
    if fast and args.prefix_sharing:
        st = eng.cache.stats()
        stats["prefix_sharing"] = {
            k: st[k] for k in ("pages_live", "pages_shared",
                               "prefix_hits", "shared_pages_matched",
                               "cow_copies", "reclaimed_pages")}
    return stats


def tp_parity_probe(model, args, trace):
    """The --tp CPU pins: the SAME prompts through the tp engine and
    its tp=1 twin in f32 greedy must match token-for-token (parity by
    construction through the shared program bodies), the tp engine's
    ladder must land on ``expected_executables``, and the sharded
    pools must report the 1/tp per-chip bytes."""
    import numpy as np
    from paddle_tpu.distributed.sharding import MeshPlan
    from paddle_tpu.serving import ServingConfig, ServingEngine
    shape = dict(
        max_slots=args.slots, max_admit=args.admit,
        block_size=args.block_size, n_blocks=args.n_blocks,
        prefill_buckets=tuple(
            int(b) for b in args.prefill_buckets.split(",")),
        decode_chunk=args.decode_chunk,
        max_total_tokens=args.max_total, dtype=None)
    prompts = [t.ids for t in trace[:3]]
    budgets = [int(t.max_new_tokens) for t in trace[:3]]
    eng_tp = ServingEngine(model, ServingConfig(
        plan=MeshPlan(tp=args.tp), **shape)).warmup()
    eng_1 = ServingEngine(model, ServingConfig(**shape))
    out_tp = eng_tp.generate_tokens(prompts, budgets)
    out_1 = eng_1.generate_tokens(prompts, budgets)
    match = all(np.array_equal(np.asarray(a), np.asarray(b))
                for a, b in zip(out_tp, out_1))
    st = eng_tp.cache.stats()
    return {
        "tp": args.tp,
        "f32_greedy_parity": bool(match),
        "parity_requests": len(prompts),
        "executables": eng_tp.executable_count(),
        "expected_executables": eng_tp.expected_executables,
        "pool_bytes": int(st["pool_bytes"]),
        "pool_bytes_per_chip": int(st["pool_bytes_per_chip"]),
    }


def run_replicated(model, args, trace, draft_model=None):
    """--replicas N: one ServingFleet of N replicas behind the central
    priority queue (the PR 11 control loop with autoscale/chaos off —
    a static fleet is just its degenerate mode). Exercises fleet
    dispatch, the per-replica snapshot rollup (skip-and-flag via
    ``ServingFleet.aggregate``), and the pod-shape registry rollup;
    throughput is still ONE host's worth of compute."""
    from paddle_tpu.observability import fleet as obs_fleet
    from paddle_tpu.observability import metrics
    from paddle_tpu.serving import FleetConfig, ServingFleet
    from paddle_tpu.serving.loadgen import replay_fleet

    fl = ServingFleet(
        model, serving_config(args), draft_model=draft_model,
        fleet=FleetConfig(replicas=args.replicas, min_replicas=1,
                          max_replicas=args.replicas, autoscale=False,
                          # the bench ladder need not cover every
                          # resumable prefix: no chaos, no requeue
                          requeue=False))
    stats, _finished, _shed = replay_fleet(fl, trace)
    summ = stats.pop("fleet")
    stats["replicas"] = args.replicas
    stats["per_replica_requests"] = [
        fl._replicas[s].finished_total for s in sorted(fl._replicas)]
    stats["recompile_events"] = summ["recompile_events"]
    stats["executables"] = summ["executables"]
    stats["expected_executables"] = summ["expected_executables"]
    # per-replica snapshot rollup (dead replicas skip-and-flag)...
    replica_rollup = fl.aggregate()
    stats["replicas_reporting"] = \
        replica_rollup["fleet.sources_reporting"]["value"]
    # ...and the pod-rollup shape over the shared registry (identical
    # call under jax.distributed on a real multi-host fleet)
    merged = obs_fleet.aggregate(metrics.snapshot(prefix="serving."))
    stats["fleet_rollup_keys"] = len(merged)
    return stats


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--requests", type=int, default=40)
    ap.add_argument("--rate", type=float, default=60.0,
                    help="open-loop arrival rate, requests/s")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prompt-lens", default="4,6,8,12,16,24,40",
                    help="prompt-length mix the trace draws from")
    ap.add_argument("--new-tokens", default="4,8,12,16,24,32",
                    help="generation-budget mix the trace draws from")
    ap.add_argument("--static-batch", type=int, default=4)
    ap.add_argument("--replicas", type=int, default=1)
    # raw-speed levers (ISSUE 16) — any of them arms the engine
    # baseline leg and the >=2x raw-speed bar
    ap.add_argument("--quant", choices=("int8", "bf16", "f32"),
                    default=None,
                    help="serve precision for the measured leg "
                         "(int8 = PTQ weights + int8 matmuls)")
    ap.add_argument("--speculative", type=int, default=0, metavar="K",
                    help="draft/verify speculative decoding, K "
                         "proposals per boundary")
    ap.add_argument("--draft-layers", type=int, default=1)
    ap.add_argument("--prefix-sharing", action="store_true",
                    help="radix/COW prefix page sharing")
    ap.add_argument("--tp", type=int, default=1, metavar="N",
                    help="tensor-parallel width for the measured leg "
                         "(MeshPlan(tp=N) shard_map engine; forces N "
                         "virtual CPU devices; headline metric "
                         "becomes serving_tp_tokens_per_sec)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    metavar="LEN",
                    help="trace-wide common prompt prefix length "
                         "(0 = off)")
    ap.add_argument("--shared-frac", type=float, default=0.9,
                    help="fraction of requests carrying the shared "
                         "prefix")
    ap.add_argument("--baseline-dtype", default="bfloat16",
                    help="plain-engine baseline leg dtype (the PR 9 "
                         "fingerprint); '' = f32")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 unless the CPU receipt bars hold")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a chrome trace with request lanes "
                         "(one lane per replica, spans colored by "
                         "latency component)")
    # engine shape
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--admit", type=int, default=4)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--n-blocks", type=int, default=128)
    ap.add_argument("--prefill-buckets", default="16,32,48")
    ap.add_argument("--decode-chunk", type=int, default=4)
    ap.add_argument("--max-total", type=int, default=80)
    ap.add_argument("--dtype", default="",
                    help="engine+static serve dtype; ''=f32 parity "
                         "mode (CPU default), bfloat16 on TPU")
    # model shape (tiny CPU default)
    ap.add_argument("--vocab", type=int, default=211)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--max-seq-len", type=int, default=128)
    args = ap.parse_args(argv)
    args.dtype = args.dtype or None
    args.baseline_dtype = args.baseline_dtype or None

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if args.tp > 1:
        # before the backend initializes: the tp mesh needs N devices
        from tools._force_cpu import force_cpu_devices
        force_cpu_devices(args.tp)
    from paddle_tpu.observability import exporters, metrics, reqtrace
    from paddle_tpu.serving.loadgen import replay_static, synthetic_trace
    from tools.tpu_doctor import serving_breach_verdict

    metrics.enable()
    model = build_model(args)
    trace = synthetic_trace(
        args.requests, vocab_size=args.vocab, seed=args.seed,
        rate_rps=args.rate,
        prompt_len_choices=tuple(
            int(x) for x in args.prompt_lens.split(",")),
        new_token_choices=tuple(
            int(x) for x in args.new_tokens.split(",")),
        shared_prefix_len=args.shared_prefix,
        shared_frac=args.shared_frac)
    draft = build_draft(args) if args.speculative else None

    tracing_overhead = None
    try:     # the gate is process-global: never leak it on an error
        if args.replicas > 1:
            # fleet path: one replay, traced (the rollup receipt is
            # the point here, not an overhead A/B)
            reqtrace.enable()
            reqtrace.reset()
            engine_stats = run_replicated(model, args, trace,
                                          draft_model=draft)
        else:
            # headline leg with tracing OFF, then the SAME trace with
            # tracing ON: the traced replay yields the tail
            # attribution and the measured overhead penalty (open-loop
            # arrivals pace both legs, so the spans are comparable)
            reqtrace.disable()
            engine_stats = run_engine_leg(model, args, trace,
                                          draft_model=draft)
            reqtrace.enable()
            reqtrace.reset()
            traced_stats = run_engine_leg(model, args, trace,
                                          draft_model=draft)
            tps_off = engine_stats["sustained_tokens_per_sec"]
            tps_on = traced_stats["sustained_tokens_per_sec"]
            penalty = (max(0.0, 1.0 - tps_on / tps_off)
                       if tps_off > 0 else -1.0)
            tracing_overhead = {
                "tokens_per_sec_off": tps_off,
                "tokens_per_sec_on": tps_on,
                "penalty": round(penalty, 4),
            }
        tail = reqtrace.explain_tail()
        breach = serving_breach_verdict(tail, summary=engine_stats)
        if args.trace:
            from paddle_tpu import profiler
            profiler.export_chrome_tracing(args.trace)
    finally:
        reqtrace.disable()

    raw = raw_speed_on(args)
    baseline_stats = None
    int8_parity = None
    if raw:
        # the PR 9 fingerprint: same trace, plain engine at
        # --baseline-dtype, no raw-speed levers, untraced
        baseline_stats = run_engine_leg(model, args, trace, fast=False)
    if raw:
        # the int8 accuracy receipt rides EVERY raw-speed artifact
        # (PTQ on the fly — independent of the measured leg's quant):
        # top-1 agreement vs the f32 parity reference + logit drift
        # bounded relative to the bf16 round-off it replaces
        import jax.numpy as jnp
        import numpy as np
        from paddle_tpu.models.decoder import DecoderSpec
        from paddle_tpu.models.generation import _gpt_params
        from paddle_tpu.quant.int8_serving import logits_drift_receipt
        L = min(t.ids.size for t in trace[:4])
        ids = jnp.asarray(np.stack([t.ids[:L] for t in trace[:4]]),
                          jnp.int32)
        int8_parity = logits_drift_receipt(
            _gpt_params(model), DecoderSpec.of(model.gpt.config), ids)

    static_cold = replay_static(model, trace,
                                batch_size=args.static_batch,
                                dtype=args.dtype)
    static_warm = replay_static(model, trace,
                                batch_size=args.static_batch,
                                dtype=args.dtype)

    tps_e = engine_stats["sustained_tokens_per_sec"]
    tps_cold = static_cold["sustained_tokens_per_sec"]
    tps_warm = static_warm["sustained_tokens_per_sec"]
    speedup_cold = round(tps_e / tps_cold, 3) if tps_cold > 0 else -1.0
    speedup_warm = round(tps_e / tps_warm, 3) if tps_warm > 0 else -1.0
    p99_e = engine_stats["ttft_ms"]["p99"]
    p99_s = static_cold["ttft_ms"]["p99"]
    zero_recompiles = engine_stats.get("recompile_events", -1) == 0
    tail_ok = bool(
        tail["cohort"]
        and all(abs(c["share_sum"] - 1.0) <= 0.02 and c["dominant"]
                for c in tail["cohort"]))
    # the <=3% tracing-penalty bar holds on arrival-paced traces (the
    # tier-1 methodology); a raw-speed receipt run is deliberately
    # OVERLOADED so its spans are server-paced and the off/on A/B is
    # scheduler noise — report the measurement, gate only when the
    # trace shape makes it meaningful. A --tp leg gets the same
    # waiver: per-step time on N virtual CPU devices is dominated by
    # multi-device dispatch jitter, so the off/on A/B is noise there
    # too (the tp pins — parity, executables, 1/tp bytes — gate).
    penalty_ok = (raw or args.tp > 1 or tracing_overhead is None
                  or 0.0 <= tracing_overhead["penalty"] <= 0.03)
    ok = (speedup_cold >= 2.0 and p99_e <= p99_s and zero_recompiles
          and tail_ok and penalty_ok)

    raw_extras = {}
    if raw:
        tps_base = baseline_stats["sustained_tokens_per_sec"]
        speedup_raw = (round(tps_e / tps_base, 3) if tps_base > 0
                       else -1.0)
        p99_base = baseline_stats["ttft_ms"]["p99"]
        raw_ok = speedup_raw >= 2.0 and p99_e <= p99_base
        raw_extras = {
            "engine_baseline": baseline_stats,
            "baseline_dtype": args.baseline_dtype or "float32",
            "speedup_vs_engine_baseline": speedup_raw,
            "p99_ttft_ms_engine_baseline": p99_base,
            "raw_speed": {"quant": args.quant,
                          "speculative_k": args.speculative,
                          "prefix_sharing": args.prefix_sharing,
                          "shared_prefix_len": args.shared_prefix},
            "raw_speed_ok": raw_ok,
        }
        if int8_parity is not None:
            # bounded drift: int8 stays within an order of magnitude
            # of the bf16 round-off it replaces (absolute floor for
            # tiny-logit models)
            drift_ok = (int8_parity["logit_drift_int8"]
                        <= max(1.0,
                               20.0 * int8_parity["logit_drift_bf16"]))
            raw_extras["int8_parity"] = dict(int8_parity,
                                             drift_bounded=drift_ok)
            raw_ok = raw_ok and drift_ok
            raw_extras["raw_speed_ok"] = raw_ok
        ok = ok and raw_ok

    tp_extras = {}
    if args.tp > 1 and args.replicas == 1:
        tp_pin = tp_parity_probe(model, args, trace)
        tp_ok = (tp_pin["f32_greedy_parity"]
                 and tp_pin["executables"]
                 == tp_pin["expected_executables"]
                 and tp_pin["pool_bytes_per_chip"] * args.tp
                 == tp_pin["pool_bytes"])
        tp_extras = {"tp_serving": dict(tp_pin, tp_ok=tp_ok)}
        ok = ok and tp_ok

    report = {
        "metric": ("serving_tp_tokens_per_sec" if args.tp > 1
                   else "serving_raw_speed_tokens_per_sec" if raw
                   else "serving_sustained_tokens_per_sec"),
        "value": tps_e,
        "unit": "tokens/s",
        "vs_baseline": speedup_cold,
        "extras": {
            "engine": engine_stats,
            "static_cold": static_cold,
            "static_warm": static_warm,
            "speedup_vs_static_cold": speedup_cold,
            "speedup_vs_static_warm": speedup_warm,
            "p99_ttft_ms_engine": p99_e,
            "p99_ttft_ms_static": p99_s,
            "zero_steady_state_recompiles": zero_recompiles,
            "tail_attribution": tail,
            "breach_verdict": breach,
            "tail_components_sum_ok": tail_ok,
            "tracing_overhead": tracing_overhead,
            **raw_extras,
            **tp_extras,
            "receipt_ok": ok,
        },
    }
    report = exporters.emit_report(
        report, jsonl_path=os.environ.get("PD_OBS_JSONL"),
        prefix="serving")
    print("serving_bench:", json.dumps(report), flush=True)
    if args.check and not ok:
        print(f"RECEIPT FAILED: speedup_cold={speedup_cold} (need "
              f">=2.0), p99 {p99_e} vs {p99_s} (need <=), "
              f"zero_recompiles={zero_recompiles}, "
              f"tail_ok={tail_ok}, "
              f"tracing_overhead={tracing_overhead}, "
              f"raw_speed={raw_extras.get('raw_speed_ok', 'n/a')} "
              f"(speedup_vs_engine_baseline="
              f"{raw_extras.get('speedup_vs_engine_baseline', 'n/a')},"
              f" need >=2.0 at equal-or-better p99 TTFT), "
              f"tp={tp_extras.get('tp_serving', 'n/a')}", flush=True)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
