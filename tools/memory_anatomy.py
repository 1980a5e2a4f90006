#!/usr/bin/env python
"""memory_anatomy CLI: which scope owns the HBM of each flagship
program, and has any program's peak quietly grown.

The memory twin of tools/step_anatomy.py + tools/graph_lint.py: lowers
each flagship program ONCE (metadata-preserving, cache-bypassed —
anatomy's compile_uncached discipline), reads XLA's buffer assignment
through observability.memory, prints the per-scope byte share tables
(shares sum to 1.0 with an `unattributed` row), and gates program-peak
growth against a checked-in baseline the way graph_lint gates new
findings.

Programs (all by default; shapes flag-tunable, tiny CPU smoke sizes):
  train      the ERNIE TrainStep (AMP O1 bf16) — its ONE executable
  spmd       the spmd_1f1b one-program pipeline engine (2 stages)
  planner    the MeshPlan ONE-executable train step, one program per
             layout (dp×tp×pp and fsdp×pp) — per-layout peaks gate
             spec-derivation regressions
  serving    the continuous-batching prefill + chunked-decode programs
             at the largest ladder buckets (donated page pools)
  serving_tp the tp=2 tensor-parallel twins at the SAME shapes —
             per-chip rows proving pool+weight bytes ≈ 1/tp (+ε for
             the tp all-reduce scratch)

Baselines (tools/memory_baseline.json by default):
  --check            exit 1 when a program's peak exceeds its baseline
                     by the tolerance (+20% default) — the finding
                     names the program AND the top-growth scope
  --write-baseline   re-anchor deliberately after triaging
  --from-json FILE   re-check previously computed results (a prior
                     --json-out) without recompiling — the CI re-gate
                     and triage-host path (no jax needed to decide)
  --inflate prog:x   multiply a program's measured peak by x — the
                     chaos lever the regression drill uses to prove
                     the gate trips (tests/test_memory_anatomy.py)

Always prints a final ``memory_anatomy: {json}`` receipt line; gauges
ride the always-on memory.* series when --publish is given.

Usage:
  python tools/memory_anatomy.py                        # tables only
  python tools/memory_anatomy.py --check                # CI gate
  python tools/memory_anatomy.py --write-baseline
  python tools/memory_anatomy.py --from-json out.json --check
"""
import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

N_DEV = int(os.environ.get("PD_MEMANAT_DEVICES", 2))
DEFAULT_BASELINE = os.path.join(REPO, "tools", "memory_baseline.json")

#: per-layout predicted HBM (bytes/chip) from the plan's cost model,
#: filled by build_planner — printed next to each measured peak and
#: carried (with the delta) in the final receipt. PR 18's plan-audit
#: join for the memory plane.
PLANNER_PREDICTED = {}


def _force_cpu_devices(n=None):
    """CPU XLA with >=2 virtual devices for the spmd program (inside
    pytest the conftest already forced 8)."""
    from tools._force_cpu import force_cpu_devices
    return force_cpu_devices(N_DEV if n is None else n)


def build_train(args):
    """The ERNIE TrainStep's one executable (AMP O1, the bench/lint
    configuration at smoke size). Returns (name, lowered)."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models import ErnieConfig, ErnieForPretraining
    from paddle_tpu.static import TrainStep

    paddle.seed(0)
    cfg = ErnieConfig(vocab_size=args.vocab, hidden_size=args.hidden,
                      num_hidden_layers=args.layers,
                      num_attention_heads=args.heads,
                      intermediate_size=args.hidden * 4,
                      max_position_embeddings=max(args.seq, 64))
    model = ErnieForPretraining(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                                 parameters=model.parameters())
    step = TrainStep(
        model, lambda o, l: ErnieForPretraining.pretraining_loss(o, l),
        opt, amp_level="O1", amp_dtype="bfloat16")
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size,
                      (args.batch, args.seq)).astype(np.int32)
    lbl = rng.randint(0, cfg.vocab_size,
                      (args.batch, args.seq)).astype(np.int32)
    lowered = step.aot_lower((paddle.to_tensor(ids),),
                             (paddle.to_tensor(lbl),))
    return [("train_step", lowered)]


def build_spmd(args):
    """The spmd_1f1b one-program pipeline engine (2 stages, lint
    shapes)."""
    import numpy as np
    import jax
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    import paddle_tpu.nn as nn

    S = min(2, jax.device_count())
    width, M, batch = args.width, 2, 8
    mesh = dist.build_mesh({"pp": S}, devices=jax.devices()[:S])
    paddle.seed(0)
    stages = [nn.Sequential(nn.Linear(width, width), nn.ReLU())
              for _ in range(S)]
    eng = dist.PipelineParallel(
        stages, lambda o, y: ((o - y) ** 2).mean(),
        paddle.optimizer.SGD(learning_rate=1e-3),
        num_micro=M, mesh=mesh, exec_mode="spmd_1f1b")
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(batch, width).astype(np.float32))
    y = paddle.to_tensor(rng.randn(batch, width).astype(np.float32))
    return [("spmd_1f1b", eng.aot_lower_train(x, y))]


def build_planner(args):
    """The MeshPlan-driven ONE-executable train step, one program PER
    LAYOUT: the same 2-stage model compiled under dp×tp×pp and under
    fsdp×pp. Per-layout peaks are the planner's memory contract — a
    spec-derivation regression (a param silently replicated where the
    plan says sharded) grows exactly one layout's peak, and the gate
    names it."""
    import numpy as np
    import jax
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    import paddle_tpu.nn as nn
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.distributed.sharding import MeshPlan

    n = jax.device_count()
    layouts = [("planner_dp2_tp2_pp2",
                dict(dp=2 if n >= 8 else 1, tp=2 if n >= 4 else 1,
                     pp=2)),
               ("planner_fsdp2_pp2",
                dict(fsdp=2 if n >= 4 else 1, pp=2))]
    width, M, batch = args.width, 2, 8
    out = []
    for name, sizes in layouts:
        paddle.seed(0)

        class _Stage(nn.Layer):
            def __init__(self):
                super().__init__()
                self.lin = nn.Linear(width, width)
                self.lin.weight.sharding_spec = P(None, "tp")
                self.lin.bias.sharding_spec = P("tp")

            def forward(self, xx):
                return paddle.tanh(self.lin(xx))

        plan = MeshPlan(**sizes)
        eng = dist.PipelineParallel(
            [_Stage() for _ in range(2)],
            lambda o, y: ((o - y) ** 2).mean(),
            paddle.optimizer.SGD(learning_rate=1e-3),
            num_micro=M, mesh=plan.build_mesh(),
            exec_mode="spmd_1f1b", plan=plan)
        rng = np.random.RandomState(0)
        x = paddle.to_tensor(rng.randn(batch, width).astype(np.float32))
        y = paddle.to_tensor(rng.randn(batch, width).astype(np.float32))
        out.append((name, eng.aot_lower_train(x, y)))
        # the plan's own HBM prediction for this layout (PR 18): the
        # cost model's candidate-report number in bytes/chip, joined
        # against the measured buffer-assignment peak below. SGD has
        # no moment slots; the 2-layer stack is 2 "layers" of width².
        try:
            from paddle_tpu.distributed.sharding import ModelDims
            dims = ModelDims(
                n_params=2 * (width * width + width), hidden=width,
                n_layers=2, seq=1, batch=batch, opt_slots=0)
            receipt = plan.predict(dims, num_micro=M)
            PLANNER_PREDICTED[name] = int(receipt.predicted_hbm_bytes)
        except Exception:
            pass  # prediction is observability: never sinks the table
    return out


def build_serving(args):
    """The serving prefill + chunked-decode programs at the largest
    ladder buckets (donated page pools — the pools ARE serving HBM)."""
    import numpy as np
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import ServingConfig, ServingEngine

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=256, hidden_size=args.srv_hidden,
                    num_layers=2, num_heads=4, max_seq_len=128,
                    dropout=0.0, use_flash_attention=False)
    model = GPTForCausalLM(cfg)
    model.eval()
    eng = ServingEngine(model, ServingConfig(
        max_slots=4, max_admit=2, block_size=8, n_blocks=32,
        prefill_buckets=(32,), decode_chunk=2,
        max_total_tokens=64, dtype=None))
    W = eng.config.table_width
    a, s, b = eng.sched.max_admit, 32, eng.config.max_slots
    key = jax.random.key(0)
    prefill = eng._prefill.lower(          # the admit batch, packed
        eng.cache.pools, np.zeros((a, W), np.int32),
        np.zeros((1, a * s), np.int32), np.zeros((a,), np.int32),
        np.ones((a,), np.int32), eng.params, key)
    decode = eng._decode.lower(
        eng.cache.pools, np.zeros((b, W), np.int32),
        np.zeros((b,), np.int32), np.zeros((b,), np.int32),
        eng.params, key)
    return [("serving_prefill", prefill), ("serving_decode", decode)]


def build_serving_tp(args):
    """The tp=2 tensor-parallel serving programs at the SAME shapes as
    the serving group — XLA's buffer assignment is per chip, so these
    rows against their tp=1 twins are the 1/tp receipt: per-chip pool
    + sharded-weight bytes halve (replicated tables/embeddings and the
    tp all-reduce scratch are the +ε)."""
    import numpy as np
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.distributed.sharding import MeshPlan
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import ServingConfig, ServingEngine

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=256, hidden_size=args.srv_hidden,
                    num_layers=2, num_heads=4, max_seq_len=128,
                    dropout=0.0, use_flash_attention=False)
    model = GPTForCausalLM(cfg)
    model.eval()
    eng = ServingEngine(model, ServingConfig(
        max_slots=4, max_admit=2, block_size=8, n_blocks=32,
        prefill_buckets=(32,), decode_chunk=2,
        max_total_tokens=64, dtype=None, plan=MeshPlan(tp=2)))
    W = eng.config.table_width
    a, s, b = eng.sched.max_admit, 32, eng.config.max_slots
    key = jax.random.key(0)
    prefill = eng._prefill.lower(          # the admit batch, packed
        eng.cache.pools, np.zeros((a, W), np.int32),
        np.zeros((1, a * s), np.int32), np.zeros((a,), np.int32),
        np.ones((a,), np.int32), eng.params, key)
    decode = eng._decode.lower(
        eng.cache.pools, np.zeros((b, W), np.int32),
        np.zeros((b,), np.int32), np.zeros((b,), np.int32),
        eng.params, key)
    return [("serving_prefill_tp2", prefill),
            ("serving_decode_tp2", decode)]


def compute(args) -> dict:
    """Lower + attribute every requested program. Returns
    program -> attribute_compiled_memory result."""
    builders = {"train": build_train, "spmd": build_spmd,
                "planner": build_planner, "serving": build_serving,
                "serving_tp": build_serving_tp}
    want = [p.strip() for p in args.programs.split(",") if p.strip()]
    # the planner layouts want a dp×tp×pp mesh — 8 virtual devices;
    # serving_tp needs >=2 (N_DEV's floor already covers it)
    _force_cpu_devices(max(N_DEV, 8) if "planner" in want else None)
    from paddle_tpu.observability import memory as mem

    unknown = [p for p in want if p not in builders]
    if unknown:
        raise SystemExit(f"unknown program(s) {unknown}; "
                         f"pick from {sorted(builders)}")
    results = {}
    for group in want:
        for name, lowered in builders[group](args):
            res = mem.program_memory(name, lowered,
                                     publish_gauges=args.publish)
            print(mem.format_table(res, title=name), flush=True)
            pred = PLANNER_PREDICTED.get(name)
            if pred is not None:
                meas = int(res["memory"]["peak_bytes"])
                err = abs(pred - meas) / max(pred, meas, 1)
                print(f"  predicted HBM/chip (plan cost model): "
                      f"{pred:,}  measured peak: {meas:,}  "
                      f"error: {err:.1%}", flush=True)
            results[name] = res
    return results


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--programs",
                    default="train,spmd,planner,serving,serving_tp",
                    help="comma-separated flagship set "
                         "(train,spmd,planner,serving)")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE)
    ap.add_argument("--check", action="store_true",
                    help="gate peaks against the baseline (exit 1 on "
                         "a regression, names program + scope)")
    ap.add_argument("--write-baseline", action="store_true",
                    help="re-anchor the baseline to current peaks")
    ap.add_argument("--tolerance", type=float, default=None,
                    help="override the baseline's growth tolerance")
    ap.add_argument("--from-json", default=None, metavar="FILE",
                    help="re-check a prior --json-out instead of "
                         "recompiling (triage hosts, CI re-gates)")
    ap.add_argument("--json-out", default=None)
    ap.add_argument("--publish", action="store_true",
                    help="publish memory.* gauges for the exporters")
    ap.add_argument("--inflate", default="", metavar="PROG:FACTOR",
                    help="seed a synthetic peak regression (drill "
                         "lever), e.g. train_step:1.25")
    # train shapes (lint-sized defaults)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--width", type=int, default=32,
                    help="spmd stage width")
    ap.add_argument("--srv-hidden", type=int, default=32)
    args = ap.parse_args(argv)

    from paddle_tpu.analysis import memory_baseline as mb

    if args.from_json:
        with open(args.from_json) as f:
            doc = json.load(f)
        peaks = doc.get("peaks") or doc
    else:
        results = compute(args)
        peaks = mb.peaks_of(results)

    # the drill lever inflates a COPY: --json-out and --write-baseline
    # persist REAL peaks only — an inflated baseline would silently
    # waive that much genuine growth forever
    checked = peaks
    for spec in [s for s in args.inflate.split(",") if s.strip()]:
        prog, _, factor = spec.partition(":")
        if prog not in checked:
            raise SystemExit(f"--inflate: unknown program {prog!r} "
                             f"(have {sorted(checked)})")
        f = float(factor or 1.0)
        if checked is peaks:
            checked = {k: dict(v) for k, v in peaks.items()}
        checked[prog]["peak_bytes"] = int(
            checked[prog]["peak_bytes"] * f)
        # the seeded growth lands on the dominant real scope too, so
        # the tripped finding names a scope exactly like a genuine
        # regression (a re-materialized buffer grows SOME scope's rows)
        scopes = dict(checked[prog].get("scopes", {}))
        named = [s for s in scopes if s != "unattributed"]
        if named:
            top = max(named, key=scopes.get)
            scopes[top] = int(scopes[top] * f)
            checked[prog]["scopes"] = scopes

    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump({"peaks": peaks}, f, indent=1)

    if args.write_baseline:
        mb.write_memory_baseline(
            peaks, args.baseline,
            tolerance=(mb.DEFAULT_TOLERANCE if args.tolerance is None
                       else args.tolerance))
        print(f"memory baseline re-anchored: {len(peaks)} program(s) "
              f"-> {args.baseline}", flush=True)

    findings = []
    rc = 0
    if args.check:
        baseline = mb.load_memory_baseline(args.baseline)
        findings = mb.check_memory_baseline(checked, baseline,
                                            tolerance=args.tolerance)
        for f in findings:
            print(f.summary(), flush=True)
        rc = 1 if any(f.severity == "error" for f in findings) else 0

    summary = {
        "programs": sorted(checked),
        "peak_bytes": {p: checked[p]["peak_bytes"] for p in checked},
        # measured-vs-predicted join for the planner layouts (PR 18):
        # symmetric relative error, same definition as the plan-audit
        # plane, so the receipt and the gauges agree
        "planner_predicted_hbm": {
            p: {"predicted_bytes": pred,
                "measured_bytes": int(checked[p]["peak_bytes"]),
                "error": round(
                    abs(pred - checked[p]["peak_bytes"])
                    / max(pred, checked[p]["peak_bytes"], 1), 4)}
            for p, pred in sorted(PLANNER_PREDICTED.items())
            if p in checked},
        "findings": len(findings),
        "regressions": sum(1 for f in findings
                           if f.severity == "error"),
        "baseline": args.baseline if (args.check
                                      or args.write_baseline) else None,
        "ok": rc == 0,
    }
    print("memory_anatomy:", json.dumps(summary), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
