#!/usr/bin/env python
"""Step anatomy: which component of the ONE fused train step costs what.

tools/tpu_breakdown.py times components in ISOLATION (separately-jitted
programs — indicative, but fusion/overlap effects across component
boundaries are invisible). This tool reads the real thing:

  static   per-scope FLOPs shares from the compiled single-dispatch
           ERNIE step's own HLO (observability.anatomy) — runs anywhere,
           CPU included; the "which component grew" receipt
  device   (--trace, hardware) a jax.profiler capture around N live
           steps, parsed by observability.xprof: per-scope device ms,
           idle time, and the comm-overlap receipt
           (comm.overlap_fraction — ROADMAP 3(d)'s decision input)

Both tables use the SAME scope taxonomy as tpu_breakdown.py's
components, so isolated and in-situ numbers line up column-for-column.

Runs where jax puts it: the bench shape on a TPU, the smoke shapes
under an explicit JAX_PLATFORMS=cpu. Every stage is error-isolated and
the final "anatomy:" JSON line is always printed.

Usage: python tools/step_anatomy.py [--trace] [--steps N] [--json-out F]
Env:   PD_ANATOMY_{VOCAB,HIDDEN,LAYERS,HEADS,INTER,BATCH,SEQ} override
       the CPU smoke shapes (the tier-1 smoke runs tiny).
"""
import argparse
import json
import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _smoke_shape(name, default):
    return int(os.environ.get(f"PD_ANATOMY_{name}", default))


def build_step(on_tpu):
    """The bench-shape ERNIE TrainStep (TPU) or the env-tunable CPU
    smoke config. Returns (step, ids, lbl, config_dict)."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models import ErnieConfig, ErnieForPretraining
    from paddle_tpu.static import TrainStep

    if on_tpu:
        v, h, L, nh, inter, b, s = (30528, 768, 12, 12, 3072, 48, 512)
    else:
        v = _smoke_shape("VOCAB", 2048)
        h = _smoke_shape("HIDDEN", 128)
        L = _smoke_shape("LAYERS", 2)
        nh = _smoke_shape("HEADS", 4)
        inter = _smoke_shape("INTER", 512)
        b = _smoke_shape("BATCH", 4)
        s = _smoke_shape("SEQ", 64)
    paddle.seed(0)
    cfg = ErnieConfig(vocab_size=v, hidden_size=h, num_hidden_layers=L,
                      num_attention_heads=nh, intermediate_size=inter,
                      max_position_embeddings=s)
    model = ErnieForPretraining(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 weight_decay=0.01)
    step = TrainStep(
        model, lambda o, l: ErnieForPretraining.pretraining_loss(o, l),
        opt, amp_level="O1", amp_dtype="bfloat16")
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, v, (b, s)).astype(np.int32))
    lbl = paddle.to_tensor(rng.randint(0, v, (b, s)).astype(np.int32))
    shape = {"vocab": v, "hidden": h, "layers": L, "batch": b, "seq": s}
    return step, ids, lbl, shape


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--trace", action="store_true",
                    help="also capture a live profile and run the "
                         "device-time tier (hardware)")
    ap.add_argument("--steps", type=int, default=3,
                    help="traced steps for --trace")
    ap.add_argument("--json-out", default=None)
    args = ap.parse_args(argv)

    import jax
    from paddle_tpu.observability import anatomy, xprof

    on_tpu = jax.devices()[0].platform == "tpu"

    results = {"on_tpu": bool(on_tpu)}

    def section(name, fn):
        try:
            fn()
        except Exception as e:  # pragma: no cover — hardware quirks
            results[f"{name}_error"] = f"{type(e).__name__}: {e}"[:300]
            print(f"# {name} failed: {results[f'{name}_error']}",
                  flush=True)

    holder = {}

    def build():
        step, ids, lbl, shape = build_step(on_tpu)
        results["shape"] = shape
        float(step(ids, lbl).item())  # compile + settle
        holder.update(step=step, ids=ids, lbl=lbl)

    section("build", build)

    def static_tier():
        res = anatomy.train_step_anatomy(
            holder["step"], (holder["ids"],), (holder["lbl"],),
            publish_gauges=True)
        print(anatomy.format_table(res, title="static anatomy"),
              flush=True)
        results["static"] = {
            "scope_shares": {k: round(v["share"], 4)
                             for k, v in res["scopes"].items()},
            "total_flops": res["total_flops"],
            "cost_analysis_flops": res["cost_analysis_flops"],
            "unattributed_share": round(res["unattributed_share"], 4),
        }
        results["recompiles"] = holder["step"].recompile_sentinel.fired

    if holder:
        section("static", static_tier)

    if args.trace and holder:
        def device_tier():
            step, ids, lbl = (holder["step"], holder["ids"],
                              holder["lbl"])
            d = tempfile.mkdtemp(prefix="pd_anatomy_xplane_")
            with jax.profiler.trace(d):
                for _ in range(args.steps):
                    loss = step(ids, lbl)
                float(loss.item())
            events = xprof.load_profile(d)
            dev = xprof.attribute_device_time(events, steps=args.steps)
            xprof.publish(dev)
            results["device"] = dev
            results["trace_dir"] = d
            print(xprof.format_top_ops(events, steps=args.steps),
                  flush=True)
            print("per-scope device ms/step:",
                  json.dumps(dev["per_scope_ms"]), flush=True)
            print("comm overlap receipt:", json.dumps(dev["comm"]),
                  flush=True)

        section("device", device_tier)

    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(results, f, indent=1)
    print("anatomy:", json.dumps(results), flush=True)
    return 0 if "build_error" not in results else 1


if __name__ == "__main__":
    sys.exit(main())
