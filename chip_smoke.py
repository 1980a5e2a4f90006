#!/usr/bin/env python3
"""First light: the two main paths, once each, on the chip.

    python3 chip_smoke.py                    # on a TPU; fails without one
    python3 chip_smoke.py --rehearse-on-cpu  # toy widths, CPU, says so

One process — the only one that touches jax — runs, in order:

  trainer  ErnieForPretraining at ERNIE-base width (hidden 768, 12
           layers, 12 heads, vocab 30,528, seq 512, batch 48) through
           static.TrainStep: AdamW, AMP O1 bf16, attention dropout on,
           twenty steps on one seeded batch
  server   GPTForCausalLM at GPT-base width (GPTConfig() defaults)
           behind serving.ServingEngine in its default bf16 mode:
           warmup(), then a seeded serving.loadgen trace

and, where jax shows four or more devices, the same two on a mesh: the
ERNIE step on {"dp": 2, "tp": 2} against the one-chip first loss, and
the engine under ServingConfig(plan=MeshPlan(tp=2)).

Every check that fails raises; no leg is wrapped, so any failure is a
non-zero exit and no result line. Seconds printed are wall-clock
receipts of set-up (compile) and steady phases, never a rate and never
under a metric's name. The last line of a passing run is one JSON
object: {"ok": true, "device": {"platform", "kind", "count"}}.
"""
import argparse
import gc
import json
import math
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# widths: the chip run is the full width of both models; the rehearsal
# exists to debug this script's control flow on the CPU, nothing more
CHIP = dict(
    ernie=dict(vocab_size=30528, hidden_size=768, num_hidden_layers=12,
               num_attention_heads=12, intermediate_size=3072,
               max_position_embeddings=512),
    # 20 steps, not a handful: AdamW at the bench's constant 1e-4 has no
    # warm-up, and at this width its first sign-like steps overshoot —
    # on the chip the loss zig-zags for eight steps (11.04, 17.59,
    # 11.13, 15.04, ...) and only then falls (10.60 at step 20, 10.39
    # at 40). A CPU run at the same width zig-zags the same way, so it
    # is the recipe, not the chip; a step costs about a quarter second.
    batch=48, seq=512, steps=20,
    gpt=dict(),  # GPTConfig() defaults ARE GPT-base
    requests=32)
TOY = dict(
    ernie=dict(vocab_size=512, hidden_size=64, num_hidden_layers=2,
               num_attention_heads=4, intermediate_size=128,
               max_position_embeddings=64),
    batch=8, seq=64, steps=6,
    gpt=dict(vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
             max_seq_len=256),
    requests=12)

# the first loss is ln(vocab) + ln 2 (uniform MLM + NSP guesses) plus
# the variance of the untrained logits; anything outside is a wrong
# graph, not noise
FIRST_LOSS_BAND = 0.5
# one chip against dp2 x tp2, same seed and batch: the kernel's dropout
# masks key on global rows and jax.random is layout-invariant, so only
# bf16 matmul partial-sum order differs
MESH_LOSS_TOL = 0.02

_STAMP = ""


def say(msg):
    print(f"[chip_smoke {_STAMP}] {msg}", flush=True)


def check(ok, what):
    if not ok:
        raise AssertionError(f"chip_smoke check failed: {what}")


def _cache_hits():
    from paddle_tpu.observability import metrics
    return metrics.counter("jax.compile_cache.hits", _always=True).value()


# -- trainer -----------------------------------------------------------------

def _ernie_step(w, mesh_shape=None):
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.models import ErnieConfig, ErnieForPretraining
    from paddle_tpu.static import TrainStep

    paddle.seed(0)
    cfg = ErnieConfig(**w["ernie"])
    model = ErnieForPretraining(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 weight_decay=0.01)
    kw = {}
    if mesh_shape is not None:
        mesh = dist.build_mesh(mesh_shape)
        kw = dict(mesh=mesh, sharding_plan=dist.ShardingPlan(mesh))
    step = TrainStep(model, ErnieForPretraining.pretraining_loss, opt,
                     amp_level="O1", amp_dtype="bfloat16", **kw)
    rng = np.random.RandomState(0)
    shape = (w["batch"], w["seq"])
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, shape).astype(np.int32))
    mlm = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, shape).astype(np.int32))
    nsp = paddle.to_tensor(
        rng.randint(0, 2, (w["batch"],)).astype(np.int32))
    return cfg, step, ids, (mlm, nsp)


def _train(w, step, ids, labels, tag):
    """Run the steps; returns the losses. Step 1 is set-up (trace +
    compile + run), the rest is the steady phase."""
    hits0 = _cache_hits()
    t0 = time.perf_counter()
    losses = [float(step(ids, labels).item())]
    setup_s = time.perf_counter() - t0
    first_step_hits = _cache_hits() - hits0
    t0 = time.perf_counter()
    for _ in range(w["steps"] - 1):
        losses.append(float(step(ids, labels).item()))
    steady_s = time.perf_counter() - t0
    say(f"{tag}: losses " + " ".join(f"{v:.4f}" for v in losses))
    say(f"{tag}: setup_seconds={setup_s:.1f} (step 1, compile included; "
        f"persistent-cache hits during it: {first_step_hits}) "
        f"steady_seconds={steady_s:.2f} for {w['steps'] - 1} steps")
    check(all(math.isfinite(v) for v in losses),
          f"{tag}: a loss is not finite: {losses}")
    check(losses[-1] < losses[0],
          f"{tag}: loss did not fall: {losses[0]} -> {losses[-1]}")
    execs = int(step._step_fn._cache_size())
    fired = step.recompile_sentinel.fired
    say(f"{tag}: train_executables={execs} recompiles_after_step_1={fired}")
    check(execs == 1 and fired == 0,
          f"{tag}: {execs} train executables, {fired} recompile events")
    return losses


def trainer_leg(w, on_tpu):
    from paddle_tpu.nn.functional.attention import attention_dropout_impl

    cfg, step, ids, labels = _ernie_step(w)
    say(f"trainer: ErnieForPretraining hidden={cfg.hidden_size} "
        f"layers={cfg.num_hidden_layers} vocab={cfg.vocab_size} "
        f"batch={w['batch']} seq={w['seq']} AdamW AMP-O1-bf16 "
        f"attention_dropout={cfg.attention_probs_dropout_prob}")
    losses = _train(w, step, ids, labels, "trainer")
    want = math.log(cfg.vocab_size) + math.log(2.0)
    check(abs(losses[0] - want) <= FIRST_LOSS_BAND,
          f"trainer: first loss {losses[0]:.4f} outside ln(vocab)+ln2="
          f"{want:.4f} +- {FIRST_LOSS_BAND}")

    # which attention ran: the dispatch (platform alone decides) and
    # the kernels named in the lowered step
    impl = attention_dropout_impl()
    text = step.aot_lower((ids._data,),
                          tuple(t._data for t in labels)).as_text()
    kernels = {k: len(re.findall(rf"\b{k}\b", text)) for k in (
        "flash_fwd_dropout", "flash_bwd_dq_dropout",
        "flash_bwd_dkv_dropout")}
    say(f"trainer: attention_dropout_impl={impl} "
        f"mosaic_kernels_in_lowered_step={kernels}")
    if on_tpu:
        check(impl == "kernel" and all(kernels.values()),
              "trainer: attention did not run the Pallas kernel with "
              f"in-kernel dropout (impl={impl}, kernels={kernels})")
    else:
        check(impl == "sdpa" and not any(kernels.values()),
              f"trainer: CPU reference path expected, got impl={impl}")
    return losses[0]


def trainer_mesh_leg(w, on_tpu, one_chip_first_loss):
    """The same step on dp2 x tp2: same global batch, same seed."""

    cfg, step, ids, labels = _ernie_step(w, {"dp": 2, "tp": 2})
    losses = _train(w, step, ids, labels, "trainer[dp2xtp2]")
    delta = abs(losses[0] - one_chip_first_loss)
    say(f"trainer[dp2xtp2]: first_loss={losses[0]:.4f} one_chip="
        f"{one_chip_first_loss:.4f} |delta|={delta:.4f} "
        f"(tolerance {MESH_LOSS_TOL})")
    check(delta <= MESH_LOSS_TOL,
          f"trainer[dp2xtp2]: first loss differs from one chip by {delta}")

    # spread, not stacked on the first chip: a tp-annotated weight is
    # cut in two over 'tp', and every chip of the mesh holds state
    name = "ernie.encoder.0.ffn_in.weight"
    arr = step.params[name]
    shards = arr.addressable_shards
    shard_devs = sorted(s.device.id for s in shards)
    say(f"trainer[dp2xtp2]: {name} {tuple(arr.shape)} -> "
        f"{len(shards)} shards of {tuple(shards[0].data.shape)} on "
        f"devices {shard_devs}")
    check(len(set(shard_devs)) == 4
          and shards[0].data.shape[1] * 2 == arr.shape[1],
          f"trainer[dp2xtp2]: {name} is not split over tp on 4 chips")
    stats = [d.memory_stats() for d in step.mesh.devices.flat]
    if all(s is not None for s in stats):  # the CPU reports none
        # live bytes, not the peak: the peak of chip 0 is the one-chip
        # leg this process ran first
        live = [int(s["bytes_in_use"]) for s in stats]
        say(f"trainer[dp2xtp2]: per-device bytes_in_use={live}")
        check(min(live) > 0.5 * max(live),
              f"trainer[dp2xtp2]: memory is not spread: {live}")

    # the compiled step: every Mosaic call takes this chip's rows only
    # (batch/dp x heads/tp), never the gathered [batch x heads] grid.
    # Compiled past the persistent cache, whose hits may carry no text.
    from paddle_tpu.observability.anatomy import compile_uncached
    text = compile_uncached(step.aot_lower(
        (ids._data,), tuple(t._data for t in labels))).as_text()
    rows = (w["batch"] // 2) * (cfg.num_attention_heads // 2)
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    local = [ln for ln in calls if f"[{rows},{w['seq']}," in ln]
    gathers = len(re.findall(r"= \S+ all-gather(?:-start)?\(", text))
    say(f"trainer[dp2xtp2]: compiled step has {len(calls)} Mosaic calls, "
        f"{len(local)} on local [{rows},{w['seq']},..] operands; "
        f"{gathers} all-gathers in the whole step (the fused-qkv "
        "projection output is gathered over tp before the head split — "
        "known, ROADMAP A6)")
    if on_tpu:
        check(calls and len(local) == len(calls),
              "trainer[dp2xtp2]: a Mosaic call takes non-local operands")


# -- server ------------------------------------------------------------------

def _serve(w, plan, tag):
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import ServingConfig, ServingEngine, loadgen

    paddle.seed(0)
    cfg = GPTConfig(**w["gpt"])
    model = GPTForCausalLM(cfg)
    model.eval()
    scfg = ServingConfig(plan=plan)
    eng = ServingEngine(model, scfg)
    say(f"{tag}: GPTForCausalLM hidden={cfg.hidden_size} "
        f"layers={cfg.num_layers} vocab={cfg.vocab_size} "
        f"max_seq_len={cfg.max_seq_len} dtype={scfg.dtype} tp={scfg.tp}")
    t0 = time.perf_counter()
    eng.warmup()
    setup_s = time.perf_counter() - t0

    trace = loadgen.synthetic_trace(w["requests"],
                                    vocab_size=cfg.vocab_size, seed=0)
    asked = {}
    finished = []
    t0 = time.perf_counter()
    nxt = 0
    while nxt < len(trace) or eng.has_work():
        now = time.perf_counter() - t0
        while nxt < len(trace) and trace[nxt].arrival_s <= now:
            it = trace[nxt]
            asked[eng.submit(it.ids, it.max_new_tokens)] = it
            nxt += 1
        if eng.has_work():
            finished.extend(eng.step())
        else:
            time.sleep(max(trace[nxt].arrival_s - now, 0.0))
    steady_s = time.perf_counter() - t0
    say(f"{tag}: setup_seconds={setup_s:.1f} (warmup, "
        f"{eng.executable_count()} executables compiled) "
        f"steady_seconds={steady_s:.2f} for {len(trace)} requests, "
        f"{sum(len(r.out) for r in finished)} tokens")

    check(sorted(r.rid for r in finished) == sorted(asked),
          f"{tag}: {len(finished)} of {len(asked)} requests finished")
    for r in finished:
        check(len(r.out) == asked[r.rid].max_new_tokens,
              f"{tag}: request {r.rid} got {len(r.out)} tokens, asked "
              f"{asked[r.rid].max_new_tokens}")
        check(all(0 <= t < cfg.vocab_size for t in r.out),
              f"{tag}: request {r.rid} emitted an id outside the vocab")
    execs, want = eng.executable_count(), eng.expected_executables
    say(f"{tag}: executables={execs} expected_executables={want} "
        f"recompile_events={eng.sentinel.fired}")
    check(execs == want and eng.sentinel.fired == 0,
          f"{tag}: compile ladder grew ({execs} vs {want}, "
          f"{eng.sentinel.fired} recompile events)")
    eng.cache.check_invariants()
    st = eng.cache.stats()
    say(f"{tag}: pages live={st['pages_live']} free={st['pages_free']} "
        f"scratch={st['pages_scratch']} of {scfg.n_blocks}")
    check(st["pages_live"] == 0
          and st["pages_free"] + 1 == scfg.n_blocks,
          f"{tag}: page pool not conserved at the end: {st}")
    return eng, st


def server_leg(w):
    _serve(w, None, "server")


def server_tp_leg(w):
    from paddle_tpu.distributed import MeshPlan

    eng, st = _serve(w, MeshPlan(tp=2), "server[tp2]")
    k_pool = eng.cache.pools[0][0]
    shards = k_pool.addressable_shards
    devs = sorted(s.device.id for s in shards)
    say(f"server[tp2]: K pool {tuple(k_pool.shape)} -> {len(shards)} "
        f"shards of {tuple(shards[0].data.shape)} on devices {devs}; "
        f"pool_bytes={st['pool_bytes']} "
        f"pool_bytes_per_chip={st['pool_bytes_per_chip']}")
    check(len(set(devs)) == 2
          and shards[0].data.shape[2] * 2 == k_pool.shape[2],
          "server[tp2]: K/V pool is not split over heads on 2 chips")
    check(st["pool_bytes_per_chip"] * 2 == st["pool_bytes"],
          "server[tp2]: pool_bytes_per_chip != pool_bytes / tp")


# -- entry -------------------------------------------------------------------

def main(argv=None):
    global _STAMP
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--rehearse-on-cpu", action="store_true",
        help="toy widths on the CPU backend, to debug this script; "
             "every line says so and nothing it prints is a device fact")
    args = ap.parse_args(argv)
    if args.rehearse_on_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    from paddle_tpu.core.flags import apply_compile_cache
    from paddle_tpu.observability import sentinel
    from paddle_tpu.ops.cfast import cfast_module

    cache_dir = apply_compile_cache()
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    on_tpu = dev["platform"] == "tpu"
    if not on_tpu and not args.rehearse_on_cpu:
        print(f"chip_smoke: needs a TPU, jax found platform="
              f"{dev['platform']} ({dev['count']} x {dev['kind']}); "
              "the CPU rehearsal is --rehearse-on-cpu", file=sys.stderr)
        return 2
    _STAMP = (f"platform={dev['platform']} device_kind=\"{dev['kind']}\" "
              f"devices={dev['count']}")
    if args.rehearse_on_cpu:
        _STAMP += " REHEARSAL toy-widths-on-cpu"
    w = TOY if args.rehearse_on_cpu else CHIP
    sentinel.attach_jax_compile_hook()
    say(f"compile_cache_dir={cache_dir} "
        f"c_fast_dispatch_loaded={cfast_module() is not None}")

    # gc between legs: a leg's params and pools must leave the chip
    # before the next leg's arrive
    t0 = time.perf_counter()
    first_loss = trainer_leg(w, on_tpu)
    gc.collect()
    server_leg(w)
    gc.collect()
    if dev["count"] >= 4:
        trainer_mesh_leg(w, on_tpu, first_loss)
        gc.collect()
        server_tp_leg(w)
    else:
        say(f"mesh legs not run: {dev['count']} device(s), need 4")
    say(f"all legs passed in {time.perf_counter() - t0:.0f} wall seconds; "
        f"persistent compile cache hits this run: {_cache_hits()}")
    if args.rehearse_on_cpu:
        say("rehearsal only: no result line")
        return 0
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
