#!/usr/bin/env python
"""Component-level step-time breakdown on real hardware.

Whole-step numbers and a profile top-list with "no single dominant
fusion" are not enough to target missing MFU. This tool times the
pieces in isolation so the next optimization round aims at measured
cost, not guesses:

  gemm      achievable bf16 GEMM TF/s at encoder shapes (the ceiling)
  attn      flash kernel vs SDPA, dropout on/off, fwd and fwd+bwd
  head      MLM head + fused softmax-CE fwd+bwd (≈20%% of model FLOPs)
  rng       one bernoulli mask at [b,h,s,s] (the sdpa-dropout tax)
  step      ERNIE TrainStep: fwd / fwd+bwd / fwd+bwd+opt splits

Every component is error-isolated: a Mosaic rejection or OOM in one
records an <name>_error entry and the rest still run, and the final
"breakdown:" summary line is always printed. It runs where jax puts
it: bench shapes on a TPU, smoke shapes under an explicit
JAX_PLATFORMS=cpu (whose timings are not device numbers).

All timings end on a host value read.

Usage: python tools/tpu_breakdown.py [--json-out FILE]
"""
import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402


def _sync(x):
    import jax
    if hasattr(x, "_data"):  # paddle_tpu Tensor
        x = x._data
    leaf = jax.tree_util.tree_leaves(x)[0]
    np.asarray(leaf).ravel()[:1]


def _time(fn, *args, iters=8):
    out = fn(*args)
    _sync(out)          # compile + settle
    out = fn(*args)
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    _sync(out)
    return (time.perf_counter() - t0) / iters


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json-out", default=None)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if on_tpu:
        b, s, h, n_heads, inter, vocab = 48, 512, 768, 12, 3072, 30528
    else:  # smoke shapes
        b, s, h, n_heads, inter, vocab = 4, 128, 256, 4, 1024, 8192
    hd = h // n_heads
    rows = b * s
    rng = np.random.RandomState(0)
    results = {"device": getattr(dev, "device_kind", dev.platform),
               "shape": {"batch": b, "seq": s, "hidden": h}}

    def emit(k, v):
        results[k] = v
        print(json.dumps({k: v}), flush=True)

    def section(name, fn):
        """Error isolation: one failing component records its error and
        the rest of the breakdown still runs."""
        try:
            fn()
        except Exception as e:  # pragma: no cover — hardware quirks
            emit(f"{name}_error", f"{type(e).__name__}: {e}"[:200])

    # -- gemm ceiling: the encoder's two FFN matmuls, bf16
    def comp_gemm():
        x = jnp.asarray(rng.randn(rows, h), jnp.bfloat16)
        w1 = jnp.asarray(rng.randn(h, inter), jnp.bfloat16)
        w2 = jnp.asarray(rng.randn(inter, h), jnp.bfloat16)
        ffn = jax.jit(lambda x: (x @ w1) @ w2)
        dt = _time(ffn, x)
        emit("gemm_ffn_tflops",
             round(2.0 * rows * h * inter * 2 / dt / 1e12, 1))

    section("gemm", comp_gemm)

    from paddle_tpu.ops import pallas_kernels as pk
    from paddle_tpu.nn.functional import attention as attn_mod
    q = jnp.asarray(rng.randn(b, s, n_heads, hd), jnp.float32) * 0.1
    attn_flops = 4.0 * b * n_heads * s * s * hd  # scores + values, fwd
    key = jax.random.key(0)

    # -- attention: both paths, dropout on/off, fwd and grad
    def comp_attn_pallas():
        dt = _time(lambda q: pk.flash_attention_mha(q, q, q), q)
        emit("attn_pallas_fwd_ms", round(dt * 1e3, 2))
        emit("attn_pallas_fwd_tflops", round(attn_flops / dt / 1e12, 1))
        g = jax.jit(jax.grad(lambda q: pk.flash_attention_mha(
            q, q, q).sum()))
        dt = _time(g, q)
        emit("attn_pallas_fwdbwd_ms", round(dt * 1e3, 2))

    def comp_attn_pallas_dropout():
        dt = _time(lambda q: pk.flash_attention_mha(
            q, q, q, dropout_p=0.1, seed=7), q)
        emit("attn_pallas_dropout_fwd_ms", round(dt * 1e3, 2))

    if on_tpu:
        section("attn_pallas", comp_attn_pallas)
        section("attn_pallas_dropout", comp_attn_pallas_dropout)

    def comp_attn_sdpa():
        sdpa = jax.jit(lambda q: attn_mod._sdpa_impl(
            q, q, q, None, 0.0, False, None))
        dt = _time(sdpa, q)
        emit("attn_sdpa_fwd_ms", round(dt * 1e3, 2))
        sdpa_drop = jax.jit(lambda q, k: attn_mod._sdpa_impl(
            q, q, q, None, 0.1, False, None, drop_key=k))
        dt = _time(lambda q: sdpa_drop(q, key), q)
        emit("attn_sdpa_dropout_fwd_ms", round(dt * 1e3, 2))
        sdpa_drop_g = jax.jit(jax.grad(lambda q, k: attn_mod._sdpa_impl(
            q, q, q, None, 0.1, False, None, drop_key=k).sum()))
        dt = _time(lambda q: sdpa_drop_g(q, key), q)
        emit("attn_sdpa_dropout_fwdbwd_ms", round(dt * 1e3, 2))

    section("attn_sdpa", comp_attn_sdpa)

    # -- rng: the sdpa-dropout mask tax in isolation
    def comp_rng():
        mask = jax.jit(lambda k: jax.random.bernoulli(
            k, 0.9, (b, n_heads, s, s)))
        dt = _time(mask, key)
        emit("rng_attn_mask_ms", round(dt * 1e3, 2))

    section("rng", comp_rng)

    # -- MLM head + fused CE (tied decoder: h @ E^T then softmax-CE)
    def comp_head():
        from paddle_tpu.nn.functional.loss import _softmax_ce_fused
        hstate = jnp.asarray(rng.randn(rows, h), jnp.float32) * 0.05
        emb = jnp.asarray(rng.randn(vocab, h), jnp.float32) * 0.05
        labels = jnp.asarray(rng.randint(0, vocab, (rows,)), jnp.int32)
        valid = jnp.ones((rows,), bool)

        def head_loss(hstate, emb):
            logits = (hstate.astype(jnp.bfloat16)
                      @ emb.astype(jnp.bfloat16).T)
            return _softmax_ce_fused(logits, labels, valid).mean()

        gh = jax.jit(jax.grad(head_loss, argnums=(0, 1)))
        dt = _time(gh, hstate, emb)
        emit("head_ce_fwdbwd_ms", round(dt * 1e3, 2))
        emit("head_ce_fwdbwd_tflops",
             round(3 * 2.0 * rows * h * vocab / dt / 1e12, 1))

    section("head", comp_head)

    # -- full train step splits
    def comp_step():
        import paddle_tpu as paddle
        from paddle_tpu.models import ErnieConfig, ErnieForPretraining
        from paddle_tpu.static import TrainStep
        paddle.seed(0)
        cfg = ErnieConfig(vocab_size=vocab, hidden_size=h,
                          num_hidden_layers=12 if on_tpu else 2,
                          num_attention_heads=n_heads,
                          intermediate_size=inter,
                          max_position_embeddings=s)
        model = ErnieForPretraining(cfg)
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=model.parameters(),
                                     weight_decay=0.01)
        step = TrainStep(
            model,
            lambda o, l: ErnieForPretraining.pretraining_loss(o, l),
            opt, amp_level="O1", amp_dtype="bfloat16")
        ids = paddle.to_tensor(
            rng.randint(0, vocab, (b, s)).astype(np.int32))
        lbl = paddle.to_tensor(
            rng.randint(0, vocab, (b, s)).astype(np.int32))

        dt_full = _time(lambda _=None: step(ids, lbl), iters=6)
        emit("step_full_ms", round(dt_full * 1e3, 2))

        # fwd-only and fwd+bwd through the same traced train-mode path
        # (step._forward_loss is the exact function _build
        # differentiates). CAVEAT recorded with the numbers: these are
        # separately-jitted programs WITHOUT the real step's buffer
        # donation, so step_opt_ms = full − fwdbwd is approximate and
        # can even go negative when the undonated grad program pays
        # extra HBM copies; treat splits as indicative, the full step
        # as ground truth.
        key2 = jax.random.key(1)
        raw_in, raw_lbl = (ids._data,), (lbl._data,)
        fwd_fn = jax.jit(lambda p, bufs: step._forward_loss(
            p, bufs, key2, raw_in, raw_lbl)[0])
        dt_fwd = _time(lambda _=None: fwd_fn(step.params, step.buffers),
                       iters=6)
        emit("step_fwd_ms", round(dt_fwd * 1e3, 2))

        grad_fn = jax.jit(jax.grad(lambda p, bufs: step._forward_loss(
            p, bufs, key2, raw_in, raw_lbl)[0]))
        dt_fb = _time(lambda _=None: grad_fn(step.params, step.buffers),
                      iters=6)
        emit("step_fwdbwd_ms", round(dt_fb * 1e3, 2))
        emit("step_opt_ms_approx", round((dt_full - dt_fb) * 1e3, 2))
        emit("step_bwd_share_approx",
             round((dt_fb - dt_fwd) / dt_full, 3))

    section("step", comp_step)

    def comp_eager():
        """Dispatch vs transport split for eager op overhead (how much
        is Python dispatch+enqueue, how much the device round-trip?).
        Three regimes on the same 4x4 add, device-resident inputs:
        - pipelined: N enqueues, ONE host fetch at the end (what
          bench_eager_dispatch measures) -> per-op enqueue cost
        - synced: host fetch EVERY op -> adds one device->host
          round-trip per op; the difference IS the transport latency
        - jit-cached direct: the same add through raw jax.jit without
          the registry/tape -> isolates the framework's Python layer
        """
        import paddle_tpu as paddle
        a = paddle.to_tensor(np.ones((4, 4), np.float32))
        bb = paddle.to_tensor(np.ones((4, 4), np.float32))
        np.asarray((a + bb)._data)          # warm compile
        n = 300
        t0 = time.perf_counter()
        for _ in range(n):
            c = a + bb
        np.asarray(c._data)
        emit("eager_pipelined_us",
             round((time.perf_counter() - t0) / n * 1e6, 1))
        t0 = time.perf_counter()
        for _ in range(n):
            np.asarray((a + bb)._data)
        emit("eager_synced_us",
             round((time.perf_counter() - t0) / n * 1e6, 1))
        f = jax.jit(lambda x, y: x + y)
        f(a._data, bb._data)
        t0 = time.perf_counter()
        for _ in range(n):
            r = f(a._data, bb._data)
        np.asarray(r)
        emit("eager_raw_jit_us",
             round((time.perf_counter() - t0) / n * 1e6, 1))
        # transport per round-trip = synced - pipelined; framework
        # python layer = pipelined - raw_jit

    section("eager_split", comp_eager)

    # -- scope-taxonomy rollup: the SAME rows observability.anatomy /
    # xprof / tools/step_anatomy.py report, filled from this tool's
    # ISOLATED timings — so the isolated and in-situ tables line up
    # column-for-column on the next hardware window ("attn here is the
    # same attn there"). Keys missing when their component errored.
    def scope_columns(res):
        cols = {}
        attn = res.get("attn_pallas_fwdbwd_ms",
                       res.get("attn_sdpa_dropout_fwdbwd_ms"))
        if attn is not None:
            cols["attn"] = attn
        if "head_ce_fwdbwd_ms" in res:
            cols["mlm_head_ce"] = res["head_ce_fwdbwd_ms"]
        if "step_opt_ms_approx" in res:
            cols["optimizer"] = res["step_opt_ms_approx"]
        if "step_full_ms" in res:
            cols["step_total"] = res["step_full_ms"]
        return cols

    emit("scope_ms", scope_columns(results))

    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(results, f, indent=1)
    print("breakdown:", json.dumps(results), flush=True)


if __name__ == "__main__":
    main()
