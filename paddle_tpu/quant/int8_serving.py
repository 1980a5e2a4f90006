"""True-int8 serving compute: the PTQ weight path for the decode engine.

The quant package's Frozen* layers already prove the discipline on the
nn side: per-output-channel abs-max weight scales
(``channel_wise_abs_max``), int8×int8→int32 ``dot_general`` on the
MXU's double-rate path (v5e: 394 int8 TOPS vs 197 bf16 TFLOPS), f32
rescale by ``s_x * s_w``. This module is the same math with NO nn
dependency — the serving engine's weight snapshot is a raw params
pytree (models/generation._gpt_params), so the quantized form must be
a pytree too: each block matmul weight ``<name>_w`` becomes a dict
leaf ``{"q8": int8 [in, out], "s": f32 [out]}`` that rides through
jit as TRACED arguments (scale tables never bake into the executable
— graph_lint's baked-constant rule stays clean) and through
``swap_weights`` like any other leaf.

Activations quantize DYNAMICALLY in-graph (per-row abs-max, the
QuantizationTransformPass rationale: stateless, no calibration pass,
exact for the row it scales). Embeddings, layernorms, biases and the
weight-tied lm_head stay in the serving float dtype; sampling stays
f32 — the int8 surface is exactly the four block matmuls
(qkv/proj/fc1/fc2) that dominate decode FLOPs and weight bytes.

Accuracy contract: greedy top-1 agreement vs the f32 parity engine is
receipted per-token by serving_bench (``--quant int8``), with the
logit drift bounded against the bf16 cast as the reference yardstick.
"""
from __future__ import annotations

import numpy as np

__all__ = ["quantize_weight", "quantize_params", "int8_matmul",
           "logits_drift_receipt", "QUANT_WEIGHT_KEYS"]

# the block matmuls that carry the int8 path (generation._mm consumers)
QUANT_WEIGHT_KEYS = ("qkv_w", "proj_w", "fc1_w", "fc2_w")


def _qmax(bits: int) -> int:
    return (1 << (bits - 1)) - 1


def quantize_weight(w, bits: int = 8):
    """Per-output-channel abs-max PTQ of one ``[in, out]`` (or
    ``[..., out]``) matmul weight — the channel_wise_abs_max freeze
    discipline, data-free. Returns the serving pytree leaf
    ``{"q8": int8 codes, "s": f32 dequant factor [out]}`` with
    ``w ≈ q8 * s`` (``s`` pre-divided by qmax so dequant is one
    multiply)."""
    import jax.numpy as jnp
    qmax = _qmax(int(bits))
    arr = np.asarray(w, np.float32)
    axes = tuple(range(arr.ndim - 1))
    scale = np.maximum(np.abs(arr).max(axis=axes), 1e-8)
    q = np.clip(np.round(arr / scale * qmax),
                -qmax - 1, qmax).astype(np.int8)
    return {"q8": jnp.asarray(q),
            "s": jnp.asarray((scale / qmax).astype(np.float32))}


def quantize_params(params, qcfg=None):
    """The engine's int8 build-time cast: every block's four matmul
    weights become int8+scale leaves; everything else (embeddings,
    norms, biases, already-cast floats) passes through untouched. The
    tree STRUCTURE changes — swap_weights re-runs this same transform
    so a standby pool always lands with the matching treedef.

    Order matters under a tp plan: the snapshot build permutes the
    fused-qkv columns head-major BEFORE calling this (quantization is
    per-COLUMN, so permuting float columns permutes codes and scales
    identically — the {"q8","s"} leaves then shard by the float
    parent's SERVING_TP_RULES spec: codes like the weight, scales
    like its output columns)."""
    bits = int(getattr(qcfg, "weight_bits", 8) or 8)
    out = dict(params)
    out["blocks"] = [
        {k: (quantize_weight(v, bits) if k in QUANT_WEIGHT_KEYS else v)
         for k, v in bp.items()}
        for bp in params["blocks"]]
    return out


def int8_matmul(x, q8, s):
    """``x @ w`` through the int8 pipeline: dynamic per-row abs-max
    activation quantization (f32 → int8 codes), int8×int8→int32
    ``dot_general`` (``preferred_element_type`` keeps the accumulator
    exact), then one f32 rescale by ``s_x * s_w``. Output returns in
    x's dtype so the residual stream keeps the serving float dtype."""
    import jax
    import jax.numpy as jnp
    qmax = 127.0
    xf = x.astype(jnp.float32)
    sx = jnp.max(jnp.abs(xf), axis=-1, keepdims=True) / qmax
    sx = jnp.maximum(sx, 1e-12)
    codes = jnp.clip(jnp.round(xf / sx), -128.0, qmax).astype(jnp.int8)
    acc = jax.lax.dot_general(
        codes, q8, (((codes.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32) * sx * s).astype(x.dtype)


def logits_drift_receipt(params, spec, ids, qcfg=None):
    """The accuracy receipt's numeric half: last-position logits over
    one f32 prompt forward, compared across the three serving casts.
    Returns max-abs logit drift for int8 and for bf16 (the reference
    yardstick the ISSUE bounds int8 against) plus whether the greedy
    top-1 tokens agree on these prompts."""
    import jax.numpy as jnp
    from ..models.decoder import final_logits
    from ..models.generation import _cast_params, _prefill

    def last_logits(p):
        x, _ = _prefill(spec, p, ids, ids.shape[1])
        return final_logits(spec, p, x[:, -1]).astype(jnp.float32)

    l32 = last_logits(params)
    l8 = last_logits(quantize_params(params, qcfg))
    lb = last_logits(_cast_params(params, "bfloat16"))
    drift8 = float(jnp.max(jnp.abs(l8 - l32)))
    driftb = float(jnp.max(jnp.abs(lb - l32)))
    agree = float(jnp.mean(
        (jnp.argmax(l8, -1) == jnp.argmax(l32, -1)).astype(
            jnp.float32)))
    return {"logit_drift_int8": round(drift8, 6),
            "logit_drift_bf16": round(driftb, 6),
            "top1_agreement_last": round(agree, 4)}
