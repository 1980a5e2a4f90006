"""A decoder LM of routed-expert layers over grouped-query attention,
window and full attention mixed by layer (inference only).

The stack is the common modern one (RMSNorm, no biases, grouped
key-value heads, a per-head RMSNorm on q and k, rotary positions, an
untied head) with every MLP a dropless routed-expert layer: a token's
router probabilities over all the experts, its top-k of them
renormalised, each a SwiGLU of `moe_intermediate_size`
(models/decoder.py, `_experts`). `layer_types` gives each layer its
attention: `sliding_attention` sees the last `sliding_window` keys and
turns q and k by plain rotary angles; `full_attention` sees every key
before it and may turn them by YaRN's (`rope_scaling`).

The layer may hold a share of the experts (`experts_held`, a [lo, hi)
range of ids, as one chip of an expert-parallel deployment does): it
routes over all of them and returns its own experts' part.

The class holds the parameters under the published names, with the
experts of a layer stacked as transformers' fused expert modules hold
them (`mlp.experts.gate_up_proj` [E, H, 2F], `mlp.experts.down_proj`
[E, F, H], input-major as every weight here) and describes its block as
a `DecoderSpec`; the arithmetic is `decoder.block`. `forward` is the
plain full-sequence pass; `serving.ServingEngine` serves the model
through its paged cache. There is no training path.
"""
from __future__ import annotations

import jax.numpy as jnp

from .. import nn
from ..framework import Tensor
from . import decoder
from .decoder import DecoderSpec
from .retention import _RMSNorm, _linear

__all__ = ["MoEConfig", "MoEForCausalLM"]

_KINDS = ("sliding_attention", "full_attention")


class MoEConfig:
    def __init__(self, vocab_size=98304, hidden_size=2304, num_layers=28,
                 num_heads=32, num_kv_heads=4, head_dim=128,
                 num_experts=64, num_experts_per_tok=8,
                 moe_intermediate_size=896, norm_topk_prob=True,
                 experts_held=None, layer_types=None, sliding_window=1024,
                 rope_theta=500000.0, rope_scaling=None,
                 max_seq_len=131072, rms_norm_eps=1e-6):
        if num_heads % num_kv_heads:
            raise ValueError(
                f"num_kv_heads={num_kv_heads} must divide num_heads="
                f"{num_heads}: a query head reads key-value head "
                "i // (num_heads / num_kv_heads)")
        layer_types = tuple(layer_types or ("full_attention",) * num_layers)
        if len(layer_types) != num_layers or \
                set(layer_types) - set(_KINDS):
            raise ValueError(f"layer_types: {num_layers} of {_KINDS}, "
                             f"got {layer_types}")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_intermediate_size = moe_intermediate_size
        self.norm_topk_prob = norm_topk_prob
        self.experts_held = None if experts_held is None \
            else tuple(int(e) for e in experts_held)
        self.layer_types = layer_types
        self.sliding_window = sliding_window
        self.rope_theta = rope_theta
        # YaRN on the full-attention layers: {"factor",
        # "original_max_position_embeddings", "beta_fast", "beta_slow",
        # "attention_factor"}; None: plain rotary angles there too
        self.rope_scaling = rope_scaling
        self.max_seq_len = max_seq_len
        self.rms_norm_eps = rms_norm_eps

    @property
    def n_held(self) -> int:
        lo, hi = self.experts_held or (0, self.num_experts)
        return hi - lo

    def decoder_spec(self) -> DecoderSpec:
        y = self.rope_scaling
        yarn = None if y is None else tuple(float(y[k]) for k in (
            "factor", "original_max_position_embeddings", "beta_fast",
            "beta_slow", "attention_factor"))
        full = [t == "full_attention" for t in self.layer_types]
        return DecoderSpec(
            eps=float(self.rms_norm_eps), n_heads=int(self.num_heads),
            head_dim=int(self.head_dim), norm="rms", bias=False,
            n_kv_heads=int(self.num_kv_heads), qk_norm=True,
            rope_theta=float(self.rope_theta), mlp="moe", tied_head=False,
            n_experts=int(self.num_experts),
            top_k=int(self.num_experts_per_tok),
            expert_width=int(self.moe_intermediate_size),
            norm_topk=bool(self.norm_topk_prob),
            experts_held=self.experts_held,
            windows=tuple(None if f else int(self.sliding_window)
                          for f in full),
            ropes=tuple(yarn if f else None for f in full))


class _Attention(nn.Layer):
    def __init__(self, c: MoEConfig):
        super().__init__()
        h, hd = c.hidden_size, c.head_dim
        self.q_proj = _linear(h, c.num_heads * hd)
        self.k_proj = _linear(h, c.num_kv_heads * hd)
        self.v_proj = _linear(h, c.num_kv_heads * hd)
        self.o_proj = _linear(c.num_heads * hd, h)
        self.q_norm = _RMSNorm(hd)
        self.k_norm = _RMSNorm(hd)


class _Experts(nn.Layer):
    """The held experts of one layer, stacked: gate and up side by side
    in one [E, H, 2F] (gate first), down [E, F, H]."""

    def __init__(self, c: MoEConfig):
        super().__init__()
        e, h, f = c.n_held, c.hidden_size, c.moe_intermediate_size
        self.gate_up_proj = self.create_parameter((e, h, 2 * f))
        self.down_proj = self.create_parameter((e, f, h))


class _SparseMLP(nn.Layer):
    def __init__(self, c: MoEConfig):
        super().__init__()
        self.gate = _linear(c.hidden_size, c.num_experts)      # router
        self.experts = _Experts(c)


class _Layer(nn.Layer):
    def __init__(self, c: MoEConfig):
        super().__init__()
        self.input_layernorm = _RMSNorm(c.hidden_size)
        self.self_attn = _Attention(c)
        self.post_attention_layernorm = _RMSNorm(c.hidden_size)
        self.mlp = _SparseMLP(c)


class _Model(nn.Layer):
    def __init__(self, c: MoEConfig):
        super().__init__()
        self.embed_tokens = nn.Embedding(c.vocab_size, c.hidden_size)
        self.layers = nn.LayerList([_Layer(c)
                                    for _ in range(c.num_layers)])
        self.norm = _RMSNorm(c.hidden_size)


def _block_params(layer: _Layer) -> dict:
    a, m = layer.self_attn, layer.mlp
    return {
        "ln1_w": layer.input_layernorm.weight._data,
        "q_w": a.q_proj.weight._data, "k_w": a.k_proj.weight._data,
        "v_w": a.v_proj.weight._data, "proj_w": a.o_proj.weight._data,
        "qn_w": a.q_norm.weight._data, "kn_w": a.k_norm.weight._data,
        "ln2_w": layer.post_attention_layernorm.weight._data,
        "router_w": m.gate.weight._data,
        "experts_gate_up_w": m.experts.gate_up_proj._data,
        "experts_down_w": m.experts.down_proj._data,
    }


class MoEForCausalLM(nn.Layer):
    def __init__(self, config: MoEConfig = None, **kwargs):
        super().__init__()
        self.config = config or MoEConfig(**kwargs)
        self.model = _Model(self.config)
        self.lm_head = _linear(self.config.hidden_size,
                               self.config.vocab_size)

    def decoder_spec(self) -> DecoderSpec:
        return self.config.decoder_spec()

    def decoder_params(self) -> dict:
        """The tree `decoder.block` reads (models/decoder.py's header)."""
        m = self.model
        return {"wte": m.embed_tokens.weight._data,
                "lnf_w": m.norm.weight._data,
                "head_w": self.lm_head.weight._data,
                "blocks": [_block_params(l) for l in m.layers]}

    def forward(self, input_ids):
        """Logits [B, S, V] of whole sequences: every position attends
        its own past (the last `sliding_window` of it on a sliding
        layer) under a dense mask."""
        ids = input_ids._data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        spec, params = self.decoder_spec(), self.decoder_params()
        s = ids.shape[1]

        def attend(_, q, k, v, window=None):
            kc, vc = (jnp.einsum("bsnh->bnsh", t) for t in (k, v))
            return decoder.masked_attention(
                q, kc, vc, decoder.causal_mask(s, window=window),
                spec.scale), None

        x = decoder.blocks(spec, params, decoder.embed(params, ids, None),
                           None, attend, jnp.arange(s))[0]
        return Tensor(decoder.final_logits(spec, params, x))
