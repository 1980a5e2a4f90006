"""A decoder LM of power-retention layers (inference only).

The stack is the common modern one — RMSNorm, no biases, grouped
key-value heads, a per-head RMSNorm on q and k, rotary positions, a
SwiGLU MLP, an untied head — with every softmax attention replaced by a
power retention layer (Manifest AI, "Scaling Context Requires
Rethinking Attention", arXiv:2507.04239): a gated linear attention
whose kernel is `(q.k)^2`, so a request holds a fixed-size state per
layer and key-value head in place of a K/V cache that grows
(models/decoder.py, "the retention mixer").

The class holds the parameters under the published names and describes
its block as a `DecoderSpec`; the arithmetic is `decoder.block`, the one
body every servable model runs. `forward` is the plain full-sequence
pass (the quadratic form, no state); `serving.ServingEngine` serves the
model through state rows (serving/state_cache.py). There is no training
path: `forward` runs outside the autograd tape.
"""
from __future__ import annotations

import jax.numpy as jnp

from .. import nn
from ..framework import Tensor
from ..nn.initializer import Constant
from . import decoder
from .decoder import DecoderSpec

__all__ = ["RetentionConfig", "RetentionForCausalLM"]


class RetentionConfig:
    def __init__(self, vocab_size=151936, hidden_size=5120, num_layers=40,
                 num_heads=40, num_kv_heads=8, head_dim=128,
                 intermediate_size=17408, max_seq_len=32768,
                 rms_norm_eps=1e-6, rope_theta=1e6):
        if num_heads % num_kv_heads:
            raise ValueError(
                f"num_kv_heads={num_kv_heads} must divide num_heads="
                f"{num_heads}: a query head reads key-value head "
                "i // (num_heads / num_kv_heads)")
        if head_dim % 2:
            raise ValueError(f"head_dim={head_dim} must be even (rotary "
                             "halves, and the state's circular offsets)")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.intermediate_size = intermediate_size
        self.max_seq_len = max_seq_len
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta

    def decoder_spec(self) -> DecoderSpec:
        return DecoderSpec(
            eps=float(self.rms_norm_eps), n_heads=int(self.num_heads),
            head_dim=int(self.head_dim), norm="rms", bias=False,
            n_kv_heads=int(self.num_kv_heads), qk_norm=True,
            rope_theta=float(self.rope_theta), mlp="swiglu",
            tied_head=False, mixer="retention")


class _RMSNorm(nn.Layer):
    """Holds the gain; the arithmetic is `decoder._rms`."""

    def __init__(self, size):
        super().__init__()
        self.weight = self.create_parameter(
            (size,), default_initializer=Constant(1.0))


def _linear(n_in, n_out, bias=False):
    return nn.Linear(n_in, n_out, bias_attr=None if bias else False)


class _Retention(nn.Layer):
    def __init__(self, c: RetentionConfig):
        super().__init__()
        h, hd = c.hidden_size, c.head_dim
        self.q_proj = _linear(h, c.num_heads * hd)
        self.k_proj = _linear(h, c.num_kv_heads * hd)
        self.v_proj = _linear(h, c.num_kv_heads * hd)
        self.o_proj = _linear(c.num_heads * hd, h)
        # one log-sigmoid gate a key-value head, the only bias
        self.g_proj = _linear(h, c.num_kv_heads, bias=True)
        self.q_norm = _RMSNorm(hd)
        self.k_norm = _RMSNorm(hd)


class _SwiGLU(nn.Layer):
    def __init__(self, c: RetentionConfig):
        super().__init__()
        self.gate_proj = _linear(c.hidden_size, c.intermediate_size)
        self.up_proj = _linear(c.hidden_size, c.intermediate_size)
        self.down_proj = _linear(c.intermediate_size, c.hidden_size)


class _Layer(nn.Layer):
    def __init__(self, c: RetentionConfig):
        super().__init__()
        self.input_layernorm = _RMSNorm(c.hidden_size)
        self.self_attn = _Retention(c)
        self.post_attention_layernorm = _RMSNorm(c.hidden_size)
        self.mlp = _SwiGLU(c)


class _Model(nn.Layer):
    def __init__(self, c: RetentionConfig):
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
        "g_w": a.g_proj.weight._data, "g_b": a.g_proj.bias._data,
        "qn_w": a.q_norm.weight._data, "kn_w": a.k_norm.weight._data,
        "ln2_w": layer.post_attention_layernorm.weight._data,
        "gate_w": m.gate_proj.weight._data, "up_w": m.up_proj.weight._data,
        "down_w": m.down_proj.weight._data,
    }


class RetentionForCausalLM(nn.Layer):
    def __init__(self, config: RetentionConfig = None, **kwargs):
        super().__init__()
        self.config = config or RetentionConfig(**kwargs)
        self.model = _Model(self.config)
        self.lm_head = _linear(self.config.hidden_size,
                               self.config.vocab_size)

    def decoder_spec(self) -> DecoderSpec:
        return self.config.decoder_spec()

    def decoder_params(self) -> dict:
        """The tree `decoder.block` reads (the module's header)."""
        m = self.model
        return {"wte": m.embed_tokens.weight._data,
                "lnf_w": m.norm.weight._data,
                "head_w": self.lm_head.weight._data,
                "blocks": [_block_params(l) for l in m.layers]}

    def forward(self, input_ids):
        """Logits [B, S, V] of whole sequences: every position attends
        its own past through the quadratic form."""
        ids = input_ids._data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        spec, params = self.decoder_spec(), self.decoder_params()

        def attend(_, q, k, v, gate):
            return decoder.retained_attention(q, k, v, gate), None

        x, _ = decoder.blocks(spec, params, decoder.embed(params, ids, None),
                              None, attend, jnp.arange(ids.shape[1]))
        return Tensor(decoder.final_logits(spec, params, x))
