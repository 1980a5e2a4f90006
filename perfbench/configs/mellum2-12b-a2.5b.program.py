"""How `mellum2-12b-a2.5b` is built out of the program: MoEForCausalLM
at the configuration's sizes (the first `num_hidden_layers` of the
published `layer_types`), its weights laid in from the reference's
`make_params` (made on the device in one jitted call from the seed, in
the type they are served in), behind a warmed ServingEngine of the one
engine shape the configuration's file states. The engine takes a
page's row width from the model's block: the 4 key-value heads.
"""
from __future__ import annotations


def model_config(config: dict):
    """The program's MoEConfig of the configuration's file."""
    from paddle_tpu.models import MoEConfig
    full = config["rope_parameters"]["full_attention"]
    return MoEConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], num_experts=config["num_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        norm_topk_prob=config["norm_topk_prob"],
        layer_types=config["layer_types"][:config["num_hidden_layers"]],
        sliding_window=config["sliding_window"],
        rope_theta=full["rope_theta"],
        rope_scaling=full if full["rope_type"] == "yarn" else None,
        max_seq_len=config["max_position_embeddings"],
        rms_norm_eps=config["rms_norm_eps"])


def build(config: dict, seed: int, weights: dict, engine_overrides=None):
    import paddle_tpu as paddle
    try:
        from paddle_tpu.models import MoEForCausalLM
    except ImportError:
        raise SystemExit("perfbench: this program has no MoEForCausalLM "
                         "(paddle_tpu/models): it cannot run the "
                         "configuration mellum2-12b-a2.5b")
    from paddle_tpu.serving import ServingConfig, ServingEngine
    from paddle_tpu.utils.abstract_init import abstract_parameters

    paddle.seed(int(seed) & 0x7FFFFFFF)
    with abstract_parameters():
        model = MoEForCausalLM(model_config(config))
    model.eval()
    state = model.state_dict()
    if sorted(state) != sorted(weights):
        raise SystemExit(
            "perfbench: the reference's parameter table and the "
            "program's state_dict differ: "
            f"{sorted(set(state) ^ set(weights))[:6]}")
    for name, tensor in state.items():
        if tuple(tensor.shape) != tuple(weights[name].shape):
            raise SystemExit(f"perfbench: shape of {name} differs")
        tensor._data = weights[name]
    e = {**config["engine"], **(engine_overrides or {})}
    e["prefill_buckets"] = tuple(e["prefill_buckets"])
    eng = ServingEngine(model, ServingConfig(seed=int(seed) & 0x7FFFFFFF,
                                             **e))
    eng.warmup()
    return eng
