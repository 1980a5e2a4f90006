"""How `brumby-14b-base` is built out of the program:
RetentionForCausalLM at the configuration's sizes, its weights laid in
from the reference's `make_params` (made on the device in one jitted
call from the seed, in the type they are served in), behind a warmed
ServingEngine of the one engine shape the configuration's file states.
The engine takes the cache kind from the model's block: state rows, no
pages (`n_blocks` counts the rows).
"""
from __future__ import annotations


def build(config: dict, seed: int, weights: dict, engine_overrides=None):
    import paddle_tpu as paddle
    try:
        from paddle_tpu.models import RetentionConfig, RetentionForCausalLM
    except ImportError:
        raise SystemExit("perfbench: this program has no "
                         "RetentionForCausalLM (paddle_tpu/models): it "
                         "cannot run the configuration brumby-14b-base")
    from paddle_tpu.serving import ServingConfig, ServingEngine
    from paddle_tpu.utils.abstract_init import abstract_parameters

    paddle.seed(int(seed) & 0x7FFFFFFF)
    rcfg = RetentionConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        intermediate_size=config["intermediate_size"],
        max_seq_len=config["max_position_embeddings"],
        rms_norm_eps=config["rms_norm_eps"],
        rope_theta=config["rope_theta"])
    with abstract_parameters():
        model = RetentionForCausalLM(rcfg)
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
