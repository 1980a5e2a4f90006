"""How `gpt2-large` is built out of the program: GPTForCausalLM at the
configuration's sizes, its weights laid in from the reference's
`make_params` (made on the device in one jitted call from the seed, in
the type they are served in), behind a warmed ServingEngine of the one
engine shape the configuration's file states. The recipe is
chip_smoke.py's `_serve`, restated.
"""
from __future__ import annotations


def build(config: dict, seed: int, weights: dict, engine_overrides=None):
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import ServingConfig, ServingEngine
    from paddle_tpu.utils.abstract_init import abstract_parameters

    paddle.seed(int(seed) & 0x7FFFFFFF)
    gcfg = GPTConfig(vocab_size=config["assumed"]["padded_vocab_size"],
                     hidden_size=config["n_embd"],
                     num_layers=config["n_layer"],
                     num_heads=config["n_head"],
                     max_seq_len=config["n_positions"],
                     layer_norm_eps=config["layer_norm_epsilon"])
    with abstract_parameters():
        model = GPTForCausalLM(gcfg)
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
