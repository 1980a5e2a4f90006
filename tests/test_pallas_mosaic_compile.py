"""Deviceless Mosaic compile of the flash kernels for TPU v5e.

libtpu ships a compile-only client: `get_topology_desc("v5e:2x2")`
yields four `TPU v5 lite` devices that can be compiled for but not run
on, so the real Mosaic compiler judges the kernels here on the CPU
sandbox — what it refuses, the chip refuses (a three-value
`pltpu.prng_seed` fails here word for word as it did on hardware).
What the on-chip PRNG *produces* is only checked on a chip
(`PD_TEST_TPU=1 pytest tests/test_pallas_attention.py`).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from paddle_tpu.ops import pallas_kernels as pk


@functools.lru_cache(maxsize=1)
def _v5e_devices():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    except Exception as e:  # no libtpu / no compile-only client here
        pytest.skip(f"v5e:2x2 topology cannot be built: "
                    f"{type(e).__name__}: {e}")
    return tuple(topo.devices)


def _compile(fn, avals, shardings):
    jitted = jax.jit(fn, in_shardings=shardings)
    return jitted.trace(*avals).lower(
        lowering_platforms=("tpu",)).compile()


def _loss(attn):
    def f(q, k, v, seed):
        return attn(q, k, v, seed).astype(jnp.float32).sum()
    return jax.grad(f, argnums=(0, 1, 2))


QKV = jax.ShapeDtypeStruct((4, 512, 12, 64), jnp.bfloat16)
SEED = jax.ShapeDtypeStruct((1,), jnp.int32)


@pytest.mark.parametrize("dropout_p", [0.0, 0.1])
@pytest.mark.parametrize("causal", [False, True])
def test_kernels_compile_for_one_v5e_chip(dropout_p, causal):
    """forward + both backward kernels, bf16, with and without the
    in-kernel dropout, on one device of the topology."""
    dev = _v5e_devices()[0]
    assert dev.device_kind == "TPU v5 lite"
    one = SingleDeviceSharding(dev)

    def attn(q, k, v, seed):
        return pk.flash_attention_mha(q, k, v, causal=causal,
                                      dropout_p=dropout_p, seed=seed)
    compiled = _compile(_loss(attn), (QKV, QKV, QKV, SEED),
                        (one, one, one, one))
    # fwd (recomputed for the vjp residuals), dq, dk/dv
    assert compiled.as_text().count("tpu_custom_call") >= 3


@pytest.mark.parametrize("dropout_p", [0.0, 0.1])
def test_mesh_wrapper_compiles_on_dp2_tp2(dropout_p):
    """The same kernels under flash_attention_mha_sharded on the 2x2
    mesh: a bare pallas_call does not lower there ("Mosaic kernels
    cannot be automatically partitioned"); the wrapper must, with
    batch rows over dp and heads over tp and no collective on q/k/v."""
    mesh = Mesh(np.asarray(_v5e_devices()).reshape(2, 2), ("dp", "tp"))
    sh = NamedSharding(mesh, P("dp", None, "tp", None))
    rep = NamedSharding(mesh, P())

    def attn(q, k, v, seed):
        return pk.flash_attention_mha_sharded(
            q, k, v, mesh, ("dp",), "tp", dropout_p=dropout_p, seed=seed)
    compiled = _compile(_loss(attn), (QKV, QKV, QKV, SEED),
                        (sh, sh, sh, rep))
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3
    for coll in ("all-gather", "all-to-all", "collective-permute"):
        assert coll not in text, f"{coll} around the sharded kernel"


def test_bare_kernel_does_not_lower_on_a_mesh():
    """Pins WHY the wrapper exists: if a jax upgrade teaches GSPMD to
    partition Mosaic calls, this fails and the wrapper can go."""
    mesh = Mesh(np.asarray(_v5e_devices()).reshape(2, 2), ("dp", "tp"))
    sh = NamedSharding(mesh, P("dp", None, "tp", None))

    def attn(q, k, v):
        return pk.flash_attention_mha(q, k, v)
    with pytest.raises(NotImplementedError, match="shard_map"):
        _compile(attn, (QKV, QKV, QKV), (sh, sh, sh))


def test_sharded_train_step_lowers_for_v5e_with_the_kernel_on(monkeypatch):
    """The north-star path: ONE TrainStep over dp2 x tp2, attention on
    the Mosaic kernel with in-kernel dropout. No CPU run reaches it
    (the kernel is chosen from the platform), so it is lowered and
    compiled for the topology here: every Mosaic call must take this
    chip's rows (batch/dp x heads/tp) only."""
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.models import ErnieConfig, ErnieForPretraining
    from paddle_tpu.static import TrainStep
    from paddle_tpu.utils.abstract_init import abstract_parameters

    mesh = Mesh(np.asarray(_v5e_devices()).reshape(2, 2), ("dp", "tp"))
    monkeypatch.setattr(pk, "pallas_available", lambda: True)
    paddle.seed(0)
    cfg = ErnieConfig(vocab_size=1024, hidden_size=256,
                      num_hidden_layers=1, num_attention_heads=4,
                      intermediate_size=512, max_position_embeddings=128)
    with abstract_parameters():
        model = ErnieForPretraining(cfg)
    step = TrainStep(model, ErnieForPretraining.pretraining_loss,
                     paddle.optimizer.AdamW(learning_rate=1e-4),
                     amp_level="O1", mesh=mesh,
                     sharding_plan=dist.ShardingPlan(mesh))
    ids = jax.ShapeDtypeStruct((8, 128), jnp.int32)
    text = step.aot_lower((ids,), (ids,),
                          lowering_platforms=("tpu",)).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) >= 3
    rows = (8 // 2) * (4 // 2)
    assert all(f"[{rows},128," in ln for ln in calls), calls[0][:300]
