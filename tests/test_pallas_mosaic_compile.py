"""Deviceless Mosaic compile of the flash kernels for TPU v5e.

libtpu ships a compile-only client: `get_topology_desc("v5e:2x2")`
yields four `TPU v5 lite` devices that can be compiled for but not run
on, so the real Mosaic compiler judges the kernels here on the CPU
sandbox — what it refuses, the chip refuses (a three-value
`pltpu.prng_seed` fails here word for word as it did on hardware).
What the on-chip PRNG *produces* is only checked on a chip
(`PD_TEST_TPU=1 pytest tests/test_pallas_attention.py`).
"""
import base64
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from paddle_tpu.models.decoder import DecoderSpec
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


# ------------------------------------------------- paged decode attention

# the chat cell's engine shape (perfbench/configs/gpt2-large.json)
SLOTS, TABLE_W, PAGE, HEADS, HEAD = 32, 64, 16, 20, 64
_SPEC = DecoderSpec(eps=1e-5, n_heads=HEADS, head_dim=HEAD)
_GREEDY = (0.0, None, None)


def _pool_lines(text, n_pages, width):
    """The compiled text's instructions whose result is a whole pool,
    `[n_pages, 16, width]`, as (opcode, line)."""
    pat = re.compile(
        rf"= \w+\[{n_pages},{PAGE},{width}\]\{{[^}}]*\}} ([\w-]+)\(")
    return [(m.group(1), ln.strip()) for ln in text.splitlines()
            for m in [pat.search(ln)] if m]


def _pool_copies(text, n_pages, width):
    """Layout conversions of a whole pool: with 4-D pages every
    serving program held two a pool, one at entry and one at exit. A
    conversion is a `copy`, or a `copy-start` whose source and result
    differ in more than the memory space (`S(1)`): the compiler may
    park a pool in VMEM around its scatter, a move in the same layout
    (it does so for one pool of the 2-layer decode program and for
    none of the 36-layer one), which is not a conversion."""
    shape = rf"\w+\[{n_pages},{PAGE},{width}\]\{{([^}}]*)\}}"
    moved = re.compile(rf"= \({shape}, {shape}, .*copy-start\(")
    space = re.compile(r"S\(\d+\)")
    found = [ln for op, ln in _pool_lines(text, n_pages, width)
             if op == "copy"]
    for ln in text.splitlines():
        m = moved.search(ln)
        if m and space.sub("", m.group(1)) != space.sub("", m.group(2)):
            found.append(ln.strip())
    return found


def _pool(n_pages, heads=HEADS, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct((n_pages, PAGE, heads * HEAD), dtype)


@pytest.mark.parametrize("heads,dtype", [
    (HEADS, jnp.bfloat16), (HEADS // 2, jnp.bfloat16),   # tp=2's shard
    (HEADS, jnp.float32)], ids=["bf16_nh20", "bf16_nh10", "f32_nh20"])
def test_paged_decode_kernel_compiles_for_one_v5e_chip(heads, dtype):
    """A page is [16, heads x 64]: one row a token, 1,280 (or tp=2's
    640) lanes of whole tiles; the page windows are whole in their
    last two dimensions. One Mosaic call, and no copy of a pool
    around it."""
    one = SingleDeviceSharding(_v5e_devices()[0])
    q = jax.ShapeDtypeStruct((SLOTS, heads, HEAD), dtype)
    pool = _pool(1024, heads, dtype)
    tables = jax.ShapeDtypeStruct((SLOTS, TABLE_W), jnp.int32)
    lengths = jax.ShapeDtypeStruct((SLOTS,), jnp.int32)

    def attn(q, kp, vp, tables, lengths):
        return pk.paged_decode_attention(q, kp, vp, tables, lengths, 0.125)
    text = _compile(attn, (q, pool, pool, tables, lengths),
                    (one,) * 5).as_text()
    assert text.count("tpu_custom_call") == 1
    assert "paged_decode_attention" in text
    assert not _pool_copies(text, 1024, heads * HEAD)


def _gpt2_large_decode_avals(n_layers, n_pages, pool_at, rep, param_at):
    """make_decode_fn's arguments at GPT-2-large's widths as shapes:
    pools placed by `pool_at`, block tables, tokens, positions and the
    key by `rep`, a parameter named `name` by `param_at(name)`."""
    def s(shape, sharding, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    h, vocab, ctx = HEADS * HEAD, 50304, 1024
    shapes = {"ln1_w": (h,), "ln1_b": (h,), "ln2_w": (h,), "ln2_b": (h,),
              "qkv_w": (h, 3 * h), "qkv_b": (3 * h,), "proj_w": (h, h),
              "proj_b": (h,), "fc1_w": (h, 4 * h), "fc1_b": (4 * h,),
              "fc2_w": (4 * h, h), "fc2_b": (h,)}
    block = {k: s(v, param_at(k)) for k, v in shapes.items()}
    params = {"wte": s((vocab, h), rep), "wpe": s((ctx, h), rep),
              "lnf_w": s((h,), rep), "lnf_b": s((h,), rep),
              "blocks": [block] * n_layers}
    pool = s((n_pages, PAGE, HEADS * HEAD), pool_at)
    key = jax.eval_shape(lambda: jax.random.key(0))
    return (((pool, pool),) * n_layers,
            s((SLOTS, TABLE_W), rep, jnp.int32), s((SLOTS,), rep, jnp.int32),
            s((SLOTS,), rep, jnp.int32), params,
            jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=rep))


def _engine_precision():
    """conftest.py asks for "highest" matmuls everywhere; the engine
    runs jax's default, and XLA fuses the two otherwise (under
    "highest" it nests no matmul fusion in another): the serving
    programs are compiled here as the chip compiles them."""
    return jax.default_matmul_precision("default")


def _compile_decode(n_layers, n_pages, monkeypatch):
    """make_decode_fn (the kernel chosen as on a TPU, 4 tokens a
    dispatch, pools donated) compiled for one v5e chip."""
    from paddle_tpu.serving.programs import (jit_with_donated_pools,
                                             make_decode_fn)
    one = SingleDeviceSharding(_v5e_devices()[0])
    monkeypatch.setattr(pk, "pallas_available", lambda: True)
    fn = jit_with_donated_pools(make_decode_fn(
        _SPEC, PAGE, _GREEDY, n_steps=4))
    with _engine_precision():
        return fn.trace(*_gpt2_large_decode_avals(
            n_layers, n_pages, one, one, lambda name: one)).lower(
                lowering_platforms=("tpu",)).compile()


def _compile_prefill(n_layers, n_pages, monkeypatch, bucket=1024,
                     packed=True):
    """The engine's prefill of GPT-2 (the flash kernel chosen as on a
    TPU, pools donated) for 4 prompts of up to `bucket` tokens, the
    score cell's widest shape by default, compiled for one v5e chip:
    make_packed_prefill_fn's one row of 4 x `bucket` tokens, or
    (`packed=False`) make_prefill_fn's 4 rows of `bucket`, the
    speculative draft's."""
    from paddle_tpu.serving.programs import (jit_with_donated_pools,
                                             make_packed_prefill_fn,
                                             make_prefill_fn)
    one = SingleDeviceSharding(_v5e_devices()[0])
    monkeypatch.setattr(pk, "pallas_available", lambda: True)
    pools, _, _, _, params, key = _gpt2_large_decode_avals(
        n_layers, n_pages, one, one, lambda name: one)
    admit = 4
    s32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=one)
    if packed:
        fn = jit_with_donated_pools(make_packed_prefill_fn(
            _SPEC, PAGE, _GREEDY, 1024))
        plain = (s32(1, admit * bucket), s32(admit), s32(admit))
    else:
        fn = jit_with_donated_pools(make_prefill_fn(_SPEC, PAGE, _GREEDY))
        plain = (s32(admit, bucket), s32(admit))
    with _engine_precision():
        return fn.trace(pools, s32(admit, TABLE_W), *plain, params,
                        key).lower(lowering_platforms=("tpu",)).compile()


@pytest.mark.parametrize("n_pages", [1024, 2048])
def test_decode_program_reads_pages_in_place_on_a_v5e(n_pages, monkeypatch,
                                                      capsys):
    """The whole decode program at the chat cell's shapes (36 layers
    cut to 2 for time), with the kernel chosen as on a TPU: no buffer
    of every slot's whole block table, [32 x 64 pages, 16, 1280], is
    left; one Mosaic call a layer. Its temporaries hold no copy of a
    pool (with 4-D pages they were about 400 MB at 2 layers, 7.3 GB at
    36, and 2,048 pages did not compile): printed, and held under
    50 MB."""
    n_layers = 2
    compiled = _compile_decode(n_layers, n_pages, monkeypatch)
    text = compiled.as_text()
    # neither the gathered tables nor their re-layout (at 2,048 pages a
    # pool has the re-layout's shape itself)
    assert f"[{SLOTS},{TABLE_W},{PAGE},{HEADS * HEAD}]" not in text
    if n_pages != SLOTS * TABLE_W:
        assert f"[{SLOTS * TABLE_W},{PAGE},{HEADS * HEAD}]" not in text
    assert text.count("tpu_custom_call") == n_layers
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\ndecode program, {n_layers} layers, {n_pages} pages: "
              f"temporaries {mem.temp_size_in_bytes / 1e6:.1f} MB, "
              f"arguments {mem.argument_size_in_bytes / 1e6:.1f} MB")
    assert mem.temp_size_in_bytes < 50e6


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_serving_programs_do_not_convert_the_pools(program, monkeypatch):
    """The counter of the one-layout change: a pool
    [1024, 16, 20 * 64] enters each serving program row-major, the
    layout its scatter (and the kernel) address, and no `copy` in the
    compiled text has a pool's shape. With [1024, 16, 20, 64] pools
    the device's layout was pages-minor-most and each program held a
    copy of every pool at entry and another at exit (8 at 2 layers)."""
    n_layers, n_pages = 2, 1024
    build = {"decode": _compile_decode, "prefill": _compile_prefill}
    text = build[program](n_layers, n_pages, monkeypatch).as_text()
    lines = _pool_lines(text, n_pages, HEADS * HEAD)
    params = [ln for op, ln in lines if op == "parameter"
              and "sharding=" in ln]           # the entry's, not a fusion's
    assert len(params) == 2 * n_layers, params
    assert all(f"[{n_pages},{PAGE},{HEADS * HEAD}]{{2,1,0:" in ln
               for ln in params), params
    assert not _pool_copies(text, n_pages, HEADS * HEAD)
    assert f"[{n_pages},{PAGE},{HEADS},{HEAD}]" not in text


def _tp2(n_layers, make, n_plain, plain):
    """A serving program under `MeshPlan(tp=2)`, as the engine builds
    it: the body inside a shard_map over 'tp' with the LOCAL head
    count. `plain(s32)` makes the replicated host arrays between the
    pools and the parameters. Returns the compiled text."""
    from paddle_tpu.distributed.sharding import (SERVING_POOL_SPEC,
                                                 SERVING_TP_RULES)
    from paddle_tpu.serving.programs import jit_tp_with_donated_pools
    mesh = Mesh(np.asarray(_v5e_devices()[:2]), ("tp",))
    tp = 2
    at = lambda spec: NamedSharding(mesh, spec)
    pools, _, _, _, params, key = _gpt2_large_decode_avals(
        n_layers, 1024, at(SERVING_POOL_SPEC), at(P()),
        lambda name: at(SERVING_TP_RULES.get(name, P())))
    specs = jax.tree_util.tree_map(lambda a: a.sharding.spec, params)
    s32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=at(P()))
    fn = jit_tp_with_donated_pools(
        make(dataclasses.replace(
            _SPEC, n_heads=HEADS // tp, qkv_heads_major=True,
            reduce=lambda t: jax.lax.psum(t, "tp"))),
        mesh, specs, n_plain=n_plain, n_out=2)
    with _engine_precision():
        return fn.trace(pools, *plain(s32), params, key).lower(
            lowering_platforms=("tpu",)).compile().as_text()


def test_tp2_decode_program_runs_the_kernel_on_local_heads(monkeypatch):
    """Under `MeshPlan(tp=2)` the same body runs inside a shard_map
    over 'tp': each chip's kernel call sees its 10 of the 20 heads of
    every page, [1024, 16, 640] pools, and nothing gathers or converts
    them."""
    from paddle_tpu.serving.programs import make_decode_fn
    monkeypatch.setattr(pk, "pallas_available", lambda: True)
    n_layers, tp = 2, 2
    text = _tp2(
        n_layers,
        lambda spec: make_decode_fn(spec, PAGE, _GREEDY, n_steps=4), 3,
        lambda s32: (s32(SLOTS, TABLE_W), s32(SLOTS), s32(SLOTS)))
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == n_layers
    local = f"bf16[1024,{PAGE},{HEADS // tp * HEAD}]"
    assert all(ln.count(local) >= 2 for ln in calls), calls[0][:400]
    assert f"[1024,{PAGE},{HEADS * HEAD}]" not in text
    assert "all-gather" not in text
    assert not _pool_copies(text, 1024, HEADS // tp * HEAD)


# ------------------------------------------------ flash prefill attention

@pytest.mark.parametrize("heads", [HEADS, HEADS // 2],
                         ids=["nh20", "tp_shard_nh10"])
@pytest.mark.parametrize("bucket", [128, 1024, 48])
def test_flash_prefill_kernel_compiles_for_one_v5e_chip(bucket, heads):
    """Rows [bucket, heads x 64] read in place in 128-lane blocks (a
    pair of heads): one Mosaic call under its own name, whatever the
    bucket (48 is padded to one 128-row tile)."""
    one = SingleDeviceSharding(_v5e_devices()[0])
    qkv = jax.ShapeDtypeStruct((4, bucket, heads, HEAD), jnp.bfloat16)
    lens = jax.ShapeDtypeStruct((4,), jnp.int32)

    def attn(q, k, v, lens):
        return pk.flash_prefill_attention(q, k, v, lens, 0.125)
    text = _compile(attn, (qkv, qkv, qkv, lens), (one,) * 4).as_text()
    assert text.count("tpu_custom_call") == 1
    assert "flash_prefill_attention" in text


def _mosaic_ops(text):
    """Operations in the Mosaic bodies of a lowered module's kernel
    calls (the serialized module each `tpu_custom_call` carries), a
    count a kernel's set-up grows with."""
    from jax._src.interpreters import mlir
    n = 0
    for body in re.findall(r'body\\22: \\22([A-Za-z0-9+/=]+)\\22', text):
        ctx = mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            module = mlir.ir.Module.parse(base64.b64decode(body))

            def walk(op):
                count = 0
                for region in op.regions:
                    for block in region.blocks:
                        for inner in block.operations:
                            count += 1 + walk(inner.operation)
                return count
            n += walk(module.operation)
    return n


# GPT-2-large's heads, and Mellum2's 32 over 4 K/V heads of 128 on a
# full and on a window layer
@pytest.mark.parametrize("heads,kv,hd,window", [
    (HEADS, HEADS, HEAD, None), (32, 4, 128, None), (32, 4, 128, 1024)],
    ids=["gpt2_nh20", "mellum_full", "mellum_window"])
def test_packed_prefill_kernel_is_no_larger_than_the_row_kernel(
        heads, kv, hd, window):
    """The set-up guard: a packed row's kernel body (4 x 1,024 tokens
    in one row, 4 prompt starts) holds at most 1.1 x the Mosaic
    operations of the row-a-prompt kernel's at 4 x 1,024 and the same
    heads: the segment's edge is one more comparison in the masked
    body, the blocks a query block skips or masks come in as scalars,
    and the chunks of whole key blocks go through a loop."""
    def lowered(a, s, packed):
        qd = jax.ShapeDtypeStruct((a, s, heads, hd), jnp.bfloat16)
        kvd = jax.ShapeDtypeStruct((a, s, kv, hd), jnp.bfloat16)
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
        if packed:
            fn = lambda q, k, v, n, st: pk.flash_prefill_attention(
                q, k, v, n, 0.125, window=window, starts=st, longest=1024)
            avals = (qd, kvd, kvd, i32(1), i32(4))
        else:
            fn = lambda q, k, v, n: pk.flash_prefill_attention(
                q, k, v, n, 0.125, window=window)
            avals = (qd, kvd, kvd, i32(4))
        return jax.jit(fn).trace(*avals).lower(
            lowering_platforms=("tpu",)).as_text()
    rows = _mosaic_ops(lowered(4, 1024, False))
    packed = _mosaic_ops(lowered(1, 4096, True))
    assert rows > 500
    assert packed <= 1.1 * rows, (packed, rows)


def test_packed_prefill_kernels_carry_no_more_code_than_the_row_kernels():
    """A kernel's compiled code is loaded, and paid for, in every
    process and for every layer of every program, compile cache or not
    (PERF.md, "Findings"). GPT-2's four packed widths (512,
    2,048, 3,072, 4,096 rows, 512-row blocks) hold no more code in all
    than its four row-a-prompt buckets (4 x 128 to 4 x 1,024, blocks of
    128 to 512 rows): with the chunks unrolled over whole key blocks
    they held 56% more, which set-up paid at every layer."""
    one = SingleDeviceSharding(_v5e_devices()[0])

    def code(a, s, packed):
        qkv = jax.ShapeDtypeStruct((a, s, HEADS, HEAD), jnp.bfloat16)
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
        if packed:
            fn = lambda q, k, v, n, st: pk.flash_prefill_attention(
                q, k, v, n, 0.125, starts=st, longest=1024)
            avals = (qkv, qkv, qkv, i32(1), i32(4))
        else:
            fn = lambda q, k, v, n: pk.flash_prefill_attention(
                q, k, v, n, 0.125)
            avals = (qkv, qkv, qkv, i32(4))
        compiled = _compile(fn, avals, (one,) * len(avals))
        return compiled.memory_analysis().generated_code_size_in_bytes
    rows = sum(code(4, b, False) for b in (128, 256, 512, 1024))
    packed = sum(code(1, w, True) for w in (512, 2048, 3072, 4096))
    assert packed <= rows, (packed, rows)


@pytest.mark.parametrize("bucket", [128, 1024])
def test_prefill_program_keeps_its_scores_on_the_chip(bucket, monkeypatch,
                                                      capsys):
    """The whole packed prefill program (36 layers cut to 2 for time)
    with the kernel chosen as on a TPU, one row of 4 x `bucket` tokens:
    one Mosaic call a layer and no [1, 20, T, T] tensor of scores, mask
    or probabilities, nor the heads-first [1, 20, T, 64] copies the
    dense attention read. The temporaries (175 MB at 2 layers of
    4 x 1,024 with the dense attention) are printed, and held under
    20 MB."""
    n_layers, t = 2, 4 * bucket
    compiled = _compile_prefill(n_layers, 1024, monkeypatch, bucket)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == n_layers
    assert "flash_prefill_attention" in text
    assert f"[1,{HEADS},{t},{t}]" not in text
    assert f"[1,{HEADS},{t},{HEAD}]" not in text
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\nprefill program, {n_layers} layers, a row of {t}: "
              f"temporaries {mem.temp_size_in_bytes / 1e6:.1f} MB")
    assert mem.temp_size_in_bytes < 20e6


def test_tp2_prefill_program_runs_the_kernel_on_local_heads(monkeypatch):
    """Under tp=2 the packed prefill body's kernel call takes this
    chip's 10 heads of every row, [1, 512, 640], and no tensor of
    scores is left; the pools are neither gathered nor converted."""
    from paddle_tpu.serving.programs import make_packed_prefill_fn
    monkeypatch.setattr(pk, "pallas_available", lambda: True)
    n_layers, tp, t = 2, 2, 512
    text = _tp2(
        n_layers,
        lambda spec: make_packed_prefill_fn(spec, PAGE, _GREEDY, 128), 4,
        lambda s32: (s32(4, TABLE_W), s32(1, t), s32(4), s32(4)))
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == n_layers
    local = f"bf16[1,{t},{HEADS // tp * HEAD}]"
    assert all(ln.count(local) >= 4 for ln in calls), calls[0][:400]
    assert f"[1,{HEADS // tp},{t},{t}]" not in text
    assert "all-gather" not in text
    assert not _pool_copies(text, 1024, HEADS // tp * HEAD)


# ------------------------------------- where the MLP's activation rides

FFN = 4 * HEADS * HEAD
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{$")
_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT )?%([\w.\-]+) = (\(?[^=]*?\)?) ([\w\-]+)\((.*)$")
# instructions that compute nothing an element (a `fusion` is counted
# through its body)
_TRIVIAL = ("parameter", "constant", "broadcast", "bitcast", "iota",
            "tuple", "fusion")


def _computations(text):
    """The compiled text as {computation: [(name, type, opcode, rest
    of the line)]}."""
    comps, cur = {}, None
    for ln in text.splitlines():
        m = _COMPUTATION.match(ln)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif ln.startswith("}"):
            cur = None
        elif cur is not None:
            m = _INSTRUCTION.match(ln)
            if m:
                cur.append(m.groups())
    return comps


def _width(type_str):
    """The last dimension of an instruction's (first) result."""
    m = re.search(r"\w+\[([\d,]*)\]", type_str)
    return int(m.group(1).split(",")[-1]) if m and m.group(1) else None


def _with_nested(comps, name):
    """A computation's instructions and those of the fusions it calls
    (XLA nests a producer fusion inside its consumer's)."""
    for ins in comps[name]:
        yield ins
        for callee in re.findall(r"calls=%([\w.\-]+)", ins[3]):
            yield from _with_nested(comps, callee)


def _mlp_fusions(comps):
    """({fc2's fused computations}, {fc1's}): those that hold the
    convolution [.., FFN] -> [.., 1280], and the other way round.
    Operands are printed by name, so their widths are looked up."""
    fc2, fc1 = set(), set()
    for name, instrs in comps.items():
        widths = {n: _width(t) for n, t, _, _ in instrs}
        for _, t, op, rest in instrs:
            if op != "convolution":
                continue
            operand = widths.get(re.findall(r"%([\w.\-]+)", rest)[0])
            if (operand, _width(t)) == (FFN, HEADS * HEAD):
                fc2.add(name)
            elif (operand, _width(t)) == (HEADS * HEAD, FFN):
                fc1.add(name)
    return fc2, fc1


# (most non-trivial instructions in fc2's fusion, `copy` instructions
# of the parent of the change that moved the activation; of the packed
# prefill's row of 4,096, two small int32 ones of its token addressing)
_ACTIVATION_CASES = {"prefill_1024": (16, 2), "prefill_128": (40, 0),
                     "decode_32x4": (40, 8)}


@pytest.mark.parametrize("program", list(_ACTIVATION_CASES))
def test_gelu_is_not_expanded_in_fc2s_operand(program, monkeypatch, capsys):
    """The counter of where the activation is evaluated. With
    `jax.nn.gelu(approximate=False)` on the bf16 result XLA expanded
    `erfc` (84 instructions an element, both branches, an exponential
    and two divides) into the OPERAND of fc2's matmul fusion, and a
    side fusion packed one of its predicates into `u8[1024, 5120]`;
    `decoder._gelu` keeps `erf` one instruction. At 4 x 1,024 the
    activation rides in fc1's epilogue and fc2's fusion is the matmul,
    the residual and the next LayerNorm's row sum (9 instructions). At
    4 x 128 and at the decode program's 32 rows XLA nests fc1's whole
    fusion, LayerNorm and `erf` with it, inside fc2's (two
    convolutions, 28-35 instructions where the expansion made 82-99;
    the LayerNorm's divide is over [.., 1280]). Fails when a later
    change lets the expansion back into a matmul's operand."""
    most, parent_copies = _ACTIVATION_CASES[program]
    n_layers = 2
    if program == "decode_32x4":
        text = _compile_decode(n_layers, 1024, monkeypatch).as_text()
    else:
        text = _compile_prefill(n_layers, 1024, monkeypatch,
                                int(program.split("_")[1])).as_text()
    comps = _computations(text)
    fc2, fc1 = _mlp_fusions(comps)
    assert len(fc2) == n_layers, sorted(fc2)
    for name in fc2:
        body = list(_with_nested(comps, name))
        ops = [op for _, _, op, _ in body]
        assert "exponential" not in ops, name
        assert not [n for n, t, op, _ in body
                    if op == "divide" and _width(t) == FFN], name
        assert ops.count("erf") <= 1
        busy = [op for op in ops if op not in _TRIVIAL]
        assert len(busy) <= most, (name, len(busy), sorted(busy))
    assert not re.search(rf"= \(?u8\[\d+,{FFN}\]", text)
    copies = [n for instrs in comps.values() for n, _, op, _ in instrs
              if op == "copy"]
    assert len(copies) <= parent_copies, copies
    with capsys.disabled():
        print(f"\n{program}, {n_layers} layers: estimated cycles of")
        for instrs in comps.values():
            for name, _, op, rest in instrs:
                callee = re.search(r"calls=%([\w.\-]+)", rest)
                cycles = re.search(r'"estimated_cycles":"(\d+)"', rest)
                if op == "fusion" and cycles and callee.group(1) in fc2 | fc1:
                    which = "fc2" if callee.group(1) in fc2 else "fc1"
                    print(f"  {which} {name}: {cycles.group(1)}")


# (copies, Mosaic calls, fusions, temporaries B, generated code B) of
# GPT-2's serving programs at 2 layers, as the retention decoder's
# changes found them (the prefill as the packed row found it: a row of
# 4 x 1,024 or 4 x 128 tokens; `rows_1024` the speculative draft's
# row-a-prompt prefill): what those changes touch is shared with them
_GPT2_PROGRAMS = {
    "prefill_1024": (2, 2, 76, 3_354_624, 8_993_280),
    "prefill_128": (0, 2, 71, 3_032_064, 6_159_872),
    "prefill_rows_1024": (1, 2, 61, 2_774_016, 9_287_168),
    "decode_32x4": (8, 2, 85, 5_677_056, 2_783_744),
    "chunk_32x5": (19, 0, 86, 205_663_232, 8_350_208)}


@pytest.mark.parametrize("program", list(_GPT2_PROGRAMS))
def test_gpt2_programs_compile_as_before(program, monkeypatch):
    """The program makers and `decoder.block` are shared with the
    retention decoder; a change to them that looks local can re-lay a
    whole program (a scatter's index shape once added a copy of a
    weight every token-step). GPT-2's packed prefill (rows of 4 x 1,024
    and 4 x 128), row-a-prompt prefill (4 x 1,024), decode (32 x 4) and
    chunk (32 x 5) programs compile for v5e to the same copies, Mosaic
    calls, fusions, temporaries and code size."""
    n_layers = 2
    if program == "decode_32x4":
        compiled = _compile_decode(n_layers, 1024, monkeypatch)
    elif program == "chunk_32x5":
        from paddle_tpu.serving.programs import (jit_with_donated_pools,
                                                 make_chunk_fn)
        one = SingleDeviceSharding(_v5e_devices()[0])
        pools, _, _, _, params, key = _gpt2_large_decode_avals(
            n_layers, 1024, one, one, lambda name: one)
        s32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                                  sharding=one)
        fn = jit_with_donated_pools(make_chunk_fn(_SPEC, PAGE, _GREEDY))
        with _engine_precision():
            compiled = fn.trace(pools, s32(SLOTS, TABLE_W), s32(SLOTS, 5),
                                s32(SLOTS), s32(SLOTS), params, key).lower(
                                    lowering_platforms=("tpu",)).compile()
    else:
        compiled = _compile_prefill(n_layers, 1024, monkeypatch,
                                    int(program.split("_")[-1]),
                                    packed="rows" not in program)
    text = compiled.as_text()
    ops = [op for instrs in _computations(text).values()
           for _, _, op, _ in instrs]
    mem = compiled.memory_analysis()
    assert (ops.count("copy"), text.count("tpu_custom_call"),
            ops.count("fusion"), mem.temp_size_in_bytes,
            mem.generated_code_size_in_bytes) == _GPT2_PROGRAMS[program]


# ---------------------------------------------------- retention decode step

# the complete cell's engine shape (perfbench/configs/brumby-14b-base.json):
# 33 state rows, 32 lanes, 40 query and 8 key-value heads of 128
R_ROWS, R_HEADS, R_KV, R_HEAD = 33, 40, 8, 128
R_FEATS = R_HEAD // 2 + 1


def _state_avals(one, rows=R_ROWS, kv=R_KV, hd=R_HEAD):
    feats = hd // 2 + 1
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                              sharding=one)
    return (f32(rows, kv, feats, hd, hd),
            f32(rows, kv, -(-feats // 8) * 8, hd))


def _state_copies(text, rows=R_ROWS):
    """`copy` or layout-changing `copy-start` of a whole state pool,
    S [rows, 8, 65, 128, 128] or z [rows, 8, 72, 128]."""
    shape = rf"f32\[{rows},{R_KV},(?:{R_FEATS},{R_HEAD},{R_HEAD}|\d+,{R_HEAD})\]"
    found = [ln.strip() for ln in text.splitlines()
             if re.search(rf"= {shape}\{{[^}}]*\}} copy\(", ln)]
    moved = re.compile(rf"= \({shape}\{{([^}}]*)\}}, {shape}\{{([^}}]*)\}}, "
                       r".*copy-start\(")
    space = re.compile(r"S\(\d+\)")
    for ln in text.splitlines():
        m = moved.search(ln)
        if m and space.sub("", m.group(1)) != space.sub("", m.group(2)):
            found.append(ln.strip())
    return found


@pytest.mark.parametrize("heads,kv,hd", [(R_HEADS, R_KV, R_HEAD),
                                         (8, 8, R_HEAD), (16, 4, 64)],
                         ids=["grp5_hd128", "grp1_hd128", "grp4_hd64"])
def test_retention_decode_kernel_compiles_for_one_v5e_chip(heads, kv, hd):
    """A lane's state of one key-value head is [65, 128, 128] f32 in
    whole tiles, z [72, 128]; a chunk of 4 keys; the write a flag the
    program computes. One Mosaic call under its own name, the state
    operands aliased to the outputs (donated: written in place, S by
    the kernel's own copy), and no temporary of the state's size."""
    one = SingleDeviceSharding(_v5e_devices()[0])
    state = _state_avals(one, R_ROWS, kv, hd)
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)

    def step(S, z, rows, q, keys, vals, decay, weights, write):
        return pk.retention_decode((S, z), rows, q, keys, vals, decay,
                                   weights, write)
    chunk = (SLOTS, kv, 4)
    compiled = jax.jit(step, donate_argnums=(0, 1)).trace(
        *state, s((SLOTS,), jnp.int32),
        s((SLOTS, heads, hd), jnp.bfloat16), s(chunk + (hd,), jnp.float32),
        s(chunk + (hd,), jnp.float32), s((SLOTS, kv), jnp.float32),
        s(chunk, jnp.float32), s((), jnp.bool_)
    ).lower(lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "retention_decode" in text
    mem = compiled.memory_analysis()
    state_bytes = sum(np.prod(a.shape) * 4 for a in state)
    assert mem.alias_size_in_bytes >= state_bytes
    assert mem.temp_size_in_bytes < 4e6


def _retention_avals(n_layers, one):
    """The decode and prefill programs' arguments at the published
    widths as shapes (pools, params, key)."""
    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    h, ffn, vocab = 5120, 17408, 151936
    block = {"ln1_w": s((h,)), "q_w": s((h, R_HEADS * R_HEAD)),
             "k_w": s((h, R_KV * R_HEAD)), "v_w": s((h, R_KV * R_HEAD)),
             "proj_w": s((R_HEADS * R_HEAD, h)), "g_w": s((h, R_KV)),
             "g_b": s((R_KV,)), "qn_w": s((R_HEAD,)), "kn_w": s((R_HEAD,)),
             "ln2_w": s((h,)), "gate_w": s((h, ffn)), "up_w": s((h, ffn)),
             "down_w": s((ffn, h))}
    params = {"wte": s((vocab, h)), "lnf_w": s((h,)),
              "head_w": s((h, vocab)), "blocks": [block] * n_layers}
    key = jax.eval_shape(lambda: jax.random.key(0))
    return ((_state_avals(one),) * n_layers, params,
            jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one))


def test_retention_decode_program_writes_a_row_once_a_chunk(monkeypatch):
    """The decode program of 4 token-steps at the published widths (2
    layers): the Mosaic calls that can write the state are one a layer
    inside the scan's loop, each told whether it writes by an s32[1]
    flag that the loop's counter gives; the flag is set on the last
    token-step only (`writes_per_dispatch` 1, which the CPU tests hold
    the program to token by token)."""
    from paddle_tpu.serving.programs import (jit_with_donated_pools,
                                             make_decode_fn)
    n_layers = 2
    one = SingleDeviceSharding(_v5e_devices()[0])
    monkeypatch.setattr(pk, "pallas_available", lambda: True)
    pools, params, key = _retention_avals(n_layers, one)
    s32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=one)
    run = make_decode_fn(_retention_spec(), PAGE, _GREEDY, n_steps=4)
    assert run.writes_per_dispatch == 1
    with _engine_precision():
        text = jit_with_donated_pools(run).trace(
            pools, s32(SLOTS), s32(SLOTS), s32(SLOTS), params, key).lower(
                lowering_platforms=("tpu",)).compile().as_text()
    comps = _computations(text)
    bodies = [name for name, instrs in comps.items()
              if any("tpu_custom_call" in rest for *_, rest in instrs)]
    calls = [(n, t, rest) for name in bodies for n, t, op, rest
             in comps[name] if "tpu_custom_call" in rest]
    assert len(calls) == n_layers
    # the write flag rides as a scalar-prefetched s32[1] operand
    for n, t, rest in calls:
        args = re.findall(r"%([\w.\-]+)", rest.split("custom_call_target")[0])
        types = {m: tt for name in bodies
                 for m, tt, _, _ in comps[name]}
        assert any(types.get(a, "").startswith("s32[1]") for a in args), \
            rest[:300]


def _retention_spec():
    from paddle_tpu.models import RetentionConfig
    return RetentionConfig().decoder_spec()


@pytest.mark.parametrize("program", ["decode", "prefill_128"])
def test_retention_programs_keep_their_state_in_place(program, monkeypatch,
                                                      capsys):
    """The decode program (32 lanes x 4 token-steps) and a prefill
    (4 x 128) of the retention decoder at the published widths (4
    layers cut to 2 for time), the kernel chosen as on a TPU: one
    Mosaic call a layer in the decode program and none in the prefill
    (its attention is the quadratic form in XLA); the donated state
    rows are neither copied nor converted (z's rows are padded to 72
    for that: with 65 the device lays [33, 8, 65, 128] out with the 8
    heads second-minor and every program converted it at entry and
    exit); temporaries printed and held under 400 MB."""
    from paddle_tpu.serving.programs import (jit_with_donated_pools,
                                             make_decode_fn,
                                             make_prefill_fn)
    n_layers = 2
    one = SingleDeviceSharding(_v5e_devices()[0])
    monkeypatch.setattr(pk, "pallas_available", lambda: True)
    pools, params, key = _retention_avals(n_layers, one)
    s32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=one)
    spec = _retention_spec()
    with _engine_precision():
        if program == "decode":
            fn = jit_with_donated_pools(make_decode_fn(
                spec, PAGE, _GREEDY, n_steps=4))
            compiled = fn.trace(pools, s32(SLOTS), s32(SLOTS), s32(SLOTS),
                                params, key).lower(
                                    lowering_platforms=("tpu",)).compile()
        else:
            fn = jit_with_donated_pools(make_prefill_fn(spec, PAGE,
                                                        _GREEDY))
            compiled = fn.trace(pools, s32(4), s32(4, 128), s32(4), params,
                                key).lower(
                                    lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == (n_layers if program == "decode" else 0)
    assert not _state_copies(text)
    mem = compiled.memory_analysis()
    state_bytes = n_layers * sum(np.prod(a.shape) * 4 for a in pools[0])
    assert mem.alias_size_in_bytes >= state_bytes
    with capsys.disabled():
        print(f"\nretention {program}, {n_layers} layers: temporaries "
              f"{mem.temp_size_in_bytes / 1e6:.1f} MB, arguments "
              f"{mem.argument_size_in_bytes / 1e9:.2f} GB")
    assert mem.temp_size_in_bytes < 400e6


# ------------------------------------------- the expert model's programs

# the code cell's engine shape (perfbench/configs/mellum2-12b-a2.5b.json):
# 11,085 pages of [16, 4 x 128], 32 lanes, tables of 326 pages, 2 prompts
# a prefill; 32 query heads over 4 K/V heads, 64 experts of 896
M_PAGES, M_KV, M_HEADS, M_WIDTH, M_TABLE = 11085, 4, 32, 128, 326
M_HIDDEN, M_EXPERTS, M_FFN, M_VOCAB = 2304, 64, 896, 98304


def _moe_spec(n_layers):
    """Mellum2's block at published widths, layers [sliding, full]."""
    from paddle_tpu.models import MoEConfig
    return MoEConfig(
        num_layers=n_layers,
        layer_types=["sliding_attention", "full_attention"][:n_layers],
        rope_scaling={"factor": 16, "original_max_position_embeddings": 8192,
                      "beta_fast": 32, "beta_slow": 1,
                      "attention_factor": 1.2772588722239782}
    ).decoder_spec()


def _moe_avals(n_layers, one):
    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    h, d = M_HIDDEN, M_HEADS * M_WIDTH
    block = {"ln1_w": s((h,)), "q_w": s((h, d)),
             "k_w": s((h, M_KV * M_WIDTH)), "v_w": s((h, M_KV * M_WIDTH)),
             "proj_w": s((d, h)), "qn_w": s((M_WIDTH,)),
             "kn_w": s((M_WIDTH,)), "ln2_w": s((h,)),
             "router_w": s((h, M_EXPERTS)),
             "experts_gate_up_w": s((M_EXPERTS, h, 2 * M_FFN)),
             "experts_down_w": s((M_EXPERTS, M_FFN, h))}
    params = {"wte": s((M_VOCAB, h)), "lnf_w": s((h,)),
              "head_w": s((h, M_VOCAB)), "blocks": [block] * n_layers}
    pool = s((M_PAGES, PAGE, M_KV * M_WIDTH))
    key = jax.eval_shape(lambda: jax.random.key(0))
    return (((pool, pool),) * n_layers, params,
            jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one))


@pytest.mark.parametrize("program", ["decode_32x4", "prefill_2x2048"])
def test_moe_programs_compile_for_one_v5e_chip(program, monkeypatch,
                                               capsys):
    """The expert model's decode (32 lanes x 4 token-steps) and packed
    prefill (a row of 2 x 2,048) programs at the published widths, a sliding and a full
    layer, the kernels chosen as on a TPU: three Mosaic calls a layer
    (the attention kernel and the two grouped matmuls), the pools of 4
    K/V heads (512 lanes) neither copied nor converted, the expert load
    returned beside the tokens, temporaries printed and held under 2 GB
    (a prefill's 32,768 expert rows: their gate|up in f32 is 235 MB)."""
    from paddle_tpu.serving.programs import (jit_with_donated_pools,
                                             make_decode_fn,
                                             make_packed_prefill_fn)
    n_layers = 2
    one = SingleDeviceSharding(_v5e_devices()[0])
    monkeypatch.setattr(pk, "pallas_available", lambda: True)
    pools, params, key = _moe_avals(n_layers, one)
    s32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=one)
    spec = _moe_spec(n_layers)
    with _engine_precision():
        if program == "decode_32x4":
            fn = jit_with_donated_pools(make_decode_fn(spec, PAGE, _GREEDY,
                                                       n_steps=4))
            args = (pools, s32(SLOTS, M_TABLE), s32(SLOTS), s32(SLOTS))
        else:
            # the engine's packed row of two prompts of up to 2,048
            fn = jit_with_donated_pools(make_packed_prefill_fn(
                spec, PAGE, _GREEDY, 5120))
            args = (pools, s32(2, M_TABLE), s32(1, 4096), s32(2), s32(2))
        traced = fn.trace(*args, params, key)
        compiled = traced.lower(lowering_platforms=("tpu",)).compile()
    assert traced.out_info[-1].shape == (2, n_layers, M_EXPERTS)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3 * n_layers
    assert not _pool_copies(text, M_PAGES, M_KV * M_WIDTH)
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\nexpert model {program}, {n_layers} layers: temporaries "
              f"{mem.temp_size_in_bytes / 1e6:.1f} MB")
    assert mem.temp_size_in_bytes < 2e9
