"""Pallas flash-attention kernel vs the composed SDPA reference.

Runs the kernels through the Pallas interpreter (portable) and, when a TPU
backend is present, compiled via Mosaic. Mirrors the reference's OpTest
contract (numpy/composed reference vs kernel, fwd + grads): see
/root/reference/python/paddle/fluid/tests/unittests/op_test.py:251.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops.pallas_kernels import flash_attention_mha, pallas_available
import paddle_tpu.ops.pallas_kernels as pk
from paddle_tpu.nn.functional.attention import _sdpa_impl

# bf16-MXU noise floor (TPU dots run bf16 by default in the reference too)
TOL = 2e-2

CASES = [
    (2, 128, 2, 64, False),
    (2, 200, 2, 64, True),     # seq not a multiple of the block
    (1, 256, 4, 128, True),
    (2, 96, 2, 32, False),     # small head_dim
]


def _data(b, s, n, h, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, s, n, h), jnp.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("b,s,n,h,causal", CASES)
def test_forward_matches_sdpa(b, s, n, h, causal):
    q, k, v = _data(b, s, n, h)
    interpret = not pallas_available()
    ref = _sdpa_impl(q, k, v, None, 0.0, causal, None)
    out = flash_attention_mha(q, k, v, causal=causal, interpret=interpret)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=TOL, rtol=TOL)


@pytest.mark.parametrize("b,s,n,h,causal", CASES[:2])
def test_grads_match_sdpa(b, s, n, h, causal):
    q, k, v = _data(b, s, n, h)
    interpret = not pallas_available()

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(_sdpa_impl(q, k, v, None, 0.0, causal, None)))

    def loss_pal(q, k, v):
        return jnp.sum(jnp.sin(flash_attention_mha(
            q, k, v, causal=causal, interpret=interpret)))

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    gp = jax.grad(loss_pal, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gr, gp):
        np.testing.assert_allclose(np.asarray(b_), np.asarray(a),
                                   atol=TOL, rtol=TOL)


def test_cross_attention_shapes():
    # kv seq != q seq
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(2, 64, 2, 64), jnp.float32)
    k = jnp.asarray(rng.randn(2, 192, 2, 64), jnp.float32)
    v = jnp.asarray(rng.randn(2, 192, 2, 64), jnp.float32)
    interpret = not pallas_available()
    ref = _sdpa_impl(q, k, v, None, 0.0, False, None)
    out = flash_attention_mha(q, k, v, interpret=interpret)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=TOL, rtol=TOL)


def test_functional_dispatch():
    """F.flash_attention runs end-to-end on framework Tensors."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    paddle.seed(0)
    q = paddle.randn([2, 64, 2, 32])
    k = paddle.randn([2, 64, 2, 32])
    v = paddle.randn([2, 64, 2, 32])
    out = F.flash_attention(q, k, v, causal=True)
    ref = F.scaled_dot_product_attention(q, k, v, is_causal=True)
    np.testing.assert_allclose(out.numpy(), ref.numpy(), atol=TOL, rtol=TOL)


class TestKernelDropout:
    """In-kernel attention dropout. The Pallas interpreter stubs
    prng_random_bits to zeros, so only the dropout_p=0 equivalence runs
    under interpret mode; the RNG-dependent checks (determinism, mean
    preservation, the fixed-seed numeric grad check that pins backward
    mask regeneration) run on real TPU hardware
    (PD_TEST_TPU=1 through the chip tool)."""

    def _qkv(self, b=1, s=16, n=2, h=8, seed=0):
        rng = np.random.RandomState(seed)
        mk = lambda: rng.randn(b, s, n, h).astype(np.float32) * 0.5
        return jnp.asarray(mk()), jnp.asarray(mk()), jnp.asarray(mk())

    def test_zero_dropout_identical(self):
        q, k, v = self._qkv()
        base = pk.flash_attention_mha(q, k, v, interpret=True)
        drop0 = pk.flash_attention_mha(q, k, v, interpret=True,
                                       dropout_p=0.0, seed=123)
        np.testing.assert_allclose(np.asarray(base), np.asarray(drop0),
                                   rtol=1e-6, atol=1e-6)

    @pytest.mark.skipif(not pallas_available(), reason="needs TPU")
    def test_tpu_deterministic_per_seed(self):
        q, k, v = self._qkv()
        a = pk.flash_attention_mha(q, k, v, dropout_p=0.4, seed=7)
        b2 = pk.flash_attention_mha(q, k, v, dropout_p=0.4, seed=7)
        c = pk.flash_attention_mha(q, k, v, dropout_p=0.4, seed=8)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b2))
        assert np.abs(np.asarray(a) - np.asarray(c)).max() > 1e-6

    @pytest.mark.skipif(not pallas_available(), reason="needs TPU")
    def test_tpu_mean_preserved(self):
        q, k, v = self._qkv(s=128, n=1, h=64)
        base = np.asarray(pk.flash_attention_mha(q, k, v))
        acc = np.zeros_like(base)
        m = 64
        for sd in range(m):
            acc += np.asarray(pk.flash_attention_mha(
                q, k, v, dropout_p=0.3, seed=sd))
        np.testing.assert_allclose(acc / m, base, atol=0.15)

    @pytest.mark.skipif(not pallas_available(), reason="needs TPU")
    def test_tpu_grads_match_numeric_at_fixed_seed(self):
        # backward regenerates the forward's block masks; any mismatch
        # between the two mask streams fails this check
        q, k, v = self._qkv(s=128, n=1, h=64)
        p, sd = 0.35, 11

        def f(q_, k_, v_):
            return pk.flash_attention_mha(q_, k_, v_, dropout_p=p,
                                          seed=sd).sum()

        gq, gk, gv = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        eps = 1e-2
        rngi = np.random.RandomState(99)
        for arr, g, idx in ((q, gq, 0), (k, gk, 1), (v, gv, 2)):
            base = [np.asarray(q), np.asarray(k), np.asarray(v)]
            for _ in range(3):
                pos = tuple(rngi.randint(0, d) for d in arr.shape)
                pert = [a.copy() for a in base]
                pert[idx][pos] += eps
                up = float(f(*map(jnp.asarray, pert)))
                pert[idx][pos] -= 2 * eps
                dn = float(f(*map(jnp.asarray, pert)))
                num = (up - dn) / (2 * eps)
                np.testing.assert_allclose(
                    float(np.asarray(g)[pos]), num, rtol=1e-1,
                    atol=1e-2)

    @pytest.mark.skipif(not pallas_available() or jax.device_count() < 4,
                        reason="needs four TPU chips")
    def test_tpu_sharded_dropout_drops_the_unsharded_links(self):
        # masks key on GLOBAL batch·heads rows, so dp2 x tp2 must
        # reproduce the one-chip output link for link
        from jax.sharding import Mesh
        mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                    ("dp", "tp"))
        q, k, v = self._qkv(b=4, s=256, n=4, h=64)
        one = pk.flash_attention_mha(q, k, v, dropout_p=0.3, seed=5)
        four = pk.flash_attention_mha_sharded(
            q, k, v, mesh, ("dp",), "tp", dropout_p=0.3, seed=5)
        np.testing.assert_allclose(np.asarray(four), np.asarray(one),
                                   rtol=1e-6, atol=1e-6)


class TestMeshWrapper:
    """flash_attention_mha_sharded on the virtual CPU mesh (interpret
    mode; Mosaic's verdict on it is tests/test_pallas_mosaic_compile)."""

    def test_global_rows_tile_the_unsharded_grid(self):
        b, n, dp, tp = 4, 6, 2, 3
        b_l, n_l = b // dp, n // tp
        seen = {}
        for bi in range(dp):
            for hi in range(tp):
                first = bi * b_l * n + hi * n_l
                for r in range(b_l * n_l):
                    want = (bi * b_l + r // n_l) * n + hi * n_l + r % n_l
                    assert pk._global_row(r, first, n_l, n) == want
                    seen[want] = seen.get(want, 0) + 1
        assert seen == {r: 1 for r in range(b * n)}

    @pytest.mark.skipif(jax.device_count() < 4,
                        reason="needs four devices")
    def test_sharded_matches_unsharded(self):
        from jax.sharding import Mesh
        mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                    ("dp", "tp"))
        q, k, v = _data(2, 128, 2, 32)

        def f(attn):  # jitted: one compile per side, not one per op
            return jax.jit(jax.value_and_grad(
                lambda q: jnp.sum(jnp.sin(attn(q)))))(q)
        want, gwant = f(lambda q: flash_attention_mha(
            q, k, v, causal=True, interpret=True))
        got, ggot = f(lambda q: pk.flash_attention_mha_sharded(
            q, k, v, mesh, ("dp",), "tp", causal=True, interpret=True))
        np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(ggot), np.asarray(gwant),
                                   rtol=1e-5, atol=1e-5)


    @pytest.mark.skipif(jax.device_count() < 4,
                        reason="needs four devices")
    def test_sharded_trainstep_hands_its_mesh_to_the_kernel(
            self, monkeypatch):
        # a TrainStep over a mesh must reach the kernel through the
        # wrapper, with ITS mesh — even when the same op at the same
        # shapes was traced mesh-less first (per-op jit caches key on
        # attributes, and the mesh rides as one)
        import paddle_tpu as paddle
        import paddle_tpu.distributed as dist
        import paddle_tpu.nn.functional as F
        from paddle_tpu.models import ErnieConfig, ErnieForPretraining
        from paddle_tpu.static import TrainStep
        seen = []

        def fake_sharded(q, k, v, mesh, batch_axes, head_axis, **kw):
            seen.append((mesh, tuple(batch_axes), head_axis))
            return _sdpa_impl(q, k, v, None, 0.0, kw["causal"], None)
        monkeypatch.setattr(pk, "pallas_available", lambda: True)
        monkeypatch.setattr(pk, "flash_attention_mha_sharded",
                            fake_sharded)
        monkeypatch.setattr(
            pk, "flash_attention_mha",
            lambda q, k, v, causal=False, **kw: _sdpa_impl(
                q, k, v, None, 0.0, causal, None))
        paddle.seed(0)
        cfg = ErnieConfig.tiny(attention_probs_dropout_prob=0.0)
        q = paddle.randn([4, 16, cfg.num_attention_heads,
                          cfg.hidden_size // cfg.num_attention_heads])
        F.flash_attention(q, q, q)          # mesh-less trace, cached
        assert seen == []
        mesh = dist.build_mesh({"dp": 2, "tp": 2})
        model = ErnieForPretraining(cfg)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())
        step = TrainStep(model, ErnieForPretraining.pretraining_loss,
                         opt, mesh=mesh,
                         sharding_plan=dist.ShardingPlan(mesh))
        ids = paddle.to_tensor(np.zeros((4, 16), np.int32))
        assert np.isfinite(float(step(ids, ids).item()))
        assert seen and all(s == (mesh, ("dp",), "tp") for s in seen)


class TestModelAttentionDropout:
    def test_sdpa_dropout_changes_output_and_eval_does_not(self):
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F
        paddle.seed(0)
        rng = np.random.RandomState(0)
        q = paddle.to_tensor(rng.randn(2, 8, 2, 8).astype(np.float32))
        a = F.scaled_dot_product_attention(q, q, q, dropout_p=0.5,
                                           training=True)
        b = F.scaled_dot_product_attention(q, q, q, dropout_p=0.5,
                                           training=True)
        c = F.scaled_dot_product_attention(q, q, q, dropout_p=0.5,
                                           training=False)
        d = F.scaled_dot_product_attention(q, q, q)
        assert np.abs(np.asarray(a._data) - np.asarray(b._data)).max() \
            > 1e-6
        np.testing.assert_allclose(np.asarray(c._data),
                                   np.asarray(d._data))

    def test_ernie_attention_dropout_active_in_train(self):
        import paddle_tpu as paddle
        from paddle_tpu.models import ErnieConfig, ErnieModel
        paddle.seed(1)
        cfg = ErnieConfig.tiny(hidden_dropout_prob=0.0,
                               attention_probs_dropout_prob=0.5)
        m = ErnieModel(cfg)
        rng = np.random.RandomState(1)
        ids = paddle.to_tensor(
            rng.randint(0, cfg.vocab_size, (2, 16)).astype(np.int32))
        m.train()
        a, _ = m(ids)
        b, _ = m(ids)
        assert np.abs(np.asarray(a._data) - np.asarray(b._data)).max() \
            > 1e-6
        m.eval()
        c, _ = m(ids)
        d, _ = m(ids)
        np.testing.assert_allclose(np.asarray(c._data),
                                   np.asarray(d._data))


class TestBlockwiseDropoutTier:
    """The middle dispatch tier (attention.py _flash_dropout_blockwise):
    pure-JAX flash-dropout — flash semantics (denominator over ALL
    links, dropout on the normalized probs, per-block regenerated
    masks) with no Mosaic RNG. What PD_ATTN_DROPOUT_IMPL=blockwise
    selects, and what varlen (kv_lens) batches run."""

    def _qkv(self, b=2, s=64, n=2, h=16, seed=0):
        rng = np.random.RandomState(seed)
        mk = lambda: rng.randn(b, s, n, h).astype(np.float32) * 0.5
        return jnp.asarray(mk()), jnp.asarray(mk()), jnp.asarray(mk())

    def test_p0_equals_no_dropout_flash(self):
        from paddle_tpu.nn.functional.attention import (
            _flash_dropout_blockwise, _flash_attention_op)
        q, k, v = self._qkv()
        base = _flash_attention_op.__pure_fn__(q, k, v, causal=False)
        drop0 = _flash_dropout_blockwise(q, k, v, jax.random.key(3),
                                         False, 0.0, block_k=16)
        np.testing.assert_allclose(np.asarray(drop0), np.asarray(base),
                                   rtol=1e-5, atol=1e-5)

    def test_deterministic_per_key_and_key_sensitive(self):
        from paddle_tpu.nn.functional.attention import (
            _flash_dropout_blockwise)
        q, k, v = self._qkv()
        a = _flash_dropout_blockwise(q, k, v, jax.random.key(7), False,
                                     0.4, block_k=16)
        a2 = _flash_dropout_blockwise(q, k, v, jax.random.key(7), False,
                                      0.4, block_k=16)
        c = _flash_dropout_blockwise(q, k, v, jax.random.key(8), False,
                                     0.4, block_k=16)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(a2))
        assert np.abs(np.asarray(a) - np.asarray(c)).max() > 1e-6

    @pytest.mark.slow  # >15 s on the tier-1 sandbox; run via -m slow
    def test_mean_preserved(self):
        from paddle_tpu.nn.functional.attention import (
            _flash_dropout_blockwise, _flash_attention_op)
        q, k, v = self._qkv(b=1, s=32, n=1, h=8)
        base = np.asarray(_flash_attention_op.__pure_fn__(
            q, k, v, causal=False))
        acc = np.zeros_like(base)
        m = 64
        for sd in range(m):
            acc += np.asarray(_flash_dropout_blockwise(
                q, k, v, jax.random.key(sd), False, 0.3, block_k=8))
        err = np.abs(acc / m - base).max() / (np.abs(base).max() + 1e-9)
        assert err < 0.12, f"dropout mean drift {err}"

    def test_causal_p0_matches_flash_causal(self):
        from paddle_tpu.nn.functional.attention import (
            _flash_dropout_blockwise, _flash_attention_op)
        q, k, v = self._qkv()
        base = _flash_attention_op.__pure_fn__(q, k, v, causal=True)
        drop0 = _flash_dropout_blockwise(q, k, v, jax.random.key(0),
                                         True, 0.0, block_k=16)
        np.testing.assert_allclose(np.asarray(drop0), np.asarray(base),
                                   rtol=1e-5, atol=1e-5)

    def test_grad_finite_and_p0_grad_matches(self):
        from paddle_tpu.nn.functional.attention import (
            _flash_dropout_blockwise, _flash_attention_op)
        q, k, v = self._qkv()
        g_base = jax.grad(lambda q: _flash_attention_op.__pure_fn__(
            q, k, v, causal=False).sum())(q)
        g_p0 = jax.grad(lambda q: _flash_dropout_blockwise(
            q, k, v, jax.random.key(1), False, 0.0, block_k=16).sum())(q)
        np.testing.assert_allclose(np.asarray(g_p0), np.asarray(g_base),
                                   rtol=1e-4, atol=1e-4)
        g_drop = jax.grad(lambda q: _flash_dropout_blockwise(
            q, k, v, jax.random.key(1), False, 0.4, block_k=16).sum())(q)
        g_drop = np.asarray(g_drop)
        assert np.isfinite(g_drop).all() and np.abs(g_drop).max() > 1e-6

    def test_backward_has_no_dense_probs_buffer(self):
        # grad at sq=sk=512, block 128: the rematerialized backward must
        # not hold any 512x512 probs/logits buffer (sdpa fallback would)
        import re
        from paddle_tpu.nn.functional.attention import (
            _flash_dropout_blockwise)
        s = 512
        q = jnp.zeros((1, s, 1, 32), jnp.float32)

        def loss(q):
            return _flash_dropout_blockwise(
                q, q, q, jax.random.key(0), False, 0.2,
                block_k=128).sum()

        text = jax.jit(jax.grad(loss)).lower(q).as_text()
        hits = [ln for ln in text.splitlines()
                if re.search(rf"{s}x{s}", ln)]
        assert not hits, "dense 512x512 buffer in blockwise-dropout " \
            "backward:\n" + "\n".join(hits[:5])

    def test_env_forces_tier(self, monkeypatch):
        from paddle_tpu.nn.functional import attention as am
        monkeypatch.setenv("PD_ATTN_DROPOUT_IMPL", "blockwise")
        assert am.attention_dropout_impl() == "blockwise"
        monkeypatch.setenv("PD_ATTN_DROPOUT_IMPL", "sdpa")
        assert am.attention_dropout_impl() == "sdpa"
        monkeypatch.delenv("PD_ATTN_DROPOUT_IMPL")
        # unforced, the platform alone decides: kernel on a TPU, the
        # sdpa reference elsewhere
        assert am.attention_dropout_impl() == (
            "kernel" if pallas_available() else "sdpa")

    def test_functional_routes_blockwise(self, monkeypatch):
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F
        monkeypatch.setenv("PD_ATTN_DROPOUT_IMPL", "blockwise")
        rng = np.random.RandomState(0)
        q = paddle.to_tensor(rng.randn(2, 32, 2, 16).astype("float32"))
        q.stop_gradient = False
        out = F.flash_attention(q, q, q, dropout=0.3, training=True)
        out.sum().backward()
        g = q.grad.numpy()
        assert np.isfinite(g).all() and np.abs(g).max() > 0


class TestVarlenKvLens:
    """kv_lens (per-batch right-padding bound) through the blockwise
    flash path — the reference's flash_attn_varlen capability without
    materializing masks (attention.py _flash_carry_update)."""

    def _qkv(self, b=3, s=48, n=2, h=16, seed=0):
        rng = np.random.RandomState(seed)
        mk = lambda: rng.randn(b, s, n, h).astype(np.float32) * 0.5
        return jnp.asarray(mk()), jnp.asarray(mk()), jnp.asarray(mk())

    def _sdpa_masked(self, q, k, v, lens, causal=False):
        from paddle_tpu.nn.functional import attention as am
        mask = (np.arange(k.shape[1])[None, :]
                < np.asarray(lens)[:, None])[:, None, None, :]
        return am._sdpa_impl(q, k, v, jnp.asarray(mask), 0.0, causal,
                             None)

    def test_matches_masked_sdpa(self):
        from paddle_tpu.nn.functional.attention import (
            _flash_attention_op)
        q, k, v = self._qkv()
        lens = jnp.asarray([48, 17, 1], jnp.int32)
        got = _flash_attention_op.__pure_fn__(q, k, v, kv_lens=lens,
                                              block_size=16)
        want = self._sdpa_masked(q, k, v, lens)
        got, want = np.asarray(got), np.asarray(want)
        # only rows attending over >=1 valid key are defined; all are
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_causal_matches_masked_sdpa(self):
        from paddle_tpu.nn.functional.attention import (
            _flash_attention_op)
        q, k, v = self._qkv(seed=1)
        lens = jnp.asarray([40, 25, 9], jnp.int32)
        got = _flash_attention_op.__pure_fn__(q, k, v, kv_lens=lens,
                                              causal=True,
                                              block_size=16)
        want = self._sdpa_masked(q, k, v, lens, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_dropout_p0_and_determinism(self):
        from paddle_tpu.nn.functional.attention import _flash_headmajor
        q, k, v = self._qkv(seed=2)
        lens = jnp.asarray([48, 30, 12], jnp.int32)
        base = _flash_headmajor(q, k, v, False, 16, kv_lens=lens)
        p0 = _flash_headmajor(q, k, v, False, 16,
                              dropout=(jax.random.key(5), 0.0),
                              kv_lens=lens)
        np.testing.assert_allclose(np.asarray(p0), np.asarray(base),
                                   rtol=1e-5, atol=1e-5)
        d1 = _flash_headmajor(q, k, v, False, 16,
                              dropout=(jax.random.key(5), 0.4),
                              kv_lens=lens)
        d2 = _flash_headmajor(q, k, v, False, 16,
                              dropout=(jax.random.key(5), 0.4),
                              kv_lens=lens)
        np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))

    def test_ernie_seq_lens_matches_padding_mask(self):
        # explicit seq_lens (varlen flash path) must equal the same
        # model under the equivalent right-padded [b, s] additive mask
        import paddle_tpu as paddle
        from paddle_tpu.models import ErnieConfig, ErnieModel
        kw = dict(vocab_size=211, hidden_size=32, num_hidden_layers=2,
                  num_attention_heads=2, intermediate_size=64,
                  max_position_embeddings=32,
                  hidden_dropout_prob=0.0,
                  attention_probs_dropout_prob=0.0)
        paddle.seed(6)
        m_flash = ErnieModel(ErnieConfig(use_flash_attention=True, **kw))
        paddle.seed(6)
        m_sdpa = ErnieModel(ErnieConfig(use_flash_attention=False, **kw))
        m_flash.eval(), m_sdpa.eval()
        rng = np.random.RandomState(0)
        ids = paddle.to_tensor(
            rng.randint(0, 211, (3, 16)).astype(np.int32))
        lens = (16, 9, 4)
        mask = np.zeros((3, 16), np.int32)
        for i, L in enumerate(lens):
            mask[i, :L] = 1
        a, _ = m_flash(ids, seq_lens=paddle.to_tensor(
            np.asarray(lens, np.int32)))
        b, _ = m_sdpa(ids, attention_mask=paddle.to_tensor(mask))
        np.testing.assert_allclose(np.asarray(a._data),
                                   np.asarray(b._data),
                                   rtol=2e-4, atol=2e-4)
        # mask OR lens, never both
        import pytest as _pytest
        with _pytest.raises(ValueError, match="not both"):
            m_flash(ids, attention_mask=paddle.to_tensor(mask),
                    seq_lens=paddle.to_tensor(
                        np.asarray(lens, np.int32)))

    def test_static_capture_and_eval_clone_keep_kv_lens(self):
        # kv_lens rides an INPUT slot: a static program can feed
        # per-batch lengths at run time, and clone(for_test) — which
        # rewrites flash_attention_dropout to the deterministic op —
        # must carry the varlen bound through (dropping it would
        # silently attend over padding keys in the eval program)
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F
        from paddle_tpu import static

        rng = np.random.RandomState(3)
        qv = rng.randn(2, 32, 2, 8).astype(np.float32)
        main, startup = static.Program(), static.Program()
        with static.program_guard(main, startup):
            q = static.data("q", [2, 32, 2, 8], "float32")
            lens = static.data("lens", [2], "int32")
            out = F.flash_attention(q, q, q, dropout=0.3,
                                    training=True, kv_lens=lens)
        ev = main.clone(for_test=True)
        exe = static.Executor()
        full = np.asarray([32, 32], np.int32)
        short = np.asarray([32, 5], np.int32)
        o_full = exe.run(ev, feed={"q": qv, "lens": full},
                         fetch_list=[out])[0]
        o_short = exe.run(ev, feed={"q": qv, "lens": short},
                          fetch_list=[out])[0]
        # row 0 identical (same lens), row 1 must differ (fewer keys)
        np.testing.assert_allclose(o_full[0], o_short[0], rtol=1e-6)
        assert np.abs(o_full[1] - o_short[1]).max() > 1e-6
        # and the eval clone is deterministic (rng key dropped)
        o_again = exe.run(ev, feed={"q": qv, "lens": short},
                          fetch_list=[out])[0]
        np.testing.assert_array_equal(o_short, o_again)
