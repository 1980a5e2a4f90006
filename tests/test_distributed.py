"""Distributed tests on the 8-device virtual CPU mesh.

Better than the reference's approach (test_dist_base.py forks real
multi-GPU processes): XLA's forced host device count gives us real SPMD
partitioning + collectives in one process, so DP/TP/ZeRO/ring/pipeline
paths run in CI.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
import paddle_tpu.distributed as dist
from paddle_tpu.distributed import fleet
from paddle_tpu.static import TrainStep


@pytest.fixture(autouse=True)
def fresh_mesh():
    dist.set_mesh(None)
    yield
    dist.set_mesh(None)


def test_eight_devices_visible():
    assert len(jax.devices()) == 8


def test_collectives_in_shard_map():
    mesh = dist.build_mesh({"dp": 8})
    dist.set_mesh(mesh)

    def body(x):
        s = dist.all_reduce(x.clone(), op=dist.ReduceOp.SUM)
        mx = dist.all_reduce(x.clone(), op=dist.ReduceOp.MAX)
        g = dist.all_gather(x)
        rs = dist.reduce_scatter(g.reshape([-1]))
        return s, mx, g, rs

    wrapped = dist.shard_parallel(
        body, mesh, in_specs=P("dp"),
        out_specs=(P("dp"), P("dp"), P(None, None), P("dp")))
    x = paddle.to_tensor(np.arange(8, dtype=np.float32))
    s, mx, g, rs = wrapped(x)
    np.testing.assert_allclose(s.numpy(), np.full(8, 28.0))  # sum 0..7
    np.testing.assert_allclose(mx.numpy(), np.full(8, 7.0))
    # all_gather: every rank holds all 8 values (replicated [8,1])
    assert g.shape == [8, 1]
    np.testing.assert_allclose(g.numpy().ravel(), np.arange(8))
    # reduce_scatter of the gathered [8] per rank: each rank gets sum/8
    np.testing.assert_allclose(rs.numpy(), np.arange(8) * 8.0)


def test_p2p_shift_ring():
    mesh = dist.build_mesh({"sp": 8})

    def body(x):
        return dist.p2p_shift(x, shift=1, group="sp")

    wrapped = dist.shard_parallel(body, mesh, in_specs=P("sp"),
                                  out_specs=P("sp"), axes=("sp",))
    x = paddle.to_tensor(np.arange(8, dtype=np.float32))
    y = wrapped(x)
    np.testing.assert_allclose(y.numpy(), np.roll(np.arange(8), 1))


def test_collective_star_import_exports_resolve():
    """Regression: __all__ listed `recv` before any recv existed, so
    `from ...collective import *` raised — every exported name must
    resolve to a real attribute."""
    from paddle_tpu.distributed import collective
    ns = {}
    exec("from paddle_tpu.distributed.collective import *", ns)
    missing = [n for n in collective.__all__ if n not in ns]
    assert not missing, f"__all__ names not importable: {missing}"
    assert callable(ns["recv"]) and callable(ns["send"])


def test_send_recv_loopback_world_size_one():
    """send_v2/recv_v2 at world size 1: the staged payload loops back
    (same model file runs anywhere)."""
    x = paddle.to_tensor(np.arange(4, dtype=np.float32))
    dist.send(x, dst=0)
    y = dist.recv(src=0)
    np.testing.assert_allclose(y.numpy(), np.arange(4))


def test_send_recv_pair_in_shard_map():
    """SPMD p2p: send() stages, recv() issues ONE ppermute [(src, dst)]
    — dst gets src's payload, every other rank keeps its own buffer."""
    mesh = dist.build_mesh({"pp": 8})

    def body(x):
        dist.send(x, dst=3, group="pp")
        return dist.recv(x, src=1, group="pp")

    wrapped = dist.shard_parallel(body, mesh, in_specs=P("pp"),
                                  out_specs=P("pp"), axes=("pp",))
    x = paddle.to_tensor(np.arange(8, dtype=np.float32))
    y = wrapped(x)
    expect = np.arange(8, dtype=np.float32)
    expect[3] = 1.0                       # rank 3 received rank 1's value
    np.testing.assert_allclose(y.numpy(), expect)


def test_mirror_into_copies_autograd_linkage():
    """In-place collectives must mirror the result's _node/_out_idx,
    not just _data — a stale node backprops through the pre-collective
    value (one helper, one hazard: all_reduce/broadcast/reduce/recv)."""
    from paddle_tpu.distributed import collective as C
    a = paddle.to_tensor(np.ones(3, np.float32), stop_gradient=False)
    b = a * 2.0                                # carries an autograd node
    t = paddle.to_tensor(np.zeros(3, np.float32))
    out = C._mirror_into(t, b)
    assert out is t
    assert t._node is b._node and t._out_idx == b._out_idx
    np.testing.assert_allclose(t.numpy(), 2.0)
    C._mirror_into(t, np.arange(3, dtype=np.float32))  # raw array source
    assert t._node is None and t._out_idx == 0
    np.testing.assert_allclose(t.numpy(), np.arange(3))


def test_reduce_in_place_mirrors_result():
    """dist.reduce mutates its input in place (paddle surface): the
    returned tensor IS the input, holding the reduced value on dst."""
    mesh = dist.build_mesh({"dp": 8})

    def body(x):
        y = dist.reduce(x, dst=0)
        assert y is x                          # in-place contract
        return y

    wrapped = dist.shard_parallel(body, mesh, in_specs=P("dp"),
                                  out_specs=P("dp"))
    x = paddle.to_tensor(np.arange(8, dtype=np.float32))
    y = wrapped(x)
    exp = np.arange(8, dtype=np.float32)
    exp[0] = 28.0                              # sum 0..7 lands on dst
    np.testing.assert_allclose(y.numpy(), exp)


def test_recv_without_send_raises():
    with pytest.raises(RuntimeError, match="staged"):
        dist.recv(src=0)


def test_recv_on_wrong_axis_raises():
    """A recv must pair with the staged send over the SAME group —
    silently ppermuting over a different axis would move the wrong
    payload."""
    mesh = dist.build_mesh({"pp": 2, "dp": 4})

    def body(x):
        dist.send(x, dst=0, group="pp")
        return dist.recv(x, src=0, group="dp")

    wrapped = dist.shard_parallel(body, mesh, in_specs=P("pp", "dp"),
                                  out_specs=P("pp", "dp"),
                                  axes=("pp", "dp"))
    x = paddle.to_tensor(np.arange(8, dtype=np.float32).reshape(2, 4))
    with pytest.raises(RuntimeError, match="SAME group"):
        wrapped(x)
    # the mismatch peeked, not popped: the staged send is still queued
    # (recoverable pairing) — drop it so later tests start clean
    from paddle_tpu.distributed import collective
    assert len(collective._p2p_staged) == 1
    collective._p2p_staged.clear()


def test_broadcast_in_shard_map():
    mesh = dist.build_mesh({"dp": 8})

    def body(x):
        return dist.broadcast(x.clone(), src=3)

    wrapped = dist.shard_parallel(body, mesh, in_specs=P("dp"),
                                  out_specs=P("dp"))
    x = paddle.to_tensor(np.arange(8, dtype=np.float32))
    y = wrapped(x)
    np.testing.assert_allclose(y.numpy(), np.full(8, 3.0))


def test_data_parallel_training_step_sharded():
    """DP via TrainStep + ShardingPlan over dp axis: param update must
    equal single-device training on the full batch."""
    paddle.seed(21)
    mesh = dist.build_mesh({"dp": 8})
    plan = dist.ShardingPlan(mesh)

    def make_model():
        paddle.seed(42)
        return nn.Linear(4, 2)

    xs = np.random.randn(16, 4).astype(np.float32)
    ys = np.random.randn(16, 2).astype(np.float32)

    net_a = make_model()
    opt_a = paddle.optimizer.SGD(learning_rate=0.1,
                                 parameters=net_a.parameters())
    step_a = TrainStep(net_a, lambda o, y: F.mse_loss(o, y), opt_a,
                       mesh=mesh, sharding_plan=plan)
    loss_a = step_a(paddle.to_tensor(xs), paddle.to_tensor(ys))

    net_b = make_model()
    opt_b = paddle.optimizer.SGD(learning_rate=0.1,
                                 parameters=net_b.parameters())
    step_b = TrainStep(net_b, lambda o, y: F.mse_loss(o, y), opt_b)
    loss_b = step_b(paddle.to_tensor(xs), paddle.to_tensor(ys))

    np.testing.assert_allclose(loss_a.item(), loss_b.item(), rtol=1e-5)
    for k in step_a.params:
        np.testing.assert_allclose(np.asarray(step_a.params[k]),
                                   np.asarray(step_b.params[k]), atol=1e-5)


def test_zero_sharding_optimizer_state():
    """ZeRO-1: Adam moments sharded over dp; result matches replicated."""
    paddle.seed(22)
    mesh = dist.build_mesh({"dp": 8})
    plan = dist.ShardingPlan(mesh, zero_stage=1)

    def make():
        paddle.seed(5)
        return nn.Linear(8, 8)

    xs = np.random.randn(16, 8).astype(np.float32)
    ys = np.random.randn(16, 8).astype(np.float32)

    net = make()
    opt = paddle.optimizer.Adam(learning_rate=0.01,
                                parameters=net.parameters())
    step = TrainStep(net, lambda o, y: F.mse_loss(o, y), opt, mesh=mesh,
                     sharding_plan=plan)
    # moment arrays must actually be sharded over dp
    m = step.opt_state["weight"]["moment1"]
    assert not m.sharding.is_fully_replicated

    net2 = make()
    opt2 = paddle.optimizer.Adam(learning_rate=0.01,
                                 parameters=net2.parameters())
    step2 = TrainStep(net2, lambda o, y: F.mse_loss(o, y), opt2)
    for _ in range(3):
        la = step(paddle.to_tensor(xs), paddle.to_tensor(ys))
        lb = step2(paddle.to_tensor(xs), paddle.to_tensor(ys))
    np.testing.assert_allclose(la.item(), lb.item(), rtol=1e-4)
    # one jit signature across steps: the scalar beta-pow state goes in
    # placed on the mesh, as it comes back out
    assert step._step_fn._cache_size() == 1
    assert step.recompile_sentinel.fired == 0


def test_tensor_parallel_linear_spec_mode():
    """TP via sharding specs: col+row parallel pair matches dense."""
    paddle.seed(23)
    mesh = dist.build_mesh({"tp": 8})
    dist.set_mesh(mesh)
    col = dist.ColumnParallelLinear(16, 32, gather_output=False)
    row = dist.RowParallelLinear(32, 16)
    assert col.weight.sharding_spec == P(None, "tp")
    assert row.weight.sharding_spec == P("tp", None)
    x = paddle.randn([4, 16])
    # run inside pjit with param shardings applied
    wc, bc = col.inner.weight, col.inner.bias
    wr, br = row.inner.weight, row.inner.bias

    @jax.jit
    def f(x, wc, bc, wr, br):
        h = x @ wc + bc
        h = jax.nn.relu(h)
        return h @ wr + br

    wc_s = jax.device_put(wc._data, NamedSharding(mesh, P(None, "tp")))
    wr_s = jax.device_put(wr._data, NamedSharding(mesh, P("tp", None)))
    out = f(x._data, wc_s, bc._data, wr_s, br._data)
    ref = jax.nn.relu(x.numpy() @ wc.numpy() + bc.numpy()) @ wr.numpy() \
        + br.numpy()
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-4)


def test_tp_layers_in_shard_map():
    """Explicit shard_map mode: RowParallelLinear psums partial products."""
    paddle.seed(24)
    mesh = dist.build_mesh({"tp": 8})
    dist.set_mesh(mesh)
    row = dist.RowParallelLinear(16, 4)
    w = row.inner.weight.numpy()
    b = row.inner.bias.numpy()
    x = paddle.randn([2, 16])

    def body(xl, wl):
        from paddle_tpu.distributed.collective import all_reduce
        partial = paddle.matmul(xl, wl)
        return all_reduce(partial, group="tp")

    wrapped = dist.shard_parallel(
        body, mesh, in_specs=(P(None, "tp"), P("tp", None)),
        out_specs=P(), axes=("tp",))
    out = wrapped(x, paddle.to_tensor(w))
    np.testing.assert_allclose(out.numpy(), x.numpy() @ w, atol=1e-4)


def test_vocab_parallel_embedding_shard_map():
    paddle.seed(25)
    mesh = dist.build_mesh({"tp": 8})
    dist.set_mesh(mesh)
    vocab, dim = 32, 8
    emb = dist.VocabParallelEmbedding(vocab, dim)
    full_w = emb.inner.weight.numpy()
    ids = np.array([[0, 5, 31], [7, 16, 24]])

    def body(ids_t, w_local):
        import jax.numpy as jnp
        from jax import lax
        from paddle_tpu.ops.registry import run_op

        def impl(ids, wt):
            n = lax.axis_size("tp")
            idx = lax.axis_index("tp")
            per = vocab // n
            local = ids - idx * per
            ok = (local >= 0) & (local < per)
            safe = jnp.where(ok, local, 0)
            e = jnp.take(wt, safe, axis=0)
            e = jnp.where(ok[..., None], e, 0.0)
            return lax.psum(e, "tp")
        return run_op("vpe", impl, (ids_t, w_local), {})

    wrapped = dist.shard_parallel(
        body, mesh, in_specs=(P(), P("tp", None)), out_specs=P(),
        axes=("tp",))
    out = wrapped(paddle.to_tensor(ids), paddle.to_tensor(full_w))
    np.testing.assert_allclose(out.numpy(), full_w[ids], atol=1e-6)


def test_ring_attention_matches_flash():
    """Ring attention over sp=4 must equal single-device flash attention."""
    paddle.seed(26)
    mesh = dist.build_mesh({"sp": 4}, devices=jax.devices()[:4])
    b, s, h, d = 2, 16, 2, 8
    q = paddle.randn([b, s, h, d])
    k = paddle.randn([b, s, h, d])
    v = paddle.randn([b, s, h, d])
    ref = F.scaled_dot_product_attention(q, k, v).numpy()

    def body(q, k, v):
        return dist.ring_flash_attention(q, k, v, causal=False, group="sp")

    spec = P(None, "sp", None, None)
    wrapped = dist.shard_parallel(body, mesh, in_specs=(spec, spec, spec),
                                  out_specs=spec, axes=("sp",))
    out = wrapped(q, k, v)
    np.testing.assert_allclose(out.numpy(), ref, atol=1e-4)
    # causal
    ref_c = F.scaled_dot_product_attention(q, k, v, is_causal=True).numpy()

    def body_c(q, k, v):
        return dist.ring_flash_attention(q, k, v, causal=True, group="sp")
    wrapped_c = dist.shard_parallel(body_c, mesh,
                                    in_specs=(spec, spec, spec),
                                    out_specs=spec, axes=("sp",))
    out_c = wrapped_c(q, k, v)
    np.testing.assert_allclose(out_c.numpy(), ref_c, atol=1e-4)


def test_ulysses_attention_matches():
    paddle.seed(27)
    mesh = dist.build_mesh({"sp": 2}, devices=jax.devices()[:2])
    b, s, h, d = 2, 8, 4, 8
    q = paddle.randn([b, s, h, d])
    k = paddle.randn([b, s, h, d])
    v = paddle.randn([b, s, h, d])
    ref = F.scaled_dot_product_attention(q, k, v).numpy()

    def body(q, k, v):
        return dist.ulysses_attention(q, k, v, group="sp")

    spec = P(None, "sp", None, None)
    wrapped = dist.shard_parallel(body, mesh, in_specs=(spec, spec, spec),
                                  out_specs=spec, axes=("sp",))
    out = wrapped(q, k, v)
    np.testing.assert_allclose(out.numpy(), ref, atol=1e-4)


def test_gpipe_schedule():
    """4-stage pipeline of y=x+1 blocks must add 4 with stage params."""
    mesh = dist.build_mesh({"pp": 4}, devices=jax.devices()[:4])
    num_micro = 8
    from jax import shard_map
    from paddle_tpu.distributed.pipeline import gpipe_schedule

    # stage params: each stage adds its own constant
    stage_consts = jnp.arange(1.0, 5.0)[:, None]  # [4,1]
    x = jnp.ones((num_micro, 2, 3))

    def block_fn(c, xm):
        return xm + c[0]

    def spmd(x, consts):
        import paddle_tpu.distributed.env as env
        with env.axis_context("pp"):
            return gpipe_schedule(block_fn, consts[0], x, num_micro,
                                  axis="pp")

    out = shard_map(spmd, mesh=mesh,
                    in_specs=(P(), P("pp")), out_specs=P(),
                    check_vma=False)(x, stage_consts)
    # output valid on last stage: x + 1+2+3+4 = 11
    np.testing.assert_allclose(np.asarray(out)[:, 0, 0], np.full(8, 11.0))


def test_fleet_init_and_strategy_mesh():
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2,
                               "pp_degree": 2, "sep_degree": 1}
    strategy.pipeline = True
    fleet.init(is_collective=True, strategy=strategy)
    mesh = dist.get_mesh()
    assert dict(zip(mesh.axis_names, mesh.devices.shape)) == {
        "dp": 2, "tp": 2, "pp": 2}


def test_fleet_distributed_optimizer_train_step():
    """fleet strategy compiler → sharded TrainStep (DP8 + AMP + accum)."""
    paddle.seed(28)
    strategy = fleet.DistributedStrategy()
    strategy.amp = True
    strategy.gradient_merge = True
    strategy.gradient_merge_configs = {"k_steps": 2, "avg": True}
    fleet.init(is_collective=True, strategy=strategy)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    opt = paddle.optimizer.Adam(learning_rate=0.01,
                                parameters=net.parameters())
    dopt = fleet.distributed_optimizer(opt)
    step = dopt.build_train_step(net, lambda o, y: F.mse_loss(o, y))
    xs = paddle.randn([16, 8])
    ys = paddle.randn([16, 4])
    l0 = step(xs, ys).item()
    for _ in range(30):
        l1 = step(xs, ys).item()
    assert l1 < l0


def test_recompute_matches_plain():
    paddle.seed(29)
    net = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 4))
    x = paddle.randn([2, 4], "float32")
    x.stop_gradient = False
    y1 = net(x).sum()
    y1.backward()
    g_plain = x.grad.numpy().copy()
    x.clear_grad()
    y2 = dist.recompute(lambda t: net(t), x).sum()
    y2.backward()
    np.testing.assert_allclose(x.grad.numpy(), g_plain, atol=1e-5)


def test_data_parallel_eager_wrapper():
    dist.init_parallel_env({"dp": 8})
    net = nn.Linear(4, 2)
    dp = dist.DataParallel(net)
    x = paddle.randn([16, 4])
    y = dp(x)
    assert y.shape == [16, 2]
    loss = dp.scale_loss(y.sum())
    loss.backward()
    assert net.weight.grad is not None
