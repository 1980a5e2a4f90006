"""2-process data-parallel TrainStep worker (reference
test_dist_base.py:671 convergence pattern: N-trainer losses must match
the single-process run). Each process owns one CPU device; the global
dp=2 mesh spans processes, so the grad all-reduce crosses the
coordination-service-bootstrapped comm — the NCCL-ring equivalent.
Writes per-step losses to $PD_TEST_OUT/rank<i>.json."""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 1)

import numpy as np


def main():
    rank = int(os.environ["PADDLE_TRAINER_ID"])
    world = int(os.environ["PADDLE_TRAINERS_NUM"])
    coord_port = os.environ["PD_TEST_COORD_PORT"]
    out_dir = os.environ["PD_TEST_OUT"]

    jax.distributed.initialize(f"127.0.0.1:{coord_port}",
                               num_processes=world, process_id=rank)
    assert jax.device_count() == world

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.static import TrainStep

    mesh = dist.build_mesh({"dp": world}, devices=jax.devices()[:world])
    dist.set_mesh(mesh)
    plan = dist.ShardingPlan(mesh, zero_stage=1)

    paddle.seed(0)
    net = paddle.nn.Sequential(
        paddle.nn.Linear(16, 32), paddle.nn.ReLU(),
        paddle.nn.Linear(32, 4))
    opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=net.parameters())
    step = TrainStep(net, lambda o, y: ((o - y) ** 2).mean(), opt,
                     mesh=mesh, sharding_plan=plan)

    # identical global batch on every process (deterministic rng); jax
    # shards it over the cross-process dp axis
    rng = np.random.RandomState(0)
    from jax.sharding import NamedSharding, PartitionSpec as P
    losses = []
    for i in range(3):
        gx = rng.randn(8, 16).astype(np.float32)
        gy = rng.randn(8, 4).astype(np.float32)
        x = jax.device_put(gx, NamedSharding(mesh, P("dp")))
        y = jax.device_put(gy, NamedSharding(mesh, P("dp")))
        loss = step(paddle.Tensor(x), paddle.Tensor(y))
        losses.append(float(loss.item()))

    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"rank{rank}.json"), "w") as f:
        json.dump({"rank": rank, "losses": losses}, f)
    jax.distributed.shutdown()


if __name__ == "__main__":
    main()
