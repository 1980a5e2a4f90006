"""2-process SPMD-1F1B worker: the pp axis CROSSES the process
boundary (2 procs x 2 devices -> pp=4), validating the engine's
multi-controller claim — the host-driven engine cannot run here at
all (its controller must address every stage's devices;
distributed/pipeline_engine.py docstring), while the one-program
schedule just executes under jax.distributed.

Writes per-step losses to $PD_TEST_OUT/rank<i>.json.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)

import numpy as np


def build_and_run(mesh, steps=3):
    """Shared with the 1-process control (test_spmd_1f1b_multiproc)."""
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    import paddle_tpu.nn as nn

    S, H, M, MB = int(mesh.shape["pp"]), 16, 8, 4

    class Stage(nn.Layer):
        def __init__(self):
            super().__init__()
            self.lin = nn.Linear(H, H)

        def forward(self, xx):
            return paddle.tanh(self.lin(xx))

    paddle.seed(0)
    stages = [Stage() for _ in range(S)]
    engine = dist.SpmdPipelineParallel(
        stages, lambda o, t: ((o - t) ** 2).mean(),
        paddle.optimizer.Adam(learning_rate=1e-2), num_micro=M,
        mesh=mesh)
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(M * MB, H).astype(np.float32))
    t = paddle.to_tensor(rng.randn(M * MB, H).astype(np.float32))
    return [float(engine.train_batch(x, t).item())
            for _ in range(steps)]


def main():
    rank = int(os.environ["PADDLE_TRAINER_ID"])
    world = int(os.environ["PADDLE_TRAINERS_NUM"])
    coord_port = os.environ["PD_TEST_COORD_PORT"]
    out_dir = os.environ["PD_TEST_OUT"]

    jax.distributed.initialize(f"127.0.0.1:{coord_port}",
                               num_processes=world, process_id=rank)
    assert jax.device_count() == 2 * world

    import paddle_tpu.distributed as dist
    mesh = dist.build_mesh({"pp": 2 * world})
    # stages 0..1 live on process 0's devices, 2..3 on process 1's:
    # the stage 1 -> 2 activation hop crosses the process boundary
    procs = [d.process_index for d in mesh.devices.ravel()]
    assert procs == sorted(procs) and len(set(procs)) == world, (
        f"pp axis does not cross the process boundary: {procs}")

    losses = build_and_run(mesh)

    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"rank{rank}.json"), "w") as f:
        json.dump({"rank": rank, "losses": losses}, f)
    jax.distributed.shutdown()


if __name__ == "__main__":
    main()
