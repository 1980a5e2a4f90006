"""2-process geo-SGD worker: each rank makes DIFFERENT local progress;
after GeoSGD.sync() both ranks must hold snapshot + sum(all deltas)
(AsyncConfig geo contract over the coordination-service collective
path). Writes the post-sync param to $PD_TEST_OUT/rank<i>.json."""
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

    import paddle_tpu as paddle
    from paddle_tpu.distributed import GeoSGD
    import jax.numpy as jnp

    w = paddle.create_parameter([4], "float32")
    w._data = jnp.asarray(np.full(4, 1.0, np.float32))
    geo = GeoSGD({"w": w}, sync_steps=2)

    # k local steps of different per-rank progress: rank 0 adds +1/step,
    # rank 1 adds +10/step
    delta = 1.0 if rank == 0 else 10.0
    for _ in range(2):
        w._data = w._data + delta
        geo.step()

    # geo math: 1 + 2*1 + 2*10 = 23 on BOTH ranks after the sync
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"rank{rank}.json"), "w") as f:
        json.dump({"rank": rank,
                   "param": np.asarray(w._data).tolist()}, f)
    jax.distributed.shutdown()


if __name__ == "__main__":
    main()
