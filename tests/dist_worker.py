"""Trainer worker for the multi-process distributed test (the reference's
dist_mnist.py-style model file run by test_dist_base.py:671 forked
trainers). Launched by paddle_tpu.distributed.launch with
PADDLE_TRAINER_ID / PADDLE_TRAINERS_NUM set.

Phase 1: TCP rendezvous — rank 0 broadcasts a topology blob
         (gen_comm_id_helper.cc capability).
Phase 2: jax.distributed.initialize (the coordination service that
         replaces NCCL-id exchange) + a cross-process all-reduce through
         a 2-device global mesh on the CPU backend.
Writes {rank, world, devices, allreduce} JSON to $PD_TEST_OUT/rank<i>.json.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np


def main():
    rank = int(os.environ["PADDLE_TRAINER_ID"])
    world = int(os.environ["PADDLE_TRAINERS_NUM"])
    rdzv_port = os.environ["PD_TEST_RDZV_PORT"]
    coord_port = os.environ["PD_TEST_COORD_PORT"]
    out_dir = os.environ["PD_TEST_OUT"]

    # phase 1: bootstrap blob broadcast over raw TCP. Importing
    # paddle_tpu must NOT initialize the XLA backend (that would break
    # jax.distributed.initialize below — the same ordering rule the
    # reference has for gen_comm_id before NCCL comm init); this import
    # doubles as the regression test for that lazy-init property.
    from paddle_tpu.distributed.rendezvous import broadcast_bootstrap
    payload = b"cluster-topology-v1" if rank == 0 else None
    blob = broadcast_bootstrap(payload, f"127.0.0.1:{rdzv_port}", rank,
                               world, timeout=60.0)
    assert blob == b"cluster-topology-v1", blob

    # phase 2: multi-controller init + cross-process allreduce
    jax.distributed.initialize(f"127.0.0.1:{coord_port}",
                               num_processes=world, process_id=rank)
    assert jax.process_count() == world
    n_dev = jax.device_count()
    assert n_dev >= world, jax.devices()

    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:world]), ("dp",))
    local = jnp.full((1, 4), float(rank + 1), jnp.float32)
    garr = jax.make_array_from_single_device_arrays(
        (world, 4), NamedSharding(mesh, P("dp")),
        [jax.device_put(local, jax.local_devices()[0])])
    # the jitted sum lowers to an XLA all-reduce across the two processes
    total = jax.jit(jnp.sum,
                    out_shardings=NamedSharding(mesh, P()))(garr)
    value = float(np.asarray(total))

    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"rank{rank}.json"), "w") as f:
        json.dump({"rank": rank, "world": world, "devices": n_dev,
                   "allreduce": value}, f)
    jax.distributed.shutdown()


if __name__ == "__main__":
    main()
