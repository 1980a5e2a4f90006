"""Worker for the cross-host forensics test: two real trainer
processes bootstrap via TCP rendezvous + the JAX coordination service
(the obs_fleet_worker pattern, gloo CPU collectives), arm the flight
recorder, and run a short eager collective program — except rank 1
DELIBERATELY SKIPS the last all_reduce. Each rank then dumps its black
box to $PD_FR_DIR; the parent test merges the dumps with
tools/tpu_doctor.py, which must name rank 1 and the mismatched
(axis, op, seq)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

N_CALLS = 3  # healthy ranks make 3 allreduce calls; rank 1 makes 2


def main():
    rank = int(os.environ["PADDLE_TRAINER_ID"])
    world = int(os.environ["PADDLE_TRAINERS_NUM"])
    rdzv_port = os.environ["PD_TEST_RDZV_PORT"]
    coord_port = os.environ["PD_TEST_COORD_PORT"]

    from paddle_tpu.distributed.rendezvous import broadcast_bootstrap
    payload = b"doctor-div-v1" if rank == 0 else None
    blob = broadcast_bootstrap(payload, f"127.0.0.1:{rdzv_port}", rank,
                               world, timeout=60.0)
    assert blob == b"doctor-div-v1", blob

    jax.distributed.initialize(f"127.0.0.1:{coord_port}",
                               num_processes=world, process_id=rank)
    assert jax.process_count() == world

    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.observability import flight_recorder as fr

    fr.enable()
    x = paddle.to_tensor(np.ones(4, dtype=np.float32))
    # matched prologue on every rank: seq counters must agree here
    dist.barrier()
    n = N_CALLS - 1 if rank == 1 else N_CALLS  # rank 1 skips ONE call
    for _ in range(n):
        dist.all_reduce(x)
    doc = fr.dump(reason="divergence_test")
    assert doc["path"], "dump not written"


if __name__ == "__main__":
    main()
