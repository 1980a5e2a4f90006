"""Worker for the graph_lint cross-rank collective-schedule test: two
real trainer processes x 2 virtual CPU devices form the dp=4 gloo mesh
(the comm_hier_worker harness shape). Each rank TRACES (lowers only —
nothing is compiled or dispatched) a shard_map program that issues
collectives through the paddle collective API, with a deliberate
static divergence: rank 1's python skips the second all_reduce, the
classic rank-conditional branch that deadlocks a pod at runtime. The
trace-time schedule capture (analysis.capture_collective_schedule)
records each rank's static (axis, op, shape, dtype) sequence; ranks
dump them to $PD_TEST_OUT/rank<i>.json and the parent runs
verify_collective_schedules — the divergent rank must be NAMED at lint
time, before the runtime doctor (or the hang) would ever see it."""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)

import numpy as np


def main():
    rank = int(os.environ["PADDLE_TRAINER_ID"])
    world = int(os.environ["PADDLE_TRAINERS_NUM"])
    out_dir = os.environ["PD_TEST_OUT"]

    from paddle_tpu.distributed.rendezvous import broadcast_bootstrap
    payload = b"graph-lint-sched-v1" if rank == 0 else None
    blob = broadcast_bootstrap(
        payload, f"127.0.0.1:{os.environ['PD_TEST_RDZV_PORT']}", rank,
        world, timeout=60.0)
    assert blob == b"graph-lint-sched-v1", blob

    jax.distributed.initialize(
        f"127.0.0.1:{os.environ['PD_TEST_COORD_PORT']}",
        num_processes=world, process_id=rank)
    assert jax.device_count() == 2 * world

    import paddle_tpu.distributed as dist
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.analysis import capture_collective_schedule
    from paddle_tpu.distributed import collective
    from paddle_tpu.distributed.env import axis_context
    from paddle_tpu.framework import Tensor as _T

    def _arr(t):
        return t._data if isinstance(t, _T) else t

    mesh = dist.build_mesh({"dp": 2 * world})

    def body(x):  # local [1, 8] per device
        with axis_context("dp"):
            y = _arr(collective.all_reduce(x))
            if rank != 1:
                # the seeded divergence: a rank-conditional PYTHON
                # branch — rank 1's traced program simply lacks this
                # collective. At runtime the other ranks would block
                # in allreduce seq 2 forever.
                y = _arr(collective.all_reduce(y * 2.0))
            return _arr(collective.p2p_shift(y, 1))

    sm = jax.shard_map(body, mesh=mesh, in_specs=P("dp"),
                       out_specs=P("dp"), check_vma=False)
    aval = jax.ShapeDtypeStruct((2 * world, 8), np.float32)
    with capture_collective_schedule() as entries:
        jax.jit(sm).lower(aval)  # TRACE only — never compiled or run

    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"rank{rank}.json"), "w") as f:
        json.dump({"rank": rank, "schedule": list(entries)}, f)
    jax.distributed.shutdown()


if __name__ == "__main__":
    main()
