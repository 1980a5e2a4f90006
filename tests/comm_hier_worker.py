"""Worker for the hierarchical all-reduce cross-process test: two real
trainer processes x 2 virtual CPU devices each form the factored
('host', 'chip') mesh where 'host' CROSSES the process boundary — the
topology the HiCCL-style schedule exists for. Each rank runs the flat
all-reduce and the hierarchical schedule (intra-host reduce-scatter ->
inter-host all-reduce on shards -> intra-host all-gather) over
rank-distinct data and writes both results plus its comm.algo counter
labels to $PD_TEST_OUT/rank<i>.json; the parent asserts numeric parity
and that BOTH ranks recorded the planner's algo labels."""
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
    payload = b"comm-hier-v1" if rank == 0 else None
    blob = broadcast_bootstrap(
        payload, f"127.0.0.1:{os.environ['PD_TEST_RDZV_PORT']}", rank,
        world, timeout=60.0)
    assert blob == b"comm-hier-v1", blob

    jax.distributed.initialize(
        f"127.0.0.1:{os.environ['PD_TEST_COORD_PORT']}",
        num_processes=world, process_id=rank)
    assert jax.device_count() == 2 * world

    import paddle_tpu.distributed as dist
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.distributed.comm import CommConfig, planned_all_reduce
    from paddle_tpu.distributed.env import axis_context
    from paddle_tpu.observability import metrics

    metrics.enable()
    # 'host' spans the process boundary (process 0's devices fill host
    # row 0), 'chip' stays within a process — assert the factoring
    mesh = dist.build_mesh({"host": world, "chip": 2})
    host_rows = mesh.devices  # [host, chip] array of Devices
    for h in range(world):
        procs = {d.process_index for d in host_rows[h]}
        assert procs == {h}, (h, procs)

    # one distinct shard per DEVICE (4 total): global [4, 8]
    gnp = (np.arange(32, dtype=np.float32).reshape(4, 8) + 1.0)
    sh = NamedSharding(mesh, P(("host", "chip"), None))
    arr = jax.make_array_from_callback((4, 8), sh, lambda idx: gnp[idx])
    expect = gnp.sum(axis=0)

    from paddle_tpu.framework import Tensor as _T

    def _arr(t):
        return t._data if isinstance(t, _T) else t

    def body(x):  # local [1, 8] per device
        with axis_context("host", "chip"):
            flat = planned_all_reduce(
                x, CommConfig(algorithm="flat"),
                axes=("host", "chip"))
            hier = planned_all_reduce(
                x, CommConfig(algorithm="hierarchical",
                              hierarchy=("host", "chip")))
        return _arr(flat), _arr(hier)

    sm = jax.shard_map(body, mesh=mesh,
                       in_specs=P(("host", "chip"), None),
                       out_specs=(P(("host", "chip"), None),) * 2,
                       check_vma=False)
    flat, hier = jax.jit(sm)(arr)
    jax.block_until_ready((flat, hier))
    # this rank's addressable shard of each output (values are
    # replicated post-all-reduce; every shard must equal the full sum)
    flat_local = np.asarray(flat.addressable_shards[0].data)[0]
    hier_local = np.asarray(hier.addressable_shards[0].data)[0]

    labels = {k: v["value"] for k, v in
              metrics.snapshot("comm.algo").items()}
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"rank{rank}.json"), "w") as f:
        json.dump({
            "rank": rank,
            "flat": flat_local.tolist(),
            "hier": hier_local.tolist(),
            "expect": expect.tolist(),
            "algo_labels": labels,
        }, f)
    jax.distributed.shutdown()


if __name__ == "__main__":
    main()
