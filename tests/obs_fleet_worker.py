"""Worker for the fleet metric-aggregation test: two real trainer
processes bootstrap via TCP rendezvous + the JAX coordination service
(the same path dist_worker.py proves), each records host-local metrics,
then observability.fleet.aggregate() reduces the snapshots over the CPU
collectives. Writes the merged rollup to $PD_TEST_OUT/rank<i>.json."""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")


def main():
    rank = int(os.environ["PADDLE_TRAINER_ID"])
    world = int(os.environ["PADDLE_TRAINERS_NUM"])
    rdzv_port = os.environ["PD_TEST_RDZV_PORT"]
    coord_port = os.environ["PD_TEST_COORD_PORT"]
    out_dir = os.environ["PD_TEST_OUT"]

    from paddle_tpu.distributed.rendezvous import broadcast_bootstrap
    payload = b"obs-fleet-v1" if rank == 0 else None
    blob = broadcast_bootstrap(payload, f"127.0.0.1:{rdzv_port}", rank,
                               world, timeout=60.0)
    assert blob == b"obs-fleet-v1", blob

    jax.distributed.initialize(f"127.0.0.1:{coord_port}",
                               num_processes=world, process_id=rank)
    assert jax.process_count() == world

    from paddle_tpu.observability import fleet, metrics

    metrics.enable()
    # every host adds the same 10 → pod rollup must be world*10
    metrics.counter("obs.test.examples").add(10)
    # rank-distinct gauge → rollup min/max must span the ranks
    metrics.gauge("obs.test.rank_gauge").set(float(rank + 1))
    # per-host histogram: 3 observations each → merged count world*3
    h = metrics.histogram("obs.test.lat_ms")
    for v in (1.0, 2.0, 3.0):
        h.observe(v + rank)

    merged = fleet.aggregate()

    with open(os.path.join(out_dir, f"rank{rank}.json"), "w") as f:
        json.dump({
            "rank": rank,
            "host_count": merged["fleet.host_count"]["value"],
            "examples": merged["obs.test.examples"]["value"],
            "gauge_min": merged["obs.test.rank_gauge"]["min"],
            "gauge_max": merged["obs.test.rank_gauge"]["max"],
            "lat_count": merged["obs.test.lat_ms"]["count"],
        }, f)


if __name__ == "__main__":
    main()
