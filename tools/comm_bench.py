"""Gradient-sync communication receipt (tools/comm_bench.py).

Prints ONE JSON line measuring the distributed.comm levers at
ERNIE-tiny scale, via the same StatRegistry counters production scrapes
(`comm.algo` / `comm.fused_buckets` / `comm.wire_bytes`,
`collective.calls`/`collective.bytes`) — the numbers ARE the telemetry,
not a parallel bookkeeping path:

  per_tensor_collectives   collectives the pre-PR path issues (one flat
                           all-reduce per grad tensor)
  fused_collectives        collectives under bucketing (one per fused
                           bucket) — the >=4x count-reduction receipt
  wire_bytes_{f32,bf16,int8_ef}  on-wire payload bytes per sync under
                           each compression tier — bf16 must be <=0.55x
                           f32 (the tier-1 smoke pins both ratios)
  f32_bit_exact            the default tier returns bit-identical grads
  fr_enter_events          flight-recorder enter events per fused sync
                           (enter/exit per fused collective, NOT per
                           tensor — the PR4 seq convention)

PD_COMM_BENCH_DIST=1 adds a 2-process gloo CPU leg: both ranks run the
per-tensor and fused/compressed syncs over a REAL dp=2 mesh
(rendezvous + jax.distributed, the dist_worker pattern), verify numeric
parity of the fused sync against the cross-rank sum, and report each
rank's counter receipts.

Env: PD_COMM_BENCH_BUCKET_MB (default 4), PD_COMM_BENCH_DIST.
"""
import json
import os
import socket
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

jax.config.update("jax_platforms", "cpu")

BUCKET_MB = float(os.environ.get("PD_COMM_BENCH_BUCKET_MB", 4.0))


def _ernie_tiny_grads():
    """Param-shaped gradient pytree at ERNIE-tiny scale (values are the
    init weights — nonzero, realistic magnitudes for the int8 blocks)."""
    import paddle_tpu as paddle
    from paddle_tpu.models import ErnieConfig, ErnieForPretraining
    paddle.seed(7)
    model = ErnieForPretraining(ErnieConfig.tiny())
    return {k: t._data for k, t in model.state_dict().items()
            if not t.stop_gradient}


def _counter_delta(before, after, prefix):
    tot = 0
    for k, v in after.items():
        if k.startswith(prefix) and v.get("type") == "counter":
            tot += v["value"] - before.get(k, {}).get("value", 0)
    return tot


def _sync_wire_bytes(grads, config):
    """One fused sync under `config`; returns (synced, wire bytes,
    fused collective count) from the counter deltas."""
    from paddle_tpu.distributed.comm import GradSynchronizer
    from paddle_tpu.observability import metrics
    sync = GradSynchronizer(config)
    state = sync.init_state(grads)
    before = metrics.snapshot("comm.")
    out, _ = sync(grads, state)
    after = metrics.snapshot("comm.")
    return (out, _counter_delta(before, after, "comm.wire_bytes"),
            _counter_delta(before, after, "comm.algo"))


def single_process_leg():
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.comm import CommConfig
    from paddle_tpu.observability import flight_recorder as fr
    from paddle_tpu.observability import metrics

    metrics.enable()
    grads = _ernie_tiny_grads()
    n = len(grads)
    total_bytes = int(sum(int(np.prod(np.shape(g), dtype=np.int64))
                          * np.dtype(g.dtype).itemsize
                          for g in grads.values()))

    # pre-PR baseline: one flat full-precision all-reduce per tensor
    before = metrics.snapshot("collective.")
    for g in grads.values():
        dist.all_reduce(paddle.to_tensor(np.asarray(g)))
    after = metrics.snapshot("collective.")
    per_tensor_calls = _counter_delta(before, after, "collective.calls")
    per_tensor_bytes = _counter_delta(before, after, "collective.bytes")

    bucket_bytes = int(BUCKET_MB * (1 << 20))
    cfg = lambda **kw: CommConfig(bucket_bytes=bucket_bytes, **kw)
    f32_out, wire_f32, fused_calls = _sync_wire_bytes(grads, cfg())
    f32_exact = all(
        np.array_equal(np.asarray(f32_out[k]), np.asarray(grads[k]))
        for k in grads)
    _, wire_bf16, _ = _sync_wire_bytes(grads, cfg(compress="bf16"))
    _, wire_int8, _ = _sync_wire_bytes(grads, cfg(compress="int8_ef"))

    # flight-recorder convention receipt: enter/exit per FUSED
    # collective (bucket count), not per tensor
    fr.enable()
    from paddle_tpu.distributed.comm import GradSynchronizer
    sync = GradSynchronizer(cfg())
    sync(grads, {})
    enters = [e for e in fr.get_recorder().events()
              if e.get("k") == "collective.enter"
              and str(e.get("op", "")).startswith("fused_allreduce")]
    fr.disable()

    return {
        "n_grad_tensors": n,
        "total_grad_mb": round(total_bytes / (1 << 20), 3),
        "bucket_mb": BUCKET_MB,
        "per_tensor_collectives": per_tensor_calls,
        "per_tensor_wire_bytes": per_tensor_bytes,
        "fused_collectives": fused_calls,
        "collective_count_ratio": round(fused_calls
                                        / max(per_tensor_calls, 1), 4),
        "wire_bytes_f32": wire_f32,
        "wire_bytes_bf16": wire_bf16,
        "wire_bytes_int8_ef": wire_int8,
        "wire_ratio_bf16": round(wire_bf16 / max(wire_f32, 1), 4),
        "wire_ratio_int8_ef": round(wire_int8 / max(wire_f32, 1), 4),
        "f32_bit_exact": bool(f32_exact),
        "fr_enter_events": len(enters),
    }


# ---------------------------------------------------------------------------
# 2-process gloo leg
# ---------------------------------------------------------------------------

def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def dist_leg():
    """Launch 2 trainer processes of this same file (worker mode) and
    merge their per-rank receipts."""
    import tempfile
    out_dir = tempfile.mkdtemp(prefix="comm_bench_")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.update({
        "PD_TEST_RDZV_PORT": str(_free_port()),
        "PD_TEST_COORD_PORT": str(_free_port()),
        "PD_TEST_OUT": out_dir,
        "PD_COMM_BENCH_WORKER": "1",
        "XLA_FLAGS": "",  # children pick their own backend
    })
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
           "--nproc_per_node", "2", os.path.abspath(__file__)]
    res = subprocess.run(cmd, env=env, cwd=repo, capture_output=True,
                         text=True, timeout=240)
    if res.returncode != 0:
        raise RuntimeError(
            f"dist leg failed rc={res.returncode}: {res.stderr[-1500:]}")
    ranks = []
    for r in range(2):
        with open(os.path.join(out_dir, f"rank{r}.json")) as f:
            ranks.append(json.load(f))
    return {
        "world": 2,
        "parity_ok": all(r["parity_ok"] for r in ranks),
        "collective_count_ratio": ranks[0]["collective_count_ratio"],
        "wire_ratio_bf16": ranks[0]["wire_ratio_bf16"],
        "ranks": ranks,
    }


def dist_worker():
    """One trainer rank of the 2-process leg (dist_worker.py pattern:
    rendezvous -> gloo collectives -> jax.distributed)."""
    rank = int(os.environ["PADDLE_TRAINER_ID"])
    world = int(os.environ["PADDLE_TRAINERS_NUM"])
    out_dir = os.environ["PD_TEST_OUT"]

    from paddle_tpu.distributed.rendezvous import broadcast_bootstrap
    payload = b"comm-bench-v1" if rank == 0 else None
    blob = broadcast_bootstrap(
        payload, f"127.0.0.1:{os.environ['PD_TEST_RDZV_PORT']}", rank,
        world, timeout=60.0)
    assert blob == b"comm-bench-v1", blob

    jax.distributed.initialize(
        f"127.0.0.1:{os.environ['PD_TEST_COORD_PORT']}",
        num_processes=world, process_id=rank)
    assert jax.process_count() == world

    import paddle_tpu.distributed as dist
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.distributed.comm import CommConfig, GradSynchronizer
    from paddle_tpu.distributed.env import axis_context
    from paddle_tpu.observability import metrics

    metrics.enable()
    mesh = dist.build_mesh({"dp": world})
    grads = _ernie_tiny_grads()
    keys = sorted(grads)
    # per-rank distinct values: rank r holds (r+1) * g — the fused sum
    # must equal 3g at world 2 on BOTH ranks
    shards = {k: np.stack([(r + 1.0) * np.asarray(grads[k])
                           for r in range(world)]) for k in keys}

    def garr(a):
        sh = NamedSharding(mesh, P("dp", *([None] * (a.ndim - 1))))
        return jax.make_array_from_callback(a.shape, sh,
                                            lambda idx: a[idx])

    gin = tuple(garr(shards[k]) for k in keys)
    in_specs = tuple(P("dp", *([None] * (shards[k].ndim - 1)))
                     for k in keys)

    bucket_bytes = int(BUCKET_MB * (1 << 20))

    def run_leg(body):
        sm = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                           out_specs=in_specs, check_vma=False)
        before = metrics.snapshot()
        out = jax.jit(sm)(*gin)
        jax.block_until_ready(out)
        return out, before, metrics.snapshot()

    from paddle_tpu.framework import Tensor as _T

    def _arr(x):
        return x._data if isinstance(x, _T) else x

    # leg 1: pre-PR per-tensor flat all-reduce
    def per_tensor(*gs):
        with axis_context("dp"):
            return tuple(_arr(dist.all_reduce(g[0]))[None] for g in gs)
    _, b1, a1 = run_leg(per_tensor)
    per_tensor_calls = _counter_delta(b1, a1, "collective.calls")

    def fused_body(config):
        sync = GradSynchronizer(config)

        def body(*gs):
            with axis_context("dp"):
                d = {k: g[0] for k, g in zip(keys, gs)}
                out, _ = sync(d, sync.init_state(d))
            return tuple(out[k][None] for k in keys)
        return body

    out_f32, b2, a2 = run_leg(fused_body(
        CommConfig(bucket_bytes=bucket_bytes)))
    fused_calls = _counter_delta(b2, a2, "comm.algo")
    wire_f32 = _counter_delta(b2, a2, "comm.wire_bytes")
    _, b3, a3 = run_leg(fused_body(
        CommConfig(bucket_bytes=bucket_bytes, compress="bf16")))
    wire_bf16 = _counter_delta(b3, a3, "comm.wire_bytes")

    # parity: fused f32 sync == sum over ranks (= 3g at world 2);
    # check this rank's addressable shard (the global array spans both
    # processes)
    expect = sum(range(1, world + 1))
    parity = all(
        np.allclose(
            np.asarray(o.addressable_shards[0].data)[0],
            expect * np.asarray(grads[k]), rtol=1e-6, atol=0)
        for k, o in zip(keys, out_f32))

    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"rank{rank}.json"), "w") as f:
        json.dump({
            "rank": rank,
            "parity_ok": bool(parity),
            "per_tensor_collectives": per_tensor_calls,
            "fused_collectives": fused_calls,
            "collective_count_ratio": round(
                fused_calls / max(per_tensor_calls, 1), 4),
            "wire_bytes_f32": wire_f32,
            "wire_bytes_bf16": wire_bf16,
            "wire_ratio_bf16": round(wire_bf16 / max(wire_f32, 1), 4),
        }, f)
    jax.distributed.shutdown()


def main():
    out = single_process_leg()
    if os.environ.get("PD_COMM_BENCH_DIST") == "1":
        try:
            out["dist"] = dist_leg()
        except Exception as e:  # pragma: no cover — artifact survives
            out["dist_error"] = f"{type(e).__name__}: {e}"
    # one-code-path export bridge (PR3): the printed report and the
    # JSONL series come from emit_report when PD_OBS_JSONL is set
    try:
        from paddle_tpu.observability import exporters as obs_exporters
        out = obs_exporters.emit_report(
            out, jsonl_path=os.environ.get("PD_OBS_JSONL"),
            prefix="bench.comm")
    except Exception as e:  # pragma: no cover — the artifact survives
        out["obs_export_error"] = f"{type(e).__name__}: {e}"
    print(json.dumps(out))


if __name__ == "__main__":
    if os.environ.get("PD_COMM_BENCH_WORKER") == "1":
        dist_worker()
    else:
        main()
