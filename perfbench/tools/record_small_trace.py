#!/usr/bin/env python3
"""Record the small capture that tests/test_yardstick.py checks the
trace reduction against: a few matmuls and copies on one chip under the
benchmark's own spans. Run on the chip; writes
chiprun_out/small.xplane.pb and chiprun_out/small.expected.json (copy
both to perfbench/tests/data/ after checking the numbers by hand
against describe_trace.py's listing).
"""
import glob
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench.lib import trace_reduce  # noqa: E402


def main():
    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_small_trace: needs a TPU")
    ann = jax.profiler.TraceAnnotation
    x = jnp.ones((1024, 1024), jnp.bfloat16)

    @jax.jit
    def work(a):
        return (a @ a).T.copy() * 0.5

    work(x).block_until_ready()
    logdir = "chiprun_out/small_trace"
    shutil.rmtree(logdir, ignore_errors=True)
    jax.profiler.start_trace(logdir)
    with ann("bench:window"):
        for _ in range(3):
            with ann("bench:step_dispatch"):
                y = work(x)
            with ann("bench:wait_lagged_loss"):
                y.block_until_ready()
            with ann("bench:make_batch"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    pb = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                   recursive=True)[0]
    shutil.copy(pb, "chiprun_out/small.xplane.pb")
    r = trace_reduce.reduce(trace_reduce.load("chiprun_out/small.xplane.pb"))
    with open("chiprun_out/small.expected.json", "w") as f:
        json.dump({k: r[k] for k in ("chips", "window_s", "busy_s",
                                     "device_ops", "idle_gaps")}, f,
                  indent=1)
    shutil.rmtree(logdir, ignore_errors=True)
    print(json.dumps(r["device_ops"]), r["window_s"], r["busy_s"],
          r["idle_gaps"], os.path.getsize("chiprun_out/small.xplane.pb"))


if __name__ == "__main__":
    main()
