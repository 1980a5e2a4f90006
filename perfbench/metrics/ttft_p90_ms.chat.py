"""Time from when a request was due to its first token, 90th percentile
over the window's requests (the benchmark's own clock)."""
from perfbench.lib import common


def read(ctx):
    v = [r.first_token - r.due for r in ctx["bench"]["records"]
         if r.first_token is not None]
    return 1e3 * common.quantile(v, 0.9) if v else None
