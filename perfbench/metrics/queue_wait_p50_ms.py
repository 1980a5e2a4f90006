"""Time from when a request was due to the engine's admission stamp,
median over the window's requests."""
from perfbench.lib import common


def read(ctx):
    v = [r.admitted - r.due for r in ctx["bench"]["records"]
         if r.admitted is not None]
    return 1e3 * common.quantile(v, 0.5) if v else None
