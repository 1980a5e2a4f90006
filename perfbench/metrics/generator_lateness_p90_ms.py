"""How late the open-loop generator handed requests over, 90th
percentile over the window's requests (host clock)."""
from perfbench.lib import common


def read(ctx):
    late = ctx["bench"].get("lateness")
    return 1e3 * common.quantile(late, 0.9) if late else None
