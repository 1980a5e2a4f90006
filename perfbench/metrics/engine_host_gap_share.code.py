"""Share of the window outside every dispatch-to-sync interval of the
program's step spans: the host's share, read with no profiler."""
from perfbench.lib import step_spans


def read(ctx):
    return step_spans.host_gap_share(ctx)
