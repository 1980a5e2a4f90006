"""Per engine step the host time in alloc and build (a state row a
request admitted, the row ids and lengths a dispatch is fed); median
over the window's steps."""
from perfbench.lib import step_spans


def read(ctx):
    return step_spans.phases_ms_p50(ctx, step_spans.CACHE_PHASES)
