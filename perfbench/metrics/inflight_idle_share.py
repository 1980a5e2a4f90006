"""Share of the captured window in which a program was being enqueued or
was running (a `dispatch` start to its `sync`'s `ready`) and the device
ran no op."""
from perfbench.lib import dispatch_spans


def read(ctx):
    return dispatch_spans.inflight_idle_share(ctx)
