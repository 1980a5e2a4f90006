"""Share of the captured window in which the host was taking a result
the device had finished: a `sync`'s `ready` to its end."""
from perfbench.lib import dispatch_spans


def read(ctx):
    return dispatch_spans.fetch_tail_share(ctx)
