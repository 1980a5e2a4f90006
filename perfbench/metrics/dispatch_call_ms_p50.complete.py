"""Median host time of one jitted call until it returns (the program's
`dispatch` phase span): the enqueue, not the device's work."""
from perfbench.lib import step_spans


def read(ctx):
    return step_spans.dispatch_ms_p50(ctx)
