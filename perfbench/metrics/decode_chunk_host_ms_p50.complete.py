"""Median host time of one decode dispatch (the program's span)."""
from perfbench.lib import readers


def read(ctx):
    return readers.span_ms_p50(ctx, "decode")
