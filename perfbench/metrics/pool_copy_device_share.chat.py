"""Copies of a whole K/V page pool, share of the traced window."""
from perfbench.lib import readers


def read(ctx):
    return readers.pool_copy_share(ctx)
