"""The decode program's gather of whole block tables and its
re-layout, share of the traced window."""
from perfbench.lib import readers


def read(ctx):
    return readers.decode_gather_share(ctx)
