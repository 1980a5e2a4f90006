"""The paged decode-attention kernel's share of the memory roofline:
the logical K/V bytes of the tokens held, over the kernel's mean time a
call and the chip's bandwidth."""
from perfbench.lib import paged_decode


def read(ctx):
    return paged_decode.roofline(ctx)
