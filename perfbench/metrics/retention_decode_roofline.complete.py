"""The retention decode kernel's share of the memory roofline: the state
of the dispatch's live slots, read once, over the kernel's mean time a
call and the chip's bandwidth."""
from perfbench.lib import retention


def read(ctx):
    return retention.roofline(ctx)
