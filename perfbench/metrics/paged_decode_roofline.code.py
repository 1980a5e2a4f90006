"""The paged decode-attention kernel's share of the memory roofline: the
logical K/V bytes of a token-step (each lane's tokens on the 2 full
layers, at most 1,024 of them on the 6 windowed ones, 4 K/V heads of
128 in bf16), a call a layer, over the kernel's mean time a call."""
from perfbench.lib import moe


def read(ctx):
    return moe.paged_decode_roofline(ctx)
