"""The flash prefill-attention kernel's share of the arithmetic
roofline: the FLOPs of the true prompts' causal attention in one
dispatch, over the kernel's mean time a call and the chip's bf16 peak."""
from perfbench.lib import flash_prefill


def read(ctx):
    return flash_prefill.roofline(ctx)
