"""The flash prefill-attention kernel's share of the arithmetic roofline:
the true prompts' links (the causal triangle on full layers, the band
of 1,024 on windowed ones) at 32 query heads of 128, a call a layer,
over the kernel's mean time a call and the chip's bf16 peak."""
from perfbench.lib import moe


def read(ctx):
    return moe.prefill_attention_roofline(ctx)
