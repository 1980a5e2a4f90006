"""The expert layer's grouped matmuls against their roofline: for each
dispatch fetched inside the capture, the larger of its expert rows'
FLOPs (2 x rows x 2,304 x 896 x 3) over the bf16 peak and the bytes of
the experts it hit (6,193,152 weights each, bf16) plus the rows'
activations in and out over the memory bandwidth; summed, over the
expert kernels' device time."""
from perfbench.lib import moe


def read(ctx):
    return moe.gmm_roofline(ctx)
