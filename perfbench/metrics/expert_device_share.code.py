"""The expert layer's grouped-matmul kernels' device time, of the traced
window."""
from perfbench.lib import moe


def read(ctx):
    return moe.device_share(ctx)
