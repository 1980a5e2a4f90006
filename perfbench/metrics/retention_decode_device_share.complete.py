"""The retention decode kernel's device time, of the traced window."""
from perfbench.lib import retention


def read(ctx):
    return retention.device_share(ctx)
