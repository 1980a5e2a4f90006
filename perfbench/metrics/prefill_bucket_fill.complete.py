"""True prompt tokens over the tokens the prefill programs computed
(admit width x bucket), over the window's prefill dispatches."""
from perfbench.lib import engine_readers


def read(ctx):
    return engine_readers.bucket_fill(ctx)
