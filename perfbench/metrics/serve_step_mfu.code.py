"""Model FLOPs of the tokens prefilled and decoded in the window (forward,
from the configuration's shapes: 8 of 64 experts a token, attention over
the keys a query sees) over its time and the chip's peak."""
from perfbench.lib import readers


def read(ctx):
    return readers.serve_mfu(ctx)
