"""Most pages of the K/V pool live at a step's close, of the pages
there are: max of the `step` spans' `pages_live` over the window."""
from perfbench.lib import dispatch_spans


def read(ctx):
    return dispatch_spans.pages_live_peak_share(ctx)
