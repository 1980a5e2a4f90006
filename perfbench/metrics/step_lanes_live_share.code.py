"""Requests a decode dispatch carried, of the slots there are: mean of
the `step` spans' `lanes` over the window's steps that decoded."""
from perfbench.lib import dispatch_spans


def read(ctx):
    return dispatch_spans.lanes_live_share(ctx)
