"""The router's load: the busiest expert's rows over an expert's mean,
summed over layers, mean over the window's dispatches (the traced
`sync` phases' `expert_rows_peak` and `expert_rows`)."""
from perfbench.lib import moe


def read(ctx):
    return moe.load_peak_over_mean(ctx)
