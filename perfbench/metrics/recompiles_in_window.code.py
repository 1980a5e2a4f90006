"""Executables the engine counts at the window's last step less those at
the last step before it opened (the step span's `executables`)."""
from perfbench.lib import step_spans


def read(ctx):
    return step_spans.recompiles(ctx)
