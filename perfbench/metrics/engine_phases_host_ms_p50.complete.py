"""Per engine step the host time in retire, admit, keys, accept and
observe (the program's phase spans); median over the window's steps."""
from perfbench.lib import step_spans


def read(ctx):
    return step_spans.phases_ms_p50(ctx, step_spans.ENGINE_PHASES)
