"""State rows held by requests, of the rows there are: mean over the
window's engine steps (the step span's `state_rows_live`)."""
from perfbench.lib import retention


def read(ctx):
    return retention.rows_live_share(ctx)
