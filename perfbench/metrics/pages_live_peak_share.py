"""Most pages of the K/V pool that were live at once, of the pages
there are."""


def read(ctx):
    b = ctx["bench"]
    return 100.0 * b["pages_live_peak"] / b["pages_total"]
