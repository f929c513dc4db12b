"""events_per_s: feature rows labelled inside the window, divided by
the window's length (host clock)."""
from harness.measure import rows_per_s


def read(ctx):
    return rows_per_s(ctx.served, ctx.seconds)
