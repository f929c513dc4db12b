"""h2d_us: mean ``h2d`` span, the host's ``device_put`` of one batch's
packed words (repro.obs spans, inside ``device_exec``), before the
device trace began."""
from harness.measure import spans


def read(ctx):
    d = spans(ctx.spans, "h2d", ctx.host_window)
    return float(d.mean()) if d.size else None
