"""fetch_us: mean ``fetch`` span, the jitted call of one batch (dispatch,
kernel, argmax) and the copy of its labels back to the host (repro.obs
spans, inside ``device_exec``), before the device trace began."""
from harness.measure import spans


def read(ctx):
    d = spans(ctx.spans, "fetch", ctx.host_window)
    return float(d.mean()) if d.size else None
