"""bitpack_us: mean ``bitpack`` span, the numpy scatter of one batch's
input codes into bitplane words (repro.obs spans, inside
``aggregate_pack``), before the device trace began."""
from harness.measure import spans


def read(ctx):
    d = spans(ctx.spans, "bitpack", ctx.host_window)
    return float(d.mean()) if d.size else None
