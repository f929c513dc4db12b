"""pack_us: mean ``aggregate_pack`` span, the host's quantize and
bitplane pack of one batch (repro.obs spans), before the device trace
began."""
from harness.measure import spans


def read(ctx):
    d = spans(ctx.spans, "aggregate_pack", ctx.host_window)
    return float(d.mean()) if d.size else None
