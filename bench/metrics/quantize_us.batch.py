"""quantize_us: mean ``quantize`` span, the eager-jax input quantize of
one batch including its copy back to the host (repro.obs spans, inside
``aggregate_pack``), before the device trace began."""
from harness.measure import spans


def read(ctx):
    d = spans(ctx.spans, "quantize", ctx.host_window)
    return float(d.mean()) if d.size else None
