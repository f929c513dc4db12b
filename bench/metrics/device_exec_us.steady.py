"""device_exec_us: mean ``device_exec`` span, the host call that ships a
packed batch to the engine and waits for its labels (repro.obs spans),
before the device trace began."""
from harness.measure import spans


def read(ctx):
    d = spans(ctx.spans, "device_exec", ctx.host_window)
    return float(d.mean()) if d.size else None
