"""lut_eval_ns_per_lut: device time of one call of the streamed
``lut_eval`` kernel (device trace), in ns, over the LUTs that call
evaluates: the ``luts`` counter the streamed engine puts on its
``fetch`` spans (repro.obs). Nets of any size read on one scale, so it
tells per-call overhead (falls as the net grows) from per-LUT work
(stays flat). None where no ``fetch`` span carries the counter."""
from harness import spec


def luts(events):
    """The ``luts`` counter of the window's ``fetch`` spans, or None."""
    n = [e.args["luts"] for e in events or []
         if e.ph == "X" and e.name == "fetch" and e.args
         and "luts" in e.args]
    return max(n) if n else None


def read(ctx):
    n = luts(ctx.spans)
    if not n:
        return None
    dev_us = spec.reader("lut_eval_device_us")(ctx)
    if dev_us is None:
        return None
    return dev_us * 1e3 / n
