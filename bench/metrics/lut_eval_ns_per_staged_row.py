"""lut_eval_ns_per_staged_row: device time of one call of the streamed
``lut_eval`` kernel (device trace), in ns, over the leaf rows that call
stages from HBM: the ``staged_rows`` counter the streamed engine puts
on its ``fetch`` spans (repro.obs). It is the unit cost of the
staged-DMA gather, the path of nets whose wire plane is too large for
VMEM. None where no ``fetch`` span carries the counter, or where the
counter is 0 (a plane resident in VMEM stages nothing)."""
from harness import spec


def staged_rows(events):
    """The ``staged_rows`` counter of the window's ``fetch`` spans, or
    None."""
    n = [e.args["staged_rows"] for e in events or []
         if e.ph == "X" and e.name == "fetch" and e.args
         and "staged_rows" in e.args]
    return max(n) if n else None


def read(ctx):
    n = staged_rows(ctx.spans)
    if not n:
        return None
    dev_us = spec.reader("lut_eval_device_us")(ctx)
    if dev_us is None:
        return None
    return dev_us * 1e3 / n
