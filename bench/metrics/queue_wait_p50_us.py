"""queue_wait_p50_us: median of the scheduler's ``queue_wait`` async
spans (enqueue to batch formation, repro.obs), over the requests
enqueued before the device trace began."""
import numpy as np

from harness.measure import async_spans


def read(ctx):
    d = async_spans(ctx.spans, "queue_wait", ctx.host_window)
    return float(np.median(d)) if d.size else None
