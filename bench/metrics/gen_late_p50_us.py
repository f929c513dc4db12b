"""gen_late_p50_us: median of how late the load generator called
submit() after each request was due (open loop, host clock), over the
requests due before the device trace began. It adds to every
request's latency, which is timed from the due time."""
import numpy as np


def read(ctx):
    if ctx.tr["loop"] != "open":
        return None
    s = ctx.served
    t0, t1 = ctx.host_window
    due = (s.due_us >= t0) & (s.due_us < t1)
    late = s.submit_us[due] - s.due_us[due]
    return float(np.median(late)) if late.size else None
