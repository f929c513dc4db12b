"""gc_pause_share: share of the window before the device trace in which
a garbage collection ran on some thread (the union of the program's
``gc`` spans, repro.obs), in %."""
from harness.profile import union


def read(ctx):
    gcs = [e for e in ctx.spans or [] if e.ph == "X" and e.name == "gc"]
    if not gcs:
        return None
    t0, t1 = ctx.host_window
    if t1 <= t0:
        return None
    iv = [(max(t0, e.ts_us), min(t1, e.ts_us + e.dur_us)) for e in gcs]
    inside = union([(a, b) for a, b in iv if b > a])
    return 100.0 * sum(b - a for a, b in inside) / (t1 - t0)
