"""device_idle_share: share of the traced window in which no operation
ran on the device, mean over the cell's chips (device trace), in %."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.devices or t.window_ns <= 0:
        return None
    return 100.0 * (1.0 - t.mean_busy_s() / (t.window_ns * 1e-9))
