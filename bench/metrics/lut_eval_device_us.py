"""lut_eval_device_us: device time of one call of the streamed
``lut_eval`` kernel: the summed durations of its events in the device
trace over their number."""

# the name the kernel's events carry in the trace: the jitted wrapper
# of the Pallas call in kernels/lut_eval/lut_eval.py names the HLO
# custom call (``%lut_eval_streamed_pallas.1``)
KERNEL = "lut_eval_streamed_pallas"


def kernel_events(trace):
    return [d for dev in trace.devices for n, _, d in dev.ops
            if KERNEL in n]


def read(ctx):
    if ctx.trace is None:
        return None
    ev = kernel_events(ctx.trace)
    if not ev:
        return None
    return sum(ev) / len(ev) * 1e-3
