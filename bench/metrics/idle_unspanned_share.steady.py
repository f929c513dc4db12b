"""idle_unspanned_share: share of the device's idle time in the traced
window that no program thread span covers (device trace, with the
repro.obs spans put on its clock by ``harness.align``), in %. The
alignment's offset, residual and pair count go to standard error."""
from harness.align import unspanned_idle_share
from harness.runner import log


def read(ctx):
    return unspanned_idle_share(ctx.spans, ctx.trace, ctx.host_window, log)
