"""sched_us_per_batch: the scheduler's own time per batch, the
``batch_form`` plus ``scatter`` span time over the number of batches
(repro.obs spans), before the device trace began."""
from harness.measure import spans


def read(ctx):
    form = spans(ctx.spans, "batch_form", ctx.host_window)
    scatter = spans(ctx.spans, "scatter", ctx.host_window)
    if not form.size:
        return None
    return float((form.sum() + scatter.sum()) / form.size)
