"""overlap_share: share of the scheduler's ``exec`` spans dispatched
while another batch was still in flight, ``args.inflight`` of 1 or more
(repro.obs spans), before the device trace began, in %. An ``exec``
span without the arg counts as dispatched alone."""


def read(ctx):
    t0, t1 = ctx.host_window
    n = overlapped = 0
    for e in ctx.spans or []:
        if e.ph == "X" and e.name == "exec" and t0 <= e.ts_us < t1:
            n += 1
            overlapped += (e.args or {}).get("inflight", 0) >= 1
    return 100.0 * overlapped / n if n else None
