#!/usr/bin/env python3
"""Find the highest open-loop rate a configuration sustains (the knee).

    python3 bench/sweep.py --workload jsc-s.steady --seed 7 --seconds 5 \
        --rates 5000,10000,20000,40000

One set-up of the cell's served path, then the cell's open-loop traffic
at each rate in turn (1-row requests, Poisson arrivals). A rate is
sustained when the backlog does not grow: the median latency of the
window's last quarter of requests is within 1.5x that of its first
quarter, and every request is answered. The cell's traffic file then
offers 0.8x the highest sustained rate. Prints one line per rate and a
JSON summary as the last line.
"""
import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import numpy as np  # noqa: E402

from harness import device, runner, spec  # noqa: E402
from harness.runner import log  # noqa: E402

GROWTH = 1.5


def sustained(s) -> dict:
    """Whether the window's backlog stayed flat, with what shows it."""
    ok = s.answered
    lat = s.done_us - s.due_us
    q = max(1, s.n // 4)
    first = float(np.nanmedian(lat[:q]))
    last = float(np.nanmedian(lat[-q:]))
    done_in = int((ok & (s.done_us <= s.t1_us)).sum())
    seconds = (s.t1_us - s.t0_us) * 1e-6
    return {"requests": s.n, "unanswered": int(s.n - ok.sum()),
            "completed_per_s": done_in / seconds,
            "p50_us": float(np.nanpercentile(lat, 50)),
            "p99_us": float(np.nanpercentile(lat, 99)),
            "first_quarter_p50_us": first, "last_quarter_p50_us": last,
            "gen_late_p99_us": float(np.percentile(s.submit_us - s.due_us,
                                                   99)),
            "sustained": bool(ok.all() and last <= GROWTH * first)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, requests/s")
    args = ap.parse_args(argv)
    w = spec.cell(spec.load_benchmark(), args.workload)
    if w["tr"]["loop"] != "open":
        log(f"{args.workload} is not an open-loop cell")
        return 2
    os.environ["REPRO_AUTOTUNE_CACHE"] = ""
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    try:
        device.require(int(w["chips"]), log)
    except device.NoChip as e:
        log(str(e))
        return 3
    built = runner.build(w["cfg"])
    rows = []
    for rate in [float(r) for r in args.rates.split(",")]:
        win = runner.serve_window(built, w["cfg"],
                                  dict(w["tr"], rate_per_s=rate),
                                  args.seed, args.seconds)
        r = dict(rate_per_s=rate, **sustained(win.served))
        log(json.dumps(r))
        rows.append(r)
    held = [r["rate_per_s"] for r in rows if r["sustained"]]
    print(json.dumps({"workload": args.workload, "rates": rows,
                      "highest_sustained_per_s": max(held, default=None)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
