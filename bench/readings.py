#!/usr/bin/env python3
"""Readings that the ``correct`` limits are set from.

    python3 bench/readings.py --workload jsc-m.batch --seconds 5 \
        --seeds 101,102,103,104,105,106,107,108,109,110,111,112

One set-up of the cell, then for each seed a window of the cell's own
traffic through the timed path. Each window is read twice against the
float64 reference: once with the labels the program served (the lower
reading) and once with the control in the program's place, the same
reference computed in bfloat16, the precision below the float32 the
configuration states (the upper reading). Prints one JSON line per seed
and a summary as the last line.
"""
import argparse
import dataclasses
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

from harness import check, device, runner, spec, traffic  # noqa: E402
from harness.runner import log  # noqa: E402


def control_served(served, ctrl_labels):
    """The window's requests, answered by the control instead."""
    return dataclasses.replace(served,
                               labels=ctrl_labels[served.pool_rows()])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    w = spec.cell(spec.load_benchmark(), args.workload)
    os.environ["REPRO_AUTOTUNE_CACHE"] = ""
    import ml_dtypes

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    try:
        device.require(int(w["chips"]), log)
    except device.NoChip as e:
        log(str(e))
        return 3
    cfg, tr = w["cfg"], w["tr"]
    ref_mod = spec.reference(cfg)
    built = runner.build(cfg)
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        served = runner.serve_window(built, cfg, tr, seed,
                                     args.seconds).served
        pool = traffic.make_pool(tr, seed)
        ref = ref_mod.labels(cfg, built.weights, pool)
        ctrl = ref_mod.labels(cfg, built.weights, pool, ml_dtypes.bfloat16)
        prog_r = check.compare(served, ref)
        ctrl_r = check.compare(control_served(served, ctrl), ref)
        row = {"seed": seed, "program": prog_r, "control": ctrl_r,
               "program_correct": check.verdict(prog_r),
               "control_correct": check.verdict(ctrl_r)}
        log(json.dumps(row))
        rows.append(row)
    key = "wrong_labels"
    print(json.dumps({
        "workload": args.workload, "seeds": len(rows),
        "lower_reading": max(r["program"][key] for r in rows),
        "upper_reading": min(r["control"][key] for r in rows),
        "program_all_correct": all(r["program_correct"] for r in rows),
        "control_all_incorrect": not any(r["control_correct"]
                                         for r in rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
