#!/usr/bin/env python3
"""Benchmark of the served logic path on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` in this process: makes the
configuration's weights, compiles them to logic, synthesizes and warms
the served path (set-up), drives the cell's traffic through
``MicroBatchScheduler`` for ``--seconds``, and checks every served label
against the plain reference. The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics
read from ``repro.obs`` spans and a device trace), ``device`` and, last,
``checks`` (each compared number beside its limit; also the last lines
of standard error). Exits non-zero, printing no result, where JAX finds
no TPU or fewer chips than the cell needs.
"""
import os
import sys
import time

T_PROC0 = time.perf_counter()
BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

from harness.runner import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_proc0=T_PROC0))
