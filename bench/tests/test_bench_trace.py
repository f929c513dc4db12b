"""The device-trace reduction: busy/idle union and per-op sums."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

from harness import profile  # noqa: E402


def test_union_merges_overlapping_and_touching_intervals():
    got = profile.union([(5, 7), (0, 2), (1, 3), (3, 4), (10, 12), (6, 6)])
    assert got == [(0, 4), (5, 7), (10, 12)]
    assert profile.union([]) == []


def test_busy_idle_and_gap_labels_on_a_built_trace():
    ops = [("fusion", 100, 50), ("kernel", 120, 60), ("kernel", 400, 100)]
    dev = profile.DeviceTrace("/device:TPU:0", ops,
                              profile.union([(s, s + d) for _, s, d in ops]))
    red = profile.Reduced(window=(0, 1000), devices=[dev],
                          host=[("bench.exec", 0, 600),
                                ("bench.pack", 200, 380)])
    assert dev.busy == [(100, 180), (400, 500)]
    assert red.busy_ns(dev) == 180
    assert red.mean_busy_s() == pytest.approx(180e-9)
    assert red.op_seconds() == pytest.approx({"fusion": 50e-9,
                                              "kernel": 160e-9})
    gaps = red.idle_gaps()
    # gaps: [0,100) mid 50 -> exec; [180,400) mid 290 -> pack (innermost);
    # [500,1000) mid 750 -> no span
    assert gaps == pytest.approx({"bench.exec": 100e-9, "bench.pack": 220e-9,
                                  "no bench span": 500e-9})
    assert sum(gaps.values()) + red.mean_busy_s() == pytest.approx(1e-6)


CHIP_TRACE = os.path.join(BENCH, "tests", "data", "jsc-s-batch.xplane.pb")


def test_chip_trace_reduces_to_busy_idle_and_kernel_time():
    """A 60 ms trace of jsc-s.batch recorded on a TPU v5e."""
    red = profile.reduce_xplane(CHIP_TRACE, devices=1)
    assert [d.name for d in red.devices] == ["/device:TPU:0"]
    dev = red.devices[0]
    w0, w1 = red.window
    assert 0.05e9 < red.window_ns < 0.2e9
    # busy intervals are disjoint, sorted and inside the window
    assert all(a < b <= c < d for (a, b), (c, d) in zip(dev.busy, dev.busy[1:]))
    assert w0 <= dev.busy[0][0] and dev.busy[-1][1] <= w1
    busy = red.mean_busy_s()
    assert 0 < busy < red.window_ns * 1e-9
    # per-op sums add up to at least the busy time (ops may overlap)
    ops = red.op_seconds()
    assert sum(ops.values()) >= busy * (1 - 1e-9)
    assert any(n.startswith("%lut_eval_streamed_pallas") for n in ops)
    # idle gaps and busy time tile the window
    assert sum(red.idle_gaps().values()) + busy == pytest.approx(
        red.window_ns * 1e-9, rel=1e-9)
    assert {"bench.pack", "bench.device_exec"} <= {h[0] for h in red.host}


def test_kernel_readers_on_the_chip_trace():
    from harness import spec
    red = profile.reduce_xplane(CHIP_TRACE, devices=1)

    class Ctx:
        trace = red
        cfg = {"serve": {"sched": {"max_batch": 256}}}
        netlist = {"n_luts": 141, "k": 6, "n_pi_wires": 32, "n_out_wires": 15}
        peak = spec.peaks()["TPU v5 lite"]
        served = None
        spans = None

    dev_us = spec.reader("lut_eval_device_us")(Ctx())
    assert 1.0 < dev_us < 10_000.0
    share = spec.reader("lut_eval_roofline")(Ctx())
    assert 0.0 < share < 100.0
    idle = spec.reader("device_idle_share")(Ctx())
    assert 0.0 < idle < 100.0
