"""Work counts the benchmark's per-layer metrics divide by."""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

from harness import spec  # noqa: E402


def _metric(name):
    return spec.load_module(os.path.join(BENCH, "metrics", name + ".py"),
                            "test_metric_" + name.replace(".", "_"))


@pytest.mark.parametrize("path,flops", [
    # 2 x (64 + 32 + 32 + 32 + 5) neurons x fan-in 3
    (("configs", "jsc-s.json"), 990),
    # 2 x (16 + 8 + 5) neurons x fan-in 3
    (("tests", "data", "tiny.json"), 174)], ids=["jsc-s", "tiny"])
def test_flops_per_event(path, flops):
    with open(os.path.join(BENCH, *path)) as f:
        cfg = json.load(f)
    assert _metric("serve_mfu").flops_per_event(cfg) == flops


def test_bytes_per_call_hand_built_netlist():
    # 3 LUTs of k=6: each 64 INIT bits (8 bytes) + 6 leaf indices (24 bytes);
    # 10 PI wires and 4 output wires, 256 rows = 8 words of 4 bytes each
    net = {"n_luts": 3, "k": 6, "n_pi_wires": 10, "n_out_wires": 4,
           "depth": 2}
    got = _metric("lut_eval_roofline").bytes_per_call(net, 256)
    assert got == 3 * (8 + 24) + (10 + 4) * 8 * 4 == 544
    # a partial word still moves a whole word per wire
    assert _metric("lut_eval_roofline").bytes_per_call(net, 33) == (
        3 * 32 + (10 + 4) * 2 * 4)


def test_roofline_reads_none_without_kernel_events():
    class Ctx:
        trace = None
    assert _metric("lut_eval_roofline").read(Ctx()) is None
    assert _metric("lut_eval_device_us").read(Ctx()) is None


def test_span_readers_keep_to_the_host_window():
    from harness.measure import async_spans, spans
    from repro.obs.trace import TraceEvent

    def ev(ph, name, ts, dur=0.0, scope=0):
        return TraceEvent(ph=ph, name=name, cat="", ts_us=ts, dur_us=dur,
                          tid=0, scope_id=scope, args=None)
    events = [ev("X", "aggregate_pack", 10.0, 5.0),
              ev("X", "aggregate_pack", 50.0, 9.0),
              ev("X", "aggregate_pack", 120.0, 100.0),   # after the window
              ev("b", "queue_wait", 20.0, scope=1),
              ev("e", "queue_wait", 30.0, scope=1),
              ev("b", "queue_wait", 99.0, scope=2),
              ev("e", "queue_wait", 140.0, scope=2),
              ev("b", "queue_wait", 110.0, scope=3),     # began after it
              ev("e", "queue_wait", 111.0, scope=3)]
    assert list(spans(events, "aggregate_pack", (0.0, 100.0))) == [5.0, 9.0]
    assert list(spans(events, "aggregate_pack")) == [5.0, 9.0, 100.0]
    assert list(async_spans(events, "queue_wait", (0.0, 100.0))) == [10.0,
                                                                     41.0]
    assert spans(None, "aggregate_pack").size == 0
