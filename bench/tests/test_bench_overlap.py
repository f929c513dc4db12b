"""Readers of the scheduler's share of batches dispatched while another
was in flight."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

from harness import spec  # noqa: E402
from repro.obs import TraceEvent  # noqa: E402


def _exec(ts, inflight=None):
    args = {"rows": 256, "batch": 1}
    if inflight is not None:
        args["inflight"] = inflight
    return TraceEvent("X", "exec", "exec", float(ts), 5.0, 1, None, args)


class Ctx:
    def __init__(self, spans, host_window=(0.0, 1000.0)):
        self.spans = spans
        self.host_window = host_window


@pytest.mark.parametrize("metric", ["overlap_share.batch",
                                    "overlap_share.steady"])
def test_overlap_share_reads_the_inflight_arg(metric):
    read = spec.reader(metric)
    # spans without the arg (a program that keeps one batch in flight)
    assert read(Ctx([_exec(10), _exec(20)])) == 0.0
    mix = [_exec(10, 0), _exec(20, 1), _exec(30, 1), _exec(40),
           _exec(2000, 1),                  # began after the host window
           TraceEvent("X", "device_exec", "exec", 50.0, 5.0, 1, None,
                      {"batch": 1})]
    assert read(Ctx(mix)) == pytest.approx(50.0)
    assert read(Ctx([_exec(2000, 1)])) is None
    assert read(Ctx(None)) is None
