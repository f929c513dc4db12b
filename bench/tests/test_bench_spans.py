"""Readers of the program's pack, engine and gc spans, and the alignment
of program spans to the device trace's clock."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

from harness import align, profile, spec  # noqa: E402
from repro.obs import TraceEvent  # noqa: E402

CHIP_TRACE = os.path.join(BENCH, "tests", "data", "jsc-s-batch.xplane.pb")


def _x(name, ts, dur, tid=1):
    return TraceEvent("X", name, "pack", float(ts), float(dur), tid, None,
                      None)


class Ctx:
    def __init__(self, spans=None, trace=None, host_window=(0.0, 1000.0)):
        self.spans = spans
        self.trace = trace
        self.host_window = host_window


@pytest.mark.parametrize("metric,span", [
    ("quantize_us.batch", "quantize"), ("bitpack_us.batch", "bitpack"),
    ("h2d_us.batch", "h2d"), ("fetch_us.batch", "fetch")])
def test_span_mean_readers(metric, span):
    read = spec.reader(metric)
    spans = [_x(span, 10, 4.0), _x(span, 50, 8.0), _x("other", 60, 100.0),
             _x(span, 2000, 1e6)]        # began after the host window
    assert read(Ctx(spans)) == pytest.approx(6.0)
    assert read(Ctx([_x("aggregate_pack", 10, 4.0)])) is None
    assert read(Ctx(None)) is None


def test_gc_pause_share_is_the_union_over_threads():
    read = spec.reader("gc_pause_share.steady")
    spans = [_x("gc", 100, 50, tid=1), _x("gc", 120, 50, tid=2),
             _x("gc", 950, 100, tid=1),  # half inside the window
             _x("gc", 5000, 10, tid=3),  # after it
             _x("exec", 0, 900)]
    # [100, 170) and [950, 1000) of a 1000 us window
    assert read(Ctx(spans)) == pytest.approx(12.0)
    assert read(Ctx([_x("exec", 0, 900)])) is None
    assert read(Ctx(None)) is None


def _chip():
    return profile.reduce_xplane(CHIP_TRACE, devices=1)


def _shifted_packs(red, offset_us, lead_us=2.0):
    """aggregate_pack spans that enclose each bench.pack of ``red`` by
    ``lead_us`` on a host clock ``offset_us`` from the trace's."""
    return [_x("aggregate_pack", s * 1e-3 + offset_us - lead_us,
               (e - s) * 1e-3 + 2 * lead_us)
            for n, s, e in red.host if n == "bench.pack"]


# the capture starts this long before the trace's window does: the time
# jax.profiler.start_trace takes, several packs' worth
START_TRACE_US = 43_210.0


def _window(red, offset_us):
    """host_window whose end is the capture start, before the trace."""
    return (0.0, red.window[0] * 1e-3 + offset_us - START_TRACE_US)


def test_alignment_recovers_a_known_offset(monkeypatch):
    red = _chip()
    offset = 7_654_321.5
    spans = _shifted_packs(red, offset)
    assert len(spans) == 6
    # a second thread's spans and distractor packs do not move it
    spans += [_x("aggregate_pack", s.ts_us + 3_000.0, 500.0, tid=2)
              for s in spans[:3]]
    spans += [_x("scatter", s.ts_us + s.dur_us + 5, 30.0) for s in spans]
    assert align.pack_alignment(spans, red, _window(red, offset)) is None
    monkeypatch.setattr(align, "MIN_PAIRS", 4)
    al = align.pack_alignment(spans, red, _window(red, offset))
    assert al is not None and al.n_pairs == 6
    assert abs(al.offset_us - (offset - 2.0)) < 5.0
    assert al.residual_us < 5.0


def test_idle_unspanned_share_on_the_chip_trace(monkeypatch):
    monkeypatch.setattr(align, "MIN_PAIRS", 4)
    red = _chip()
    offset = 1_234_567.0
    packs = _shifted_packs(red, offset)
    hw = _window(red, offset)
    read = spec.reader("idle_unspanned_share.batch")
    only_packs = read(Ctx(packs, red, hw))
    assert 0.0 <= only_packs <= 100.0
    # the pack spans cover the idle time inside the bench.pack events
    w0, w1 = red.window
    edges = [w0] + [t for iv in red.devices[0].busy for t in iv] + [w1]
    idle = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    in_pack = sum(max(0, min(b, e) - max(a, s)) for a, b in idle
                  for n, s, e in red.host if n == "bench.pack")
    total = sum(b - a for a, b in idle)
    assert only_packs == pytest.approx(100.0 * (1 - in_pack / total),
                                       abs=0.1)
    # a span over the whole window leaves no idle time unspanned
    whole = _x("sched_wait", w0 * 1e-3 + offset - 10.0,
               (w1 - w0) * 1e-3 + 20.0, tid=9)
    assert read(Ctx(packs + [whole], red, hw)) == pytest.approx(0.0)
    assert spec.reader("idle_unspanned_share.steady")(
        Ctx(packs, red, hw)) == pytest.approx(only_packs)


def test_idle_unspanned_share_needs_trace_and_pack_spans(monkeypatch):
    monkeypatch.setattr(align, "MIN_PAIRS", 4)
    read = spec.reader("idle_unspanned_share.batch")
    red = _chip()
    assert read(Ctx(_shifted_packs(red, 0.0), red,
                    _window(red, 0.0))) is not None
    assert read(Ctx(None, red)) is None
    assert read(Ctx([_x("quantize", 0, 5)], red)) is None
    assert read(Ctx(_shifted_packs(red, 0.0), None)) is None
