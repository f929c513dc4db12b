"""repro.obs: request-lifecycle tracing under a fake clock (span
presence/nesting, flush reasons, shed/reject terminal events, disabled-
tracer zero-footprint), ring-buffer overflow accounting, export
round-trips, the metrics registry, LatencyHistogram edge cases, the
trace-schema validation pass, kernel latency-table estimation, and
EWMA seeding from calibrated estimates."""
import json

import numpy as np
import pytest

from repro.check.tracecheck import (check_trace, check_trace_file,
                                    synthetic_trace_events)
from repro.obs import (FLUSH_REASONS, MetricsRegistry,
                       NULL_TRACER, SpanTracer, TraceEvent,
                       load_trace_events, to_chrome_trace, to_jsonl,
                       write_chrome_trace, write_jsonl)
from repro.serve import (FakeClock, MicroBatchScheduler, ReplicaSet,
                         RequestRejected, SchedConfig)
from repro.serve.metrics import LatencyHistogram, ServeMetrics


def _traced_sched(cfg=None, capacity=4096):
    clk = FakeClock()
    tracer = SpanTracer(clock=clk, capacity=capacity)
    s = MicroBatchScheduler(
        lambda x: x.sum(axis=-1),
        cfg or SchedConfig(max_batch=4, max_wait_us=200.0),
        clock=clk, tracer=tracer)
    return clk, tracer, s


def _by(events, ph=None, name=None, cat=None):
    return [e for e in events
            if (ph is None or e.ph == ph)
            and (name is None or e.name == name)
            and (cat is None or e.cat == cat)]


# ---------------------------------------------------------------------------
# Request lifecycle under FakeClock
# ---------------------------------------------------------------------------

def test_full_lifecycle_spans_size_flush():
    clk, tracer, s = _traced_sched()
    futs = [s.submit(np.full((1, 3), i, np.float32)) for i in range(4)]
    assert s.poll() == 4
    evs = tracer.events()
    form = _by(evs, ph="X", name="batch_form")[0]

    # every request opened + closed both async spans, outcome ok
    ids = {f.trace_id for f in futs}
    assert len(ids) == 4 and 0 not in ids
    for f in futs:
        begins = [e for e in _by(evs, ph="b") if e.scope_id == f.trace_id]
        ends = [e for e in _by(evs, ph="e") if e.scope_id == f.trace_id]
        assert [e.name for e in begins] == ["request", "queue_wait"]
        assert [e.name for e in ends] == ["queue_wait", "request"]
        qw, req = ends
        # the dispatch-path queue_wait end carries only its batch's id
        # (the flush reason lives on the batch_form span; wait is ts
        # delta)
        assert qw.args == {"batch": form.args["batch"]}
        assert req.args["outcome"] == "ok"
        assert req.args["latency_us"] >= 0.0

    # the scheduler thread recorded its X spans with the right cats
    assert _by(evs, ph="X", name="batch_form", cat="batch")
    assert _by(evs, ph="X", name="exec", cat="exec")
    assert _by(evs, ph="X", name="scatter", cat="sched")
    assert form.args["flush_reason"] == "size" and form.args["rows"] == 4


def test_max_wait_flush_reason_and_wait_time():
    clk, tracer, s = _traced_sched()
    f = s.submit(np.ones((2, 3), np.float32))
    assert s.poll() == 0
    clk.advance_us(200.0)
    assert s.poll() == 1
    f.result(0)
    evs = tracer.events()
    (qw,) = _by(evs, ph="e", name="queue_wait")
    (qb,) = [e for e in _by(evs, ph="b", name="queue_wait")]
    assert qw.ts_us - qb.ts_us == 200.0     # wait == end-begin ts delta
    form = _by(evs, ph="X", name="batch_form")[0]
    assert form.args["flush_reason"] == "max_wait"


def test_shed_and_reject_terminal_events():
    clk, tracer, s = _traced_sched(
        SchedConfig(max_batch=4, n_priorities=1, lane_slo_us=(100.0,)))
    f = s.submit(np.ones((1, 3), np.float32))
    clk.advance_us(500.0)                # expire past the lane SLO
    s.drain()
    with pytest.raises(RequestRejected):
        f.result(0)
    evs = tracer.events()
    (qw,) = _by(evs, ph="e", name="queue_wait")
    (req,) = _by(evs, ph="e", name="request")
    assert qw.args["flush_reason"] == "shed"
    assert req.args["outcome"] == "shed" and req.args["lane"] == 0

    # admission reject: an instant only, never an async begin
    with pytest.raises(RequestRejected):
        s.submit(np.ones((99, 3), np.float32))
    rej = _by(tracer.events(), ph="i", name="reject")
    assert len(rej) == 1 and rej[0].cat == "admission"
    assert rej[0].args["reason"] == "too_large"
    # no new async span was opened for the rejected submission
    assert {e.scope_id for e in _by(tracer.events(), ph="b")} == \
        {f.trace_id}


def test_drain_on_stop_closes_spans_as_shutdown():
    clk, tracer, s = _traced_sched()
    f = s.submit(np.ones((1, 3), np.float32))
    s.stop(drain=False)
    with pytest.raises(RequestRejected):
        f.result(0)
    (req,) = _by(tracer.events(), ph="e", name="request")
    assert req.args["outcome"] == "shutdown"


def test_disabled_tracer_records_nothing():
    clk = FakeClock()
    tracer = SpanTracer(clock=clk, enabled=False)
    s = MicroBatchScheduler(lambda x: x.sum(axis=-1),
                            SchedConfig(max_batch=2), clock=clk,
                            tracer=tracer)
    futs = [s.submit(np.ones((1, 3), np.float32)) for _ in range(2)]
    s.poll()
    assert all(f.result(0) == 3.0 for f in futs)
    assert tracer.events() == [] and tracer.n_recorded == 0
    assert futs[0].trace_id is None      # ids not even allocated
    # the default NULL_TRACER has the same surface and also stays empty
    assert NULL_TRACER.events() == [] and not NULL_TRACER.enabled


def test_ring_buffer_overflow_keeps_latest():
    tracer = SpanTracer(clock=FakeClock(), capacity=4)
    for i in range(10):
        tracer.instant(f"ev{i}")
    assert tracer.n_recorded == 10 and tracer.n_dropped == 6
    assert [e.name for e in tracer.events()] == ["ev6", "ev7", "ev8", "ev9"]
    tracer.clear()
    assert tracer.events() == [] and tracer.n_recorded == 0


# ---------------------------------------------------------------------------
# Export round-trips
# ---------------------------------------------------------------------------

def _sample_events():
    clk = FakeClock()
    t = SpanTracer(clock=clk)
    rid = t.new_id()
    t.abegin("request", rid, args={"lane": 0})
    clk.advance_us(5.0)
    with t.span("exec", cat="exec", args={"rows": 2}):
        clk.advance_us(10.0)
    t.aend("request", rid, args={"outcome": "ok"})
    return t


def test_chrome_trace_shape_and_roundtrip(tmp_path):
    t = _sample_events()
    doc = to_chrome_trace(t, other_data={"k": 1})
    assert doc["traceEvents"][0]["ph"] == "M"       # process_name meta
    assert doc["otherData"] == {"k": 1}
    xs = [r for r in doc["traceEvents"] if r["ph"] == "X"]
    assert xs[0]["dur"] == 10.0 and xs[0]["ts"] == 5.0
    asyncs = [r for r in doc["traceEvents"] if r["ph"] in "be"]
    assert all(isinstance(r["id"], str) for r in asyncs)

    path = str(tmp_path / "trace.json")
    write_chrome_trace(path, t, other_data={"k": 1})
    back = load_trace_events(path)
    orig = t.events()
    assert len(back) == len(orig)        # M dropped on load
    for a, b in zip(orig, back):
        assert (a.ph, a.name, a.cat, a.ts_us, a.dur_us, a.scope_id) == \
               (b.ph, b.name, b.cat, b.ts_us, b.dur_us, b.scope_id)


def test_jsonl_roundtrip(tmp_path):
    t = _sample_events()
    path = str(tmp_path / "trace.jsonl")
    write_jsonl(path, t)
    assert len(to_jsonl(t).splitlines()) == len(t.events())
    back = load_trace_events(path)
    for a, b in zip(t.events(), back):
        assert a.ph == b.ph and a.ts_us == b.ts_us and a.args == b.args


# ---------------------------------------------------------------------------
# Metrics registry + histogram edge cases
# ---------------------------------------------------------------------------

def test_registry_snapshot_all_instrument_kinds():
    reg = MetricsRegistry()
    reg.counter("sched.completed").inc(3)
    assert reg.counter("sched.completed") is reg.counter("sched.completed")
    reg.gauge("depth").set(7.0)
    reg.gauge("live", fn=lambda: 42.0)
    h = reg.histogram("lat")
    for v in (10.0, 20.0, 30.0):
        h.record(v)
    reg.register("comp", lambda: {"a": 1})
    snap = reg.snapshot()
    assert snap["counters"] == {"sched.completed": 3}
    assert snap["gauges"] == {"depth": 7.0, "live": 42.0}
    assert snap["histograms"]["lat"]["n"] == 3
    assert snap["histograms"]["lat"]["mean_us"] == 20.0
    assert snap["comp"] == {"a": 1}


def test_serve_metrics_publish_into_registry():
    m = ServeMetrics(FakeClock())
    reg = MetricsRegistry()
    m.publish(reg, "serve")
    snap = reg.snapshot()
    assert "serve" in snap and snap["serve"]["completed"] == 0


def test_histogram_empty_and_percentile_clamp():
    h = LatencyHistogram()
    assert h.percentile(50) == 0.0 and h.mean() == 0.0   # empty
    for v in (1.0, 2.0, 3.0):
        h.record(v)
    assert h.percentile(-10) == 1.0      # clamped to p0 = min
    assert h.percentile(250) == 3.0      # clamped to p100 = max
    assert h.mean() == 2.0


def test_histogram_counts_only_mode():
    h = LatencyHistogram(max_samples=0)
    for v in (5.0, 15.0):
        h.record(v)                      # must not divide by zero
    assert h.n == 2 and h.samples == []
    assert h.percentile(99) == 0.0       # no reservoir -> 0.0
    assert h.mean() == 10.0              # counts/total still tracked
    assert LatencyHistogram(max_samples=-3).max_samples == 0


# ---------------------------------------------------------------------------
# Trace-schema validation pass
# ---------------------------------------------------------------------------

def _ev(ph, name, ts, dur=0.0, tid=1, sid=None, args=None, cat="request"):
    return TraceEvent(ph, name, cat, ts, dur, tid, sid, args)


def test_tracecheck_clean_on_live_scheduler_trace():
    events, n_dropped = synthetic_trace_events()
    rep = check_trace(events, n_dropped=n_dropped)
    assert rep.ok, rep.format()
    assert rep.checked > 0
    reasons = {e.args["flush_reason"] for e in events
               if e.args and "flush_reason" in e.args}
    assert reasons >= {"size", "max_wait", "shed"}
    assert reasons <= set(FLUSH_REASONS)


def test_tracecheck_rejects_violations():
    def errs(evs, **kw):
        return {i.code for i in check_trace(evs, **kw).errors}

    assert "orphan-end" in errs(
        [_ev("e", "request", 1.0, sid=1, args={"outcome": "ok"})])
    assert "unterminated-span" in errs([_ev("b", "request", 1.0, sid=1)])
    assert "bad-flush-reason" in errs(
        [_ev("i", "x", 1.0, args={"flush_reason": "vibes"})])
    assert "negative-dur" in errs([_ev("X", "exec", 5.0, dur=-1.0)])
    assert "bad-phase" in errs([_ev("Z", "x", 1.0)])
    assert "bad-outcome" in errs(
        [_ev("b", "request", 0.0, sid=1),
         _ev("e", "request", 1.0, sid=1, args={"outcome": "maybe"})])
    assert "time-regression" in errs(
        [_ev("b", "request", 5.0, sid=1),
         _ev("e", "request", 1.0, sid=1, args={"outcome": "ok"})])
    assert "end-mismatch" in errs(
        [_ev("b", "request", 0.0, sid=1),
         _ev("b", "queue_wait", 1.0, sid=1),
         _ev("e", "request", 2.0, sid=1, args={"outcome": "ok"})])
    # partially-overlapping same-thread X spans cannot come from
    # lexical `with` nesting
    assert "span-overlap" in errs(
        [_ev("X", "a", 0.0, dur=10.0), _ev("X", "b", 5.0, dur=10.0)])
    # disjoint + properly nested spans are fine
    assert not errs([_ev("X", "a", 0.0, dur=10.0),
                     _ev("X", "inner", 2.0, dur=3.0),
                     _ev("X", "later", 20.0, dur=5.0)])


def test_tracecheck_truncated_buffer_downgrades_to_warnings():
    evs = [_ev("e", "request", 1.0, sid=7, args={"outcome": "ok"})]
    rep = check_trace(evs, n_dropped=3)
    assert rep.ok                        # warnings, not errors
    assert any(i.code == "orphan-end" for i in rep.warnings)


def test_tracecheck_file_roundtrip(tmp_path):
    events, _ = synthetic_trace_events()
    path = str(tmp_path / "t.json")
    write_chrome_trace(path, events)
    rep = check_trace_file(path)
    assert rep.ok, rep.format()
    bad = str(tmp_path / "bad.json")
    with open(bad, "w") as f:
        json.dump({"traceEvents": [
            {"ph": "e", "name": "request", "cat": "request", "ts": 1.0,
             "id": "1", "args": {"outcome": "ok"}}]}, f)
    assert not check_trace_file(bad).ok


# ---------------------------------------------------------------------------
# Estimate seeding of the execution EWMAs
# ---------------------------------------------------------------------------

def test_sched_ewma_seeded_from_estimate():
    clk = FakeClock()

    def ex(x):
        clk.advance_us(100.0)
        return x.sum(axis=-1)

    s = MicroBatchScheduler(ex, SchedConfig(max_batch=1,
                                            exec_estimate_us=500.0),
                            clock=clk)
    assert s._exec_ewma_us == 500.0 and s._ewma_seeded
    s.submit(np.ones((1, 3), np.float32))
    s.poll()
    # first measurement blends into the seed instead of replacing it
    assert s._exec_ewma_us == pytest.approx(0.8 * 500.0 + 0.2 * 100.0)


def test_sched_ewma_unseeded_first_sample_wins():
    clk = FakeClock()

    def ex(x):
        clk.advance_us(100.0)
        return x.sum(axis=-1)

    s = MicroBatchScheduler(ex, SchedConfig(max_batch=1), clock=clk)
    assert not s._ewma_seeded
    s.submit(np.ones((1, 3), np.float32))
    s.poll()
    assert s._exec_ewma_us == pytest.approx(100.0)


def test_replicaset_exec_seed():
    clk = FakeClock()

    def ex(x):
        clk.advance_us(40.0)
        return x.sum(axis=-1)

    rs = ReplicaSet([ex], policy="rr", clock=clk, exec_seed_us=300.0)
    st = rs.stats()[0]
    assert st["ewma_us"] == 300.0 and st["ewma_seeded"]
    rs(np.ones((1, 3), np.float32))
    assert rs.stats()[0]["ewma_us"] == pytest.approx(
        0.8 * 300.0 + 0.2 * 40.0)
    # unseeded: first real sample overwrites the zero cold-start
    rs2 = ReplicaSet([ex], policy="rr", clock=clk)
    rs2(np.ones((1, 3), np.float32))
    assert rs2.stats()[0]["ewma_us"] == pytest.approx(40.0)
    assert not rs2.stats()[0]["ewma_seeded"]
