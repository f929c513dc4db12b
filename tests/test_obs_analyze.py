"""Online telemetry: trace analytics (per-phase attribution +
reconciliation), streaming windowed metrics, the SLO burn-rate monitor
and its scheduler degradation hook, latency-table hardening, the Prometheus pull endpoint, and the
perf-trajectory ledger."""
import json
import urllib.request

import numpy as np
import pytest

from repro.check.tracecheck import (check_phase_reconciliation,
                                    synthetic_trace_events)
from repro.obs import (BucketRing, BurnRateMonitor, MetricsRegistry,
                       MetricsServer, SpanTracer,
                       TraceEvent, WindowedMetrics, analyze_events,
                       analyze_trace, to_prometheus_text,
                       write_chrome_trace)
from repro.obs.analyze import (diff_reports, format_diff, format_report,
                               main as analyze_main)
from repro.serve import (FakeClock, MicroBatchScheduler, RejectReason,
                         RequestRejected, SchedConfig)


def _ev(ph, name, ts, dur=0.0, tid=1, sid=None, args=None, cat="request"):
    return TraceEvent(ph, name, cat, ts, dur, tid, sid, args)


def _traced_run(exec_us=100.0, n=8, gap_us=10.0):
    """FakeClock scheduler run: n requests in size-4 batches, every
    timestamp deterministic, so phase sums reconcile exactly."""
    clk = FakeClock()
    tracer = SpanTracer(clock=clk, capacity=8192)

    def ex(x):
        clk.advance_us(exec_us)
        return x.sum(axis=-1)

    s = MicroBatchScheduler(ex, SchedConfig(max_batch=4,
                                            max_wait_us=500.0),
                            clock=clk, tracer=tracer)
    futs = []
    for i in range(n):
        futs.append(s.submit(np.full((1, 3), i, np.float32)))
        clk.advance_us(gap_us)
        s.poll()
    s.poll(force=True)
    for f in futs:
        f.result(0)
    return clk, tracer, s


# ---------------------------------------------------------------------------
# Trace analytics: reconciliation + phase attribution
# ---------------------------------------------------------------------------

def test_analyze_reconciles_fakeclock_trace_exactly():
    _, tracer, _ = _traced_run()
    rpt = analyze_events(tracer.events())
    rec = rpt.reconciliation()
    assert rec["n_checked"] == 8
    assert rec["ok"] and rec["max_rel_err"] == 0.0
    # every ok request got full per-phase attribution and its phases
    # (minus post-completion scatter) sum to its measured latency
    for r in rpt.requests:
        ph = r.phases_us()
        assert ph is not None and r.outcome == "ok"
        attributed = sum(v for p, v in ph.items() if p != "scatter")
        assert attributed == pytest.approx(r.latency_us)
    summary = rpt.phase_summary()
    assert summary["dispatch"]["mean_us"] == pytest.approx(100.0)
    text = format_report(rpt)
    assert "where did the time go" in text and "reconciliation" in text


def test_analyze_cli_roundtrip(tmp_path, capsys):
    _, tracer, _ = _traced_run()
    path = str(tmp_path / "t.json")
    write_chrome_trace(path, tracer)
    assert analyze_main(["--trace", path]) == 0
    capsys.readouterr()
    assert analyze_main(["--trace", path, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["reconciliation"]["ok"] and doc["n_requests"] == 8
    assert analyze_trace(path).reconciliation()["ok"]


def test_analyze_trace_diff_attributes_regression():
    _, t_fast, _ = _traced_run(exec_us=100.0)
    _, t_slow, _ = _traced_run(exec_us=300.0)
    d = diff_reports(analyze_events(t_slow.events()),
                     analyze_events(t_fast.events()))
    # the executor got 3x slower and nothing else moved: the diff must
    # pin the regression on the executor-time phase
    assert d["attribution"] == "dispatch"
    assert d["phases"]["dispatch"]["direction"] == "regressed"
    assert d["phases"]["dispatch"]["delta_us"] == pytest.approx(200.0)
    assert "dispatch" in format_diff(d)


def test_analyze_truncated_trace_reports_not_crashes():
    # ring-buffer truncation: ends whose begins were dropped
    evs = [
        _ev("e", "queue_wait", 50.0, sid=1,
            args={"flush_reason": "size", "wait_us": 50.0}),
        _ev("X", "batch_form", 50.0, dur=0.0, cat="batch",
            args={"flush_reason": "size", "rows": 1, "n_requests": 1}),
        _ev("X", "exec", 50.0, dur=100.0, cat="exec"),
        _ev("e", "request", 150.0, sid=1,
            args={"outcome": "ok", "latency_us": 150.0}),
        _ev("e", "request", 160.0, sid=2, args={"outcome": "shed"}),
    ]
    rpt = analyze_events(evs)
    assert rpt.counts["orphan_ends"] >= 1
    truncated = [r for r in rpt.requests if r.truncated]
    assert truncated
    # truncated lifecycles are excluded from reconciliation, never
    # counted as failures
    assert rpt.reconciliation()["ok"]
    format_report(rpt)                   # must render


def test_analyze_zero_request_trace():
    rpt = analyze_events([])
    assert rpt.requests == [] and rpt.batches == []
    rec = rpt.reconciliation()
    assert rec["ok"] and rec["n_checked"] == 0
    assert "no completed requests" in format_report(rpt)


def test_analyze_shed_heavy_trace():
    # the synthetic check fixture covers every lifecycle edge: size and
    # max-wait flushes, expiry shed, admission reject, shutdown drain
    events, _ = synthetic_trace_events()
    rpt = analyze_events(events)
    d = rpt.to_dict()
    assert d["outcomes"].get("shed", 0) >= 1
    assert d["counts"]["rejects"] >= 1
    assert d["reconciliation"]["ok"]
    for r in rpt.requests:               # shed requests never rode a batch
        if r.outcome == "shed":
            assert r.phases_us() is None
    format_report(rpt)


def test_check_phase_reconciliation_pass():
    _, tracer, _ = _traced_run()
    rep = check_phase_reconciliation(tracer.events())
    assert rep.ok and rep.checked > 0
    assert rep.info["phase_recon"]["ok"]
    # a request claiming far more latency than its phases account for
    # is a broken trace — the pass must say so
    bad = [
        _ev("b", "request", 0.0, sid=1, args={"lane": 0, "rows": 1}),
        _ev("b", "queue_wait", 0.0, sid=1),
        _ev("e", "queue_wait", 10.0, sid=1,
            args={"flush_reason": "size", "wait_us": 10.0}),
        _ev("X", "batch_form", 10.0, dur=0.0, cat="batch",
            args={"flush_reason": "size", "rows": 1, "n_requests": 1}),
        _ev("X", "exec", 10.0, dur=100.0, cat="exec"),
        _ev("e", "request", 1000.0, sid=1,
            args={"outcome": "ok", "latency_us": 1000.0}),
    ]
    rep = check_phase_reconciliation(bad)
    assert not rep.ok
    assert any(i.code == "phase-reconcile" for i in rep.errors)
    # same trace from a truncated ring buffer: warning, not error
    rep = check_phase_reconciliation(bad, n_dropped=5)
    assert rep.ok
    assert any(i.code == "phase-reconcile" for i in rep.warnings)


# ---------------------------------------------------------------------------
# Streaming windowed metrics
# ---------------------------------------------------------------------------

def test_bucket_ring_tumbling_and_merged():
    ring = BucketRing(window_us=1000.0, n_windows=4)
    ring.add_done(100.0, 50.0, ok=True)
    ring.add_done(1100.0, 70.0, ok=False)
    ring.add_shed(1200.0)
    rows = ring.series()
    assert [r["t_us"] for r in rows] == [0.0, 1000.0]
    assert rows[0]["n"] == 1 and rows[0]["slo_attainment"] == 1.0
    assert rows[1]["shed"] == 1 and rows[1]["slo_attainment"] == 0.0
    m = ring.merged(1500.0, 2000.0).record(0.0, 2000.0)
    assert m["n"] == 2 and m["shed"] == 1
    assert m["slo_attainment"] == pytest.approx(1 / 3)
    # eviction: writes far in the future drop ancient buckets
    ring.add_done(100_000.0, 1.0, ok=True)
    assert all(r["t_us"] >= 97_000.0 or r["n"] == 0
               for r in ring.series()[:-1]) or len(ring.series()) <= 4


def test_windowed_metrics_as_scheduler_sink():
    clk = FakeClock()
    wm = WindowedMetrics(window_us=1000.0)

    def ex(x):
        clk.advance_us(200.0)
        return x.sum(axis=-1)

    s = MicroBatchScheduler(ex, SchedConfig(max_batch=2), clock=clk)
    s.metrics.add_sink(wm)
    for i in range(6):
        s.submit(np.full((1, 3), i, np.float32))
        s.poll()
        clk.advance_us(800.0)
    ser = wm.series()
    assert ser["window_us"] == 1000.0
    lane0 = ser["lanes"]["0"]
    assert sum(r["n"] for r in lane0) == 6
    assert all(r["slo_attainment"] is None for r in lane0)  # no deadlines
    assert sum(b["n_batches"] for b in ser["batches"]) == 3
    assert ser["batches"][0]["mean_exec_us"] == pytest.approx(200.0)
    slid = wm.sliding(10_000.0)
    assert slid["0"]["n"] == 6 and slid["0"]["p99_us"] > 0
    reg = MetricsRegistry()
    wm.publish(reg, "windows")
    assert reg.snapshot()["windows"]["lanes"]["0"]


# ---------------------------------------------------------------------------
# SLO burn-rate monitor + scheduler degradation
# ---------------------------------------------------------------------------

def _mk_monitor(**kw):
    kw.setdefault("slo_target", 0.9)
    kw.setdefault("long_window_us", 8_000.0)
    kw.setdefault("short_window_us", 1_000.0)
    kw.setdefault("threshold", 2.0)
    kw.setdefault("clear_threshold", 1.0)
    kw.setdefault("min_events", 10)
    return BurnRateMonitor(**kw)


def test_burn_rate_monitor_validation():
    with pytest.raises(ValueError):
        BurnRateMonitor(slo_target=1.5)
    with pytest.raises(ValueError):
        BurnRateMonitor(long_window_us=10.0, short_window_us=10.0)
    with pytest.raises(ValueError):
        BurnRateMonitor(threshold=2.0, clear_threshold=3.0)
    with pytest.raises(ValueError):
        _mk_monitor().check()            # no now_us and no clock bound


def test_burn_rate_fire_and_clear_with_hysteresis():
    mon = _mk_monitor()
    seen = []
    mon.on_alert(seen.append)
    t = 0.0
    for _ in range(20):                  # all-miss traffic: burn = 10x
        mon.record_done(lane=0, latency_us=500.0, now_us=t, ok=False,
                        deadline_us=t - 1.0)
        t += 50.0
    # deadline-free traffic must not dilute the burn
    mon.record_done(lane=0, latency_us=1.0, now_us=t, ok=True,
                    deadline_us=None)
    fired = mon.check(t)
    assert [a.kind for a in fired] == ["fire"]
    assert seen == fired and mon.alerting_lanes() == [0]
    assert fired[0].burn_long > 2.0 and fired[0].burn_short > 2.0
    assert "fire" in str(fired[0])
    assert mon.check(t + 10.0) == []     # still burning: no re-fire
    # traffic recovers; once the short window is clean the alert clears
    t += 3_000.0
    cleared = mon.check(t)
    assert [a.kind for a in cleared] == ["clear"]
    assert mon.alerting_lanes() == []
    assert [a.kind for a in mon.history()] == ["fire", "clear"]
    st = mon.stats(t)
    assert st["alerts_fired"] == 1 and st["lanes"]["0"]["alerting"] is False


def test_burn_rate_needs_min_events():
    mon = _mk_monitor(min_events=50)
    for i in range(20):
        mon.record_done(lane=0, latency_us=500.0, now_us=i * 10.0,
                        ok=False, deadline_us=0.0)
    assert mon.check(200.0) == []        # 20 < 50: noise, not a burn


def test_scheduler_degradation_sheds_loosest_lane():
    clk = FakeClock()
    mon = _mk_monitor()
    fired = []
    mon.on_alert(fired.append)
    s = MicroBatchScheduler(
        lambda x: x.sum(axis=-1),
        SchedConfig(max_batch=4, n_priorities=2,
                    lane_slo_us=(500.0, 5_000.0)),
        clock=clk, slo_monitor=mon)
    assert s._degrade_lane == 1          # largest SLO budget loses first
    # lane 0 burns its budget: 20 deadline misses through the metrics
    # sink path (the monitor is fed by ServeMetrics fan-out)
    for _ in range(20):
        clk.advance_us(20.0)
        s.metrics.record_done(600.0, clk.now_us(), lane=0,
                              deadline_us=clk.now_us() - 1.0)
    # loosest lane (1) is shed with a typed reject while the alert is
    # active; the burning lane itself stays admitted
    with pytest.raises(RequestRejected) as ei:
        s.submit(np.ones((1, 3), np.float32), priority=1)
    assert ei.value.reason == RejectReason.DEGRADED
    assert fired and fired[0].kind == "fire" and fired[0].lane == 0
    assert s.metrics.snapshot()["rejected_by_reason"]["degraded"] == 1
    s.submit(np.ones((1, 3), np.float32), priority=0)
    # burn stops; after a clean short window lane 1 is admitted again
    clk.advance_us(3_000.0)
    f = s.submit(np.ones((1, 3), np.float32), priority=1)
    assert mon.alerting_lanes() == []
    s.poll(force=True)
    f.result(0)


def test_degraded_check_rate_limited():
    clk = FakeClock()
    mon = _mk_monitor()
    s = MicroBatchScheduler(
        lambda x: x.sum(axis=-1),
        SchedConfig(max_batch=64, n_priorities=2,
                    lane_slo_us=(500.0, 5_000.0)),
        clock=clk, slo_monitor=mon)
    calls = []
    orig = mon.check
    mon.check = lambda now_us=None: calls.append(now_us) or orig(now_us)
    for _ in range(10):                  # same instant: one evaluation
        s.submit(np.ones((1, 3), np.float32))
    assert len(calls) == 1
    clk.advance_us(s._monitor_interval_us + 1.0)
    s.submit(np.ones((1, 3), np.float32))
    assert len(calls) == 2


# ---------------------------------------------------------------------------
# Prometheus export + pull endpoint
# ---------------------------------------------------------------------------

def _populated_registry():
    reg = MetricsRegistry()
    reg.counter("sched.completed").inc(3)
    reg.gauge("queue depth").set(7.0)
    h = reg.histogram("lat")
    for v in (10.0, 20.0, 30.0):
        h.record(v)
    reg.register("replicas", lambda: {"policy": "rr", "n": 2,
                                      "healthy": True})
    return reg


def test_prometheus_text_exposition():
    text = to_prometheus_text(_populated_registry().snapshot())
    assert "# TYPE repro_sched_completed_total counter" in text
    assert "repro_sched_completed_total 3" in text
    assert "repro_queue_depth 7" in text              # sanitized name
    assert "repro_lat_count 3" in text
    assert "repro_lat_mean_us 20" in text
    assert 'repro_lat_bucket{le="' in text
    assert "repro_replicas_n 2" in text               # provider flattened
    assert "repro_replicas_healthy 1" in text         # bool -> 0/1
    assert "rr" not in text                           # strings dropped
    assert to_prometheus_text({}) == ""


def test_metrics_server_pull_endpoint():
    srv = MetricsServer(_populated_registry(), port=0)
    try:
        with urllib.request.urlopen(srv.url, timeout=5) as r:
            assert r.status == 200
            assert "version=0.0.4" in r.headers["Content-Type"]
            body = r.read().decode()
        assert "repro_sched_completed_total 3" in body
        with urllib.request.urlopen(srv.url + ".json", timeout=5) as r:
            doc = json.loads(r.read())
        assert doc["counters"]["sched.completed"] == 3
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                srv.url.rsplit("/", 1)[0] + "/nope", timeout=5)
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# Perf-trajectory ledger
# ---------------------------------------------------------------------------

def _bench_doc(sha, p95, overhead):
    return {"section": "serve",
            "meta": {"git_sha": sha,
                     "timestamp_utc": f"2026-08-08T00:00:0{sha[-1]}Z"},
            "results": {"baseline_sequential": {"p95_us": p95,
                                                "qps": 1000.0},
                        "tracer_overhead": {"overhead_pct": overhead}}}


def test_history_ledger_idempotent_append_and_report(tmp_path):
    from benchmarks import history
    path = str(tmp_path / "ledger.jsonl")
    assert history.append_entry(_bench_doc("a0", 100.0, 1.0),
                                path=path) is not None
    # same provenance again: skipped, the ledger stays single-entry
    assert history.append_entry(_bench_doc("a0", 100.0, 1.0),
                                path=path) is None
    assert history.append_entry(_bench_doc("b1", 150.0, 1.2),
                                path=path) is not None
    entries = history.load_history(path)
    assert len(entries) == 2
    series = history.trajectory(entries, section="serve")
    p95 = series["serve/sequential/p95_us"]
    assert p95["n"] == 2 and p95["first"] == 100.0 and p95["last"] == 150.0
    assert p95["change_pct"] == pytest.approx(50.0)   # lower-better: worse
    qps = series["serve/sequential/qps"]
    assert qps["change_pct"] == 0.0                   # flat
    text = history.format_report(series)
    assert "serve/sequential/p95_us" in text and "drifting" in text
    # corrupt trailing line (killed CI job) must not poison the ledger
    with open(path, "a") as f:
        f.write("{truncated")
    assert len(history.load_history(path)) == 2
    assert history.trajectory([], section="serve") == {}
    assert "empty" in history.format_report({})


def test_history_cli(tmp_path, capsys):
    from benchmarks import history
    bench = tmp_path / "BENCH_serve.json"
    bench.write_text(json.dumps(_bench_doc("c2", 120.0, 0.5)))
    ledger = str(tmp_path / "ledger.jsonl")
    assert history.main(["--ledger", ledger, "append", str(bench)]) == 0
    capsys.readouterr()
    assert history.main(["--ledger", ledger, "report", "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["serve/tracer/overhead_pct"]["n"] == 1
    assert history.main(["--ledger", ledger, "report"]) == 0
    assert "serve/tracer/overhead_pct" in capsys.readouterr().out
    assert history.main(["--ledger", ledger, "append",
                         str(tmp_path / "missing.json")]) == 0


# ---------------------------------------------------------------------------
# Regression gate: tracer overhead is diffed direction-aware + floored
# ---------------------------------------------------------------------------

def test_check_regression_tracer_overhead_floored():
    from benchmarks.check_regression import compare, extract_metrics
    base = extract_metrics(_bench_doc("a0", 100.0, 0.3))
    noisy = extract_metrics(_bench_doc("b1", 100.0, 1.2))
    bad = extract_metrics(_bench_doc("c2", 100.0, 40.0))
    assert base["serve/tracer/overhead_pct"] == (0.3, "lower")
    # sub-floor wobble (0.3% -> 1.2%) compares as equal…
    regs, checked, _, _ = compare(base, noisy, tolerance=0.25,
                                  min_us=50.0)
    assert not regs and any(n == "serve/tracer/overhead_pct"
                            for n, *_ in checked)
    # …while a real overhead explosion (0.3% -> 40%) still fails
    regs, _, _, _ = compare(base, bad, tolerance=0.25, min_us=50.0)
    assert any(n == "serve/tracer/overhead_pct" for n, *_ in regs)
