"""repro.serve: scheduler semantics under a fake clock, backpressure,
priority lanes, per-lane SLO deadlines (EDF formation, expiry shedding,
miss-rate accounting), replica failover, bitplane aggregation, and
cross-backend bit-identity of scheduled results on JSC-S."""
import threading
import time

import numpy as np
import pytest

from repro.serve import (AllReplicasDown, BitplaneAggregator, FakeClock,
                         MicroBatchScheduler, RejectReason, ReplicaSet,
                         RequestRejected, SchedConfig)
from repro.serve.sched import BoundedPriorityQueue, ServeFuture, ServeRequest


def _sum_executor(log):
    def ex(x):
        log.append(x.shape[0])
        return x.sum(axis=-1)
    return ex


# ---------------------------------------------------------------------------
# Batch formation: deadline flush vs full-batch flush
# ---------------------------------------------------------------------------

def test_full_batch_flushes_without_deadline():
    clk, log = FakeClock(), []
    s = MicroBatchScheduler(_sum_executor(log),
                            SchedConfig(max_batch=4, max_wait_us=1e6),
                            clock=clk)
    futs = [s.submit(np.full((3,), i, np.float32)) for i in range(4)]
    # four 1-row requests = max_batch: flush immediately, no time passed
    assert s.poll() == 4
    assert log == [4]
    assert [f.result(0) for f in futs] == [0.0, 3.0, 6.0, 9.0]


def test_deadline_flush_partial_batch():
    clk, log = FakeClock(), []
    s = MicroBatchScheduler(_sum_executor(log),
                            SchedConfig(max_batch=64, max_wait_us=200.0),
                            clock=clk)
    f = s.submit(np.ones((2, 3), np.float32))
    assert s.poll() == 0                 # under max_batch, deadline not hit
    clk.advance_us(199.0)
    assert s.poll() == 0                 # 1 us early
    clk.advance_us(1.0)
    assert s.poll() == 1                 # exactly at max_wait_us
    assert log == [2]
    np.testing.assert_allclose(f.result(0), [3.0, 3.0])
    assert f.latency_us == 200.0         # true enqueue->complete time


def test_multirow_requests_never_split_and_fill_batches():
    clk, log = FakeClock(), []
    s = MicroBatchScheduler(_sum_executor(log),
                            SchedConfig(max_batch=4, max_wait_us=10.0),
                            clock=clk)
    fa = s.submit(np.ones((3, 2)))
    fb = s.submit(np.ones((2, 2)))       # does not fit with fa: 5 > 4
    clk.advance_us(11.0)
    assert s.poll() == 2                 # two batches, FIFO preserved
    assert log == [3, 2]
    assert fa.result(0).shape == (3,) and fb.result(0).shape == (2,)


# ---------------------------------------------------------------------------
# Admission control / backpressure
# ---------------------------------------------------------------------------

def test_backpressure_typed_reject():
    s = MicroBatchScheduler(_sum_executor([]),
                            SchedConfig(max_batch=8, max_queue=3),
                            clock=FakeClock())
    for _ in range(3):
        s.submit(np.ones(2))
    with pytest.raises(RequestRejected) as e:
        s.submit(np.ones(2))
    assert e.value.reason == RejectReason.QUEUE_FULL
    with pytest.raises(RequestRejected) as e:
        s.submit(np.ones((9, 2)))        # more rows than one batch
    assert e.value.reason == RejectReason.TOO_LARGE
    snap = s.metrics.snapshot()
    assert snap["rejected"] == 2
    assert snap["rejected_by_reason"] == {"queue_full": 1, "too_large": 1}
    assert s.drain() == 3                # queued work still completes


def test_shutdown_rejects_new_submissions():
    s = MicroBatchScheduler(_sum_executor([]), SchedConfig(),
                            clock=FakeClock())
    s.start()
    s.stop(drain=True)
    with pytest.raises(RequestRejected) as e:
        s.submit(np.ones(2))
    assert e.value.reason == RejectReason.SHUTDOWN


# ---------------------------------------------------------------------------
# Shutdown: stop/submit race + drain=False typed rejection
# ---------------------------------------------------------------------------

def test_stop_submit_race_rejected_not_hung():
    """A submit racing with stop()'s final drain must get a typed
    SHUTDOWN reject, not be accepted into a queue nobody serves (the
    old order set _shutdown only *after* the drain, so the racing
    request's future hung forever)."""
    clk = FakeClock()
    holder = {}

    def ex(x):
        # runs inside stop()'s final drain — exactly the race window
        try:
            holder["fut"] = s.submit(np.ones(2))
        except RequestRejected as e:
            holder["exc"] = e
        return x.sum(axis=-1)

    s = MicroBatchScheduler(ex, SchedConfig(max_batch=8), clock=clk)
    f = s.submit(np.ones(2))
    s.stop(drain=True)
    assert "fut" not in holder, "racing submit was accepted and will hang"
    assert holder["exc"].reason == RejectReason.SHUTDOWN
    assert f.result(0) == 2.0            # pre-stop work still served


def test_stop_without_drain_rejects_queued():
    s = MicroBatchScheduler(_sum_executor([]), SchedConfig(),
                            clock=FakeClock())
    f = s.submit(np.ones(2))
    s.stop(drain=False)
    with pytest.raises(RequestRejected) as e:
        f.result(0)                      # resolved, not hung
    assert e.value.reason == RejectReason.SHUTDOWN


# ---------------------------------------------------------------------------
# Admission shape validation: one bad request must not poison a batch
# ---------------------------------------------------------------------------

def test_bad_shape_rejected_at_admission_batch_survives():
    clk, log = FakeClock(), []

    def ex(x):
        log.append(x.shape[0])
        return x.sum(axis=-1)

    ex.n_features = 3
    s = MicroBatchScheduler(ex, SchedConfig(max_batch=8), clock=clk)
    good = [s.submit(np.ones(3)) for _ in range(2)]
    with pytest.raises(RequestRejected) as e:
        s.submit(np.ones((2, 4)))        # wrong width: would break concat
    assert e.value.reason == RejectReason.BAD_SHAPE
    with pytest.raises(RequestRejected) as e:
        s.submit(np.ones((2, 2, 3)))     # wrong rank
    assert e.value.reason == RejectReason.BAD_SHAPE
    assert s.drain() == 2                # the good batch executes cleanly
    assert [f.result(0) for f in good] == [3.0, 3.0]
    assert s.metrics.snapshot()["rejected_by_reason"]["bad_shape"] == 2


def test_width_pinned_from_first_request_without_executor_hint():
    s = MicroBatchScheduler(_sum_executor([]), SchedConfig(),
                            clock=FakeClock())
    s.submit(np.ones(2))                 # pins batch width to 2
    with pytest.raises(RequestRejected) as e:
        s.submit(np.ones(5))
    assert e.value.reason == RejectReason.BAD_SHAPE
    assert s.drain() == 1


# ---------------------------------------------------------------------------
# Per-lane SLO deadlines: expiry shedding, EDF, miss-rate accounting
# ---------------------------------------------------------------------------

def test_deadline_expiry_shed_with_typed_reject():
    clk, log = FakeClock(), []
    s = MicroBatchScheduler(_sum_executor(log),
                            SchedConfig(max_batch=8, max_wait_us=1e6,
                                        n_priorities=1,
                                        lane_slo_us=(100.0,)), clock=clk)
    f = s.submit(np.ones(2))
    clk.advance_us(150.0)                # past the lane-0 SLO
    assert s.drain() == 1                # resolved by shedding, not served
    assert log == []                     # never reached the executor
    with pytest.raises(RequestRejected) as e:
        f.result(0)
    assert e.value.reason == RejectReason.DEADLINE_EXCEEDED
    snap = s.metrics.snapshot()
    assert snap["shed"] == 1 and snap["completed"] == 0
    assert snap["deadline_miss_rate"] == 1.0
    assert snap["lanes"]["0"]["shed"] == 1


def test_explicit_deadline_overrides_lane_slo():
    clk, log = FakeClock(), []
    s = MicroBatchScheduler(_sum_executor(log),
                            SchedConfig(max_batch=8, max_wait_us=1e6,
                                        n_priorities=1,
                                        lane_slo_us=(100.0,)), clock=clk)
    f = s.submit(np.ones(2), deadline_us=500.0)
    clk.advance_us(150.0)                # past the lane SLO, within budget
    assert s.poll() == 0                 # not expired, not yet due
    clk.advance_us(350.0)
    assert s.poll() == 1                 # flushed at its own deadline
    assert f.result(0) == 2.0


def test_nonpositive_budget_rejected_at_admission():
    s = MicroBatchScheduler(_sum_executor([]), SchedConfig(),
                            clock=FakeClock())
    with pytest.raises(RequestRejected) as e:
        s.submit(np.ones(2), deadline_us=-5.0)
    assert e.value.reason == RejectReason.DEADLINE_EXCEEDED


def test_edf_ordering_within_lane_vs_fifo():
    clk, order = FakeClock(), []

    def ex(x):
        order.extend(int(v) for v in x[:, 0])
        return x[:, 0]

    s = MicroBatchScheduler(ex, SchedConfig(max_batch=1, n_priorities=1),
                            clock=clk)
    s.submit(np.full((1, 1), 1.0), deadline_us=500.0)
    s.submit(np.full((1, 1), 2.0), deadline_us=100.0)  # tighter, later
    s.drain()
    assert order == [2, 1]               # EDF, not arrival FIFO

    order.clear()
    s2 = MicroBatchScheduler(ex, SchedConfig(max_batch=1, n_priorities=1),
                             clock=clk)
    s2.submit(np.full((1, 1), 1.0))      # no deadlines: FIFO preserved
    s2.submit(np.full((1, 1), 2.0))
    s2.drain()
    assert order == [1, 2]


def test_per_lane_miss_rate_accounting():
    clk = FakeClock()

    def slow_ex(x):                      # execution outlives the tight SLO
        clk.advance_us(150.0)
        return x.sum(axis=-1)

    s = MicroBatchScheduler(slow_ex,
                            SchedConfig(max_batch=8, max_wait_us=1e6,
                                        n_priorities=2,
                                        lane_slo_us=(100.0, 10_000.0)),
                            clock=clk)
    tight = s.submit(np.ones(2), priority=0)
    loose = s.submit(np.ones(2), priority=1)
    assert s.drain() == 2
    assert tight.result(0) == 2.0 and loose.result(0) == 2.0
    snap = s.metrics.snapshot()
    # lane 0 completed but 50 µs past its deadline: a served-late miss
    assert snap["lanes"]["0"]["missed"] == 1
    assert snap["lanes"]["0"]["deadline_miss_rate"] == 1.0
    assert snap["lanes"]["1"]["missed"] == 0
    assert snap["lanes"]["1"]["deadline_miss_rate"] == 0.0
    assert snap["lanes"]["1"]["mean_slack_us"] == pytest.approx(9850.0)
    # now an expiry shed on the tight lane joins the miss accounting
    f = s.submit(np.ones(2), priority=0)
    clk.advance_us(200.0)
    s.drain()
    with pytest.raises(RequestRejected):
        f.result(0)
    snap = s.metrics.snapshot()
    assert snap["lanes"]["0"]["shed"] == 1
    assert snap["deadline_miss_rate"] == pytest.approx(2 / 3)


def test_next_deadline_wakes_on_slo_not_arrival_age():
    clk = FakeClock(1000.0)
    s = MicroBatchScheduler(_sum_executor([]),
                            SchedConfig(max_wait_us=1e6, n_priorities=1,
                                        lane_slo_us=(100.0,)), clock=clk)
    assert s.next_deadline_us() is None
    s.submit(np.ones(2))
    assert s.next_deadline_us() == 1100.0    # the SLO, not enqueue+1e6

    s2 = MicroBatchScheduler(_sum_executor([]),
                             SchedConfig(max_wait_us=200.0), clock=clk)
    s2.submit(np.ones(2))
    assert s2.next_deadline_us() == 1200.0   # no SLO: arrival age cap


# ---------------------------------------------------------------------------
# Deadline-aware replica dispatch
# ---------------------------------------------------------------------------

def test_replica_failover_restamps_remaining_budget():
    clk = FakeClock()

    def crash_slowly(x):
        clk.advance_us(200.0)            # the failure ate the whole budget
        raise RuntimeError("replica crash")

    rs = ReplicaSet([crash_slowly, lambda x: x.sum(axis=-1)], policy="rr",
                    clock=clk)
    with pytest.raises(RequestRejected) as e:
        rs(np.ones((1, 2)), deadline_us=100.0)
    assert e.value.reason == RejectReason.DEADLINE_EXCEEDED
    # the healthy replica is still up: budget-free traffic flows on
    np.testing.assert_allclose(rs(np.ones((1, 2))), [2.0])
    assert [r["healthy"] for r in rs.stats()] == [False, True]


def test_replica_failover_within_budget_still_retries():
    clk = FakeClock()

    def crash_fast(x):
        clk.advance_us(10.0)
        raise RuntimeError("replica crash")

    rs = ReplicaSet([crash_fast, lambda x: x.sum(axis=-1)], policy="rr",
                    clock=clk)
    np.testing.assert_allclose(rs(np.ones((1, 2)), deadline_us=100.0), [2.0])


def test_least_slack_policy_picks_smallest_expected_completion():
    rs = ReplicaSet([lambda x: x, lambda x: x], policy="least_slack")
    rs.replicas[0].ewma_us, rs.replicas[0].inflight = 100.0, 1
    rs.replicas[1].ewma_us, rs.replicas[1].inflight = 300.0, 0
    picked = rs._pick()                  # (1+1)*100 = 200 < (0+1)*300
    assert picked.rid == 0
    rs.replicas[0].inflight -= 1


# ---------------------------------------------------------------------------
# Priority lanes
# ---------------------------------------------------------------------------

def test_priority_ordering_within_flush():
    clk, order = FakeClock(), []

    def ex(x):
        order.extend(int(v) for v in x[:, 0])
        return x[:, 0]

    s = MicroBatchScheduler(ex, SchedConfig(max_batch=2, max_wait_us=10.0,
                                            n_priorities=2), clock=clk)
    lo = s.submit(np.full((1, 1), 9.0), priority=1)
    hi = [s.submit(np.full((1, 1), float(i)), priority=0) for i in range(3)]
    clk.advance_us(11.0)
    s.poll()
    # lane 0 drains FIFO first; the lone low-priority request flushes last
    assert order == [0, 1, 2, 9]
    assert lo.result(0) == 9.0 and hi[0].result(0) == 0.0


def test_bad_priority_rejected():
    s = MicroBatchScheduler(_sum_executor([]),
                            SchedConfig(n_priorities=2), clock=FakeClock())
    with pytest.raises(RequestRejected) as e:
        s.submit(np.ones(2), priority=5)
    assert e.value.reason == RejectReason.BAD_PRIORITY


def test_bounded_priority_queue_is_lm_admission_core():
    q = BoundedPriorityQueue(max_queue=2, n_priorities=3)

    def req(p):
        return ServeRequest(x=None, rows=1, priority=p, t_enqueue_us=0.0,
                            future=ServeFuture())

    q.push(req(2))
    q.push(req(0))
    with pytest.raises(RequestRejected) as e:
        q.push(req(1))
    assert e.value.reason == RejectReason.QUEUE_FULL
    (first,) = q.pop_batch(1)
    assert first.priority == 0           # freed slot admits high lane first


# ---------------------------------------------------------------------------
# Executor failure + replica failover
# ---------------------------------------------------------------------------

def test_executor_error_fails_batch_not_scheduler():
    clk = FakeClock()
    calls = []

    def flaky(x):
        calls.append(x.shape[0])
        if len(calls) == 1:
            raise RuntimeError("boom")
        return x.sum(axis=-1)

    s = MicroBatchScheduler(flaky, SchedConfig(max_batch=2), clock=clk)
    bad = [s.submit(np.ones(2)) for _ in range(2)]
    assert s.poll() == 2                 # resolved, but with the error set
    for f in bad:
        with pytest.raises(RuntimeError):
            f.result(0)
    good = [s.submit(np.ones(2)) for _ in range(2)]
    s.poll()
    assert [f.result(0) for f in good] == [2.0, 2.0]
    assert s.metrics.snapshot()["errors"] == 2


def test_replica_failover_marks_down_and_retries():
    down = {"n": 0}

    def bad(x):
        down["n"] += 1
        raise RuntimeError("replica crash")

    rs = ReplicaSet([bad, lambda x: x.sum(axis=-1)], policy="rr")
    np.testing.assert_allclose(rs(np.ones((2, 3))), [3.0, 3.0])
    assert down["n"] == 1
    rs(np.ones((1, 3)))                  # dead replica skipped, not retried
    assert down["n"] == 1
    stats = rs.stats()
    assert [r["healthy"] for r in stats] == [False, True]
    assert stats[1]["served"] == 2 and stats[0]["failures"] == 1


def test_all_replicas_down_raises_through_scheduler():
    def bad(x):
        raise RuntimeError("dead")

    rs = ReplicaSet([bad, bad])
    s = MicroBatchScheduler(rs, SchedConfig(max_batch=1), clock=FakeClock())
    f = s.submit(np.ones(2))
    s.poll()
    with pytest.raises(AllReplicasDown):
        f.result(0)


def test_least_loaded_prefers_idle_replica():
    rs = ReplicaSet([lambda x: x, lambda x: x], policy="least_loaded")
    rs.replicas[0].inflight = 3          # simulate a busy replica
    picked = rs._pick()
    assert picked.rid == 1
    rs.replicas[1].inflight -= 1


# ---------------------------------------------------------------------------
# Two batches in flight: executors whose labels are not yet on the host
# ---------------------------------------------------------------------------

class _Pending:
    """Labels that reach the host only once ``gate`` is set (or raise
    there, as a device error surfaces when the labels are awaited)."""

    def __init__(self, x, gate, fail):
        self._x, self._gate, self._fail = x, gate, fail

    def __array__(self, dtype=None, copy=None):
        assert self._gate.wait(10), "gate never opened"
        if self._fail:
            raise RuntimeError("device error")
        return self._x.sum(axis=-1)


class _GatedExecutor:
    """Returns ``_Pending`` labels; batch i's labels wait on ``gates[i]``
    and fail if ``i`` is in ``fail``."""

    def __init__(self, fail=()):
        self.gates: list = []
        self.fail = set(fail)

    def __call__(self, x):
        gate = threading.Event()
        self.gates.append(gate)
        return _Pending(x, gate, len(self.gates) - 1 in self.fail)


def _until(cond, timeout=10.0):
    t_end = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < t_end, "timed out"
        time.sleep(0.002)


def _pipelined(ex, tracer=None):
    # each 2-row request fills a batch: a size flush at once
    return MicroBatchScheduler(ex, SchedConfig(max_batch=2,
                                               max_wait_us=1e6),
                               tracer=tracer).start()


def _req(i):
    return np.full((2, 3), float(i), np.float32)


def test_pipeline_keeps_at_most_two_batches_in_flight():
    from repro.obs import SpanTracer
    from repro.serve.sched import PIPELINE_DEPTH
    assert PIPELINE_DEPTH == 2
    ex, tracer = _GatedExecutor(), SpanTracer()
    s = _pipelined(ex, tracer)
    try:
        futs = [s.submit(_req(i)) for i in range(4)]
        _until(lambda: len(ex.gates) == 2)
        time.sleep(0.05)
        # two launched and unanswered: the third waits for a slot
        assert len(ex.gates) == 2 and not any(f.done() for f in futs)
        for i in (0, 1):            # each answer lets one more launch
            ex.gates[i].set()
            _until(lambda: len(ex.gates) == i + 3)
            assert futs[i].done() and not futs[i + 1].done()
        for i in (2, 3):
            ex.gates[i].set()
        got = [f.result(10) for f in futs]
    finally:
        s.stop()
    for i, g in enumerate(got):
        np.testing.assert_array_equal(g, [3.0 * i, 3.0 * i])
    evs = tracer.events()
    execs = [e for e in evs if e.name == "exec"]
    assert [e.args["inflight"] for e in execs] == [0, 1, 1, 1]
    assert s.metrics.snapshot()["n_overlapped"] == 3
    # the interleaved spans of overlapping batches still add up to each
    # request's latency (queue_wait + batch_form + exec..collect)
    from repro.check.tracecheck import check_phase_reconciliation, check_trace
    rep = check_trace(evs)
    check_phase_reconciliation(evs, report=rep)
    assert rep.ok and rep.info["phase_recon"]["n_checked"] == 4, rep.format()


@pytest.mark.parametrize("release,fail", [((1, 0, 2), ()), ((0, 1, 2), (1,))],
                         ids=["fifo", "materialise_error"])
def test_pipeline_scatters_in_dispatch_order(release, fail):
    """Batch 1's labels released before batch 0's are still scattered
    after them; a batch whose labels raise fails alone, and the
    scheduler goes on serving."""
    ex = _GatedExecutor(fail)
    s = _pipelined(ex)
    try:
        futs = [s.submit(_req(i)) for i in range(3)]
        _until(lambda: len(ex.gates) == 2)
        first = release[0]
        ex.gates[first].set()
        time.sleep(0.05)
        # done only once every batch before it is
        assert futs[first].done() == (first == 0)
        for i in release[1:]:
            _until(lambda: len(ex.gates) > i)
            ex.gates[i].set()
        for f in futs:
            _until(f.done)
        for i, f in enumerate(futs):
            if i in fail:
                with pytest.raises(RuntimeError, match="device error"):
                    f.result(0)
            else:
                np.testing.assert_array_equal(f.result(0), [3.0 * i] * 2)
        assert [f.t_done_us for f in futs] == sorted(
            f.t_done_us for f in futs)
        late = s.submit(_req(7))
        _until(lambda: len(ex.gates) == 4)
        ex.gates[3].set()
        np.testing.assert_array_equal(late.result(10), [21.0, 21.0])
    finally:
        s.stop()
    snap = s.metrics.snapshot()
    assert snap["errors"] == len(fail)
    assert snap["completed"] == 4 - len(fail)


@pytest.mark.parametrize("drain", [True, False])
def test_stop_resolves_batches_in_flight(drain):
    """stop() returns only once the batches in flight are scattered:
    the two launched before it, and the one the dispatch thread flushes
    from the queue as it stops."""
    ex = _GatedExecutor()
    s = _pipelined(ex)
    futs = [s.submit(_req(i)) for i in range(3)]
    _until(lambda: len(ex.gates) == 2)
    stopper = threading.Thread(target=s.stop, kwargs={"drain": drain})
    stopper.start()
    time.sleep(0.05)
    assert stopper.is_alive() and not any(f.done() for f in futs)
    ex.gates[0].set()
    _until(lambda: len(ex.gates) == 3)
    time.sleep(0.05)
    assert stopper.is_alive() and not futs[1].done()
    ex.gates[1].set()
    ex.gates[2].set()
    stopper.join(10)
    assert not stopper.is_alive()
    for i, f in enumerate(futs):
        np.testing.assert_array_equal(f.result(0), [3.0 * i] * 2)
    with pytest.raises(RequestRejected) as e:
        s.submit(_req(3))
    assert e.value.reason == RejectReason.SHUTDOWN


def _open_pending(x):
    gate = threading.Event()
    gate.set()
    return _Pending(x, gate, False)


@pytest.mark.parametrize("executor,rows", [
    (_sum_executor([]), 2),         # labels already on the host
    (_open_pending, 1),             # 1-row requests: flushed on time
], ids=["ndarray_labels", "flushed_on_time"])
def test_batches_that_stay_serial(executor, rows):
    """Each batch runs start to finish on the dispatch thread — no batch
    in flight behind another, no ``collect`` span — where its labels
    are already on the host, or where it was flushed on time, not
    because it was full."""
    from repro.obs import SpanTracer
    tracer = SpanTracer()
    s = MicroBatchScheduler(executor, SchedConfig(max_batch=2,
                                                  max_wait_us=100.0),
                            tracer=tracer).start()
    try:
        for i in range(6):
            x = _req(i)[:rows]
            np.testing.assert_array_equal(s.submit(x).result(10),
                                          x.sum(axis=-1))
    finally:
        s.stop()
    evs = tracer.events()
    execs = [e for e in evs if e.name == "exec"]
    assert len(execs) == 6 and all(e.args["inflight"] == 0 for e in execs)
    reasons = {e.args["flush_reason"] for e in evs
               if e.name == "batch_form"}
    assert reasons == ({"size"} if rows == 2 else {"max_wait"})
    assert not [e for e in evs if e.name == "collect"]
    assert {e.tid for e in execs} == {
        e.tid for e in evs if e.name == "scatter"}
    assert s.metrics.snapshot()["n_overlapped"] == 0


# ---------------------------------------------------------------------------
# Scheduled serving on JSC-S: all backends, bit-identical to classify
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def jsc_small():
    from repro.configs.jsc import JSC_S
    from repro.data.jsc import train_test
    from repro.models.mlp import to_logic
    from repro.train.jsc_trainer import train_jsc
    data = train_test(2000, 400, seed=2)
    res = train_jsc(JSC_S, steps=120, batch=128, data=data)
    net = to_logic(JSC_S, res.params, res.masks, res.bn_state)
    return net, data[1][0]


@pytest.mark.parametrize("backend", ["gather", "pallas", "bitplane"])
def test_scheduled_matches_direct_classify(jsc_small, backend):
    from repro.serving.engine import LogicEngine
    net, xte = jsc_small
    eng = LogicEngine(net, 5, max_batch=64, backend=backend)
    want = eng.classify(xte[:96])
    clk = FakeClock()
    s = MicroBatchScheduler(eng.scheduler_executor(),
                            SchedConfig(max_batch=64, max_wait_us=100.0,
                                        max_queue=200), clock=clk)
    futs = [s.submit(xte[i]) for i in range(96)]   # single-sample requests
    assert s.drain() == 96
    got = np.array([int(f.result(0)) for f in futs], np.int32)
    np.testing.assert_array_equal(got, want)
    snap = s.metrics.snapshot()
    assert snap["n_batches"] == 2                  # 96 rows / max_batch 64
    assert snap["mean_batch_occupancy"] == pytest.approx(0.75)


def test_bitplane_aggregator_packs_requests_into_lanes(jsc_small):
    from repro.serving.engine import LogicEngine
    net, xte = jsc_small
    eng = LogicEngine(net, 5, max_batch=64, backend="bitplane")
    agg = BitplaneAggregator(eng.bitnet, 5)
    got = agg(xte[:40])
    np.testing.assert_array_equal(got, eng.classify(xte[:40]))
    # 40 requests -> 2 lane-words per input wire (32 + 8 lanes)
    n_wires = net.n_inputs * eng.bitnet.in_bits
    assert agg.pack_requests(xte[:40]).shape == (n_wires, 2)
    assert agg.mean_lane_occupancy == pytest.approx(40 / 64)


def test_aggregator_occupancy_counts_real_rows_under_pad_rows(jsc_small):
    from repro.serving.engine import LogicEngine
    net, xte = jsc_small
    eng = LogicEngine(net, 5, max_batch=64, backend="bitplane")
    agg = BitplaneAggregator(eng.bitnet, 5, pad_rows=64)
    got = agg(xte[:16])
    np.testing.assert_array_equal(got, eng.classify(xte[:16]))
    # 16 real rows in one lane-word: occupancy is 16/32, not deflated by
    # the 48 shape-stability pad rows (which get their own counter)
    assert agg.n_evals == 1 and agg.n_rows == 16
    assert agg.mean_lane_occupancy == pytest.approx(16 / 32)
    assert agg.n_pad_rows == 48
    assert agg.n_partial_packs == 1
    assert agg.n_features == net.n_inputs


@pytest.mark.parametrize("pad_rows", [None, 64])
def test_aggregator_pack_makes_no_device_transfer(jsc_small, pad_rows):
    import jax
    from repro.serving.engine import LogicEngine
    from repro.synth.simulate import pack_bits
    net, xte = jsc_small
    eng = LogicEngine(net, 5, max_batch=64, backend="bitplane")
    agg = BitplaneAggregator(eng.bitnet, 5, pad_rows=pad_rows)
    rows = np.concatenate([xte[:32], 3 * xte[32:40]]).astype(np.float32)
    padded = rows if pad_rows is None else np.concatenate(
        [rows, np.zeros((pad_rows - len(rows), rows.shape[1]), np.float32)])
    # the jax quantizer's codes, bit b of code i on wire i*in_bits + b
    codes = np.asarray(net.quantize_inputs(padded))
    bits = eng.bitnet.in_bits
    planes = (codes[:, :, None] >> np.arange(bits)) & 1
    want = pack_bits(planes.reshape(len(padded), -1).T)
    with jax.transfer_guard("disallow"):
        got = agg.pack_requests(rows)
    np.testing.assert_array_equal(got, want)


def _device_aggregator(jsc_small):
    """A padded aggregator on the device engine (interpreted here) and
    the numpy-engine aggregator over the same netlist."""
    from repro.serving.engine import LogicEngine
    from repro.synth import BitplaneNetwork
    net, _ = jsc_small
    ref = LogicEngine(net, 5, max_batch=64, backend="bitplane").bitnet
    dev = BitplaneNetwork(net, ref.mapped, engine="pallas-streamed")
    return (BitplaneAggregator(dev, 5, pad_rows=64),
            BitplaneAggregator(ref, 5))


def test_device_aggregator_returns_pending_labels(jsc_small):
    _, xte = jsc_small
    agg, ref = _device_aggregator(jsc_small)
    x = xte[:40]
    pending = agg(x)
    assert not isinstance(pending, np.ndarray)
    got = np.asarray(pending)
    assert got.shape == (40,)
    assert np.asarray(pending) is got            # awaited once, then kept
    words = agg.pack_requests(x)
    np.testing.assert_array_equal(
        got, np.asarray(agg.bitnet.classify_packed(words, 40, 5)))
    np.testing.assert_array_equal(got, ref(x))


def test_device_aggregator_threaded_trace_reconciles(jsc_small):
    """Threaded serving on the device engine: the labels are awaited on
    the completion thread, every batch's h2d/device_exec/fetch spans
    carry its own batch id, and the trace passes the trace checks and
    the phase reconciliation."""
    from repro.check.tracecheck import check_phase_reconciliation, check_trace
    from repro.obs import SpanTracer
    _, xte = jsc_small
    agg, ref = _device_aggregator(jsc_small)
    tracer = SpanTracer()
    # 24 requests of 8 rows: three full batches, each launched while
    # the one before may still be in flight
    s = MicroBatchScheduler(agg, SchedConfig(max_batch=64, max_wait_us=1e6,
                                             max_queue=400),
                            tracer=tracer).start()
    try:
        futs = [s.submit(xte[i: i + 8]) for i in range(0, 192, 8)]
        got = np.concatenate([f.result(60) for f in futs])
    finally:
        s.stop()
    np.testing.assert_array_equal(got, ref(xte[:192]))
    evs = tracer.events()
    rep = check_trace(evs)
    check_phase_reconciliation(evs, report=rep)
    assert rep.ok, rep.format()
    spans = {n: [e for e in evs if e.ph == "X" and e.name == n]
             for n in ("batch_form", "h2d", "device_exec", "fetch",
                       "collect")}
    ids = sorted(e.args["batch"] for e in spans["batch_form"])
    for name in ("h2d", "device_exec", "fetch", "collect"):
        assert sorted(e.args["batch"] for e in spans[name]) == ids, name
    # launched on the dispatch thread, awaited on the completion thread
    (launch_tid,) = {e.tid for e in spans["device_exec"]}
    (collect_tid,) = {e.tid for e in spans["fetch"] + spans["collect"]}
    assert launch_tid != collect_tid


def test_serve_queue_wrapper_reports_true_latency(jsc_small):
    from repro.serving.engine import LogicEngine
    net, xte = jsc_small
    eng = LogicEngine(net, 5, max_batch=64, backend="gather")
    reqs = [xte[i * 32: (i + 1) * 32] for i in range(4)]
    results, stats = eng.serve_queue(reqs)
    assert len(results) == 4
    np.testing.assert_array_equal(np.concatenate(results),
                                  eng.classify(xte[:128]))
    for key in ("p50_us", "p95_us", "p99_us", "mean_us", "qps",
                "mean_batch_occupancy"):
        assert key in stats
    assert stats["p95_us"] >= stats["p50_us"] > 0.0


def test_threaded_driver_end_to_end(jsc_small):
    from repro.serving.engine import LogicEngine
    net, xte = jsc_small
    eng = LogicEngine(net, 5, max_batch=64, backend="gather")
    s = MicroBatchScheduler(eng.scheduler_executor(),
                            SchedConfig(max_batch=64, max_wait_us=500.0,
                                        max_queue=400)).start()
    futs = [s.submit(xte[i]) for i in range(200)]
    got = np.array([int(f.result(timeout=30)) for f in futs], np.int32)
    s.stop(drain=True)
    np.testing.assert_array_equal(got, eng.classify(xte[:200]))
    assert s.metrics.snapshot()["completed"] == 200


def test_logic_replicas_pinned_one_device_each(jsc_small):
    """Replica i owns jax.devices()[i % n_devices]: its plan tensors and
    its jitted outputs are committed there, and it serves the same
    labels as a direct classify."""
    import jax

    from repro.serve import build_logic_replicas
    net, xte = jsc_small
    rs = build_logic_replicas(net, 5, n_replicas=2, backend="bitplane",
                              max_batch=64, engine="pallas-streamed")
    devs = jax.devices()
    for i, r in enumerate(rs.replicas):
        ex = r.fn.bitnet.executor
        assert ex.device == devs[i % len(devs)]
        assert ex._meta.committed and ex._meta.devices() == {ex.device}
        out = ex.device_labels(np.zeros((ex.tp.n_pis, 2), np.uint32), 5)
        assert out.committed and out.devices() == {ex.device}
    want = rs.replicas[0].fn.bitnet.classify(xte[:40], 5)
    np.testing.assert_array_equal(rs(xte[:40]), want)
    np.testing.assert_array_equal(rs(xte[:40]), want)      # replica 1
    assert [st["served"] for st in rs.stats()] == [1, 1]


# ---------------------------------------------------------------------------
# LM admission behind the scheduler queue
# ---------------------------------------------------------------------------

def test_lm_engine_admission_backpressure_and_priority():
    import jax

    from repro.configs import get_arch
    from repro.models import lm
    from repro.serving.engine import LMEngine, LMRequest

    cfg = get_arch("glm4-9b", smoke=True)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    eng = LMEngine(cfg, params, n_slots=1, max_seq=32, max_pending=2)
    rng = np.random.default_rng(0)

    def req():
        return LMRequest(prompt=rng.integers(0, cfg.vocab_size, 4,
                                             dtype=np.int32),
                         max_new_tokens=2)

    lo, hi = req(), req()
    lo_fut = eng.submit(lo, priority=1)
    hi_fut = eng.submit(hi, priority=0)
    with pytest.raises(RequestRejected) as e:
        eng.submit(req())
    assert e.value.reason == RejectReason.QUEUE_FULL
    done = eng.run()
    assert len(done) == 2
    # single slot: the high-priority request must have been admitted first
    assert done[0] is hi and done[1] is lo
    assert all(len(r.out_tokens) == 2 for r in done)
    # the futures resolve to the finished requests with real latencies
    assert hi_fut.result(0) is hi and lo_fut.result(0) is lo
    assert lo_fut.latency_us >= hi_fut.latency_us > 0.0
