"""Spans inside the pack and the device call, the batch id that joins a
batch's spans to its requests, the dispatch thread's waits, gc and
compile spans, and thread spans in a ``jax.profiler`` trace."""
import gc
import glob
import threading
import time

import numpy as np
import pytest

from repro.check.tracecheck import check_phase_reconciliation, check_trace
from repro.obs import SpanTracer, TraceEvent, analyze_events
from repro.obs.trace import NULL_TRACER, WAIT_REASONS
from repro.serve import (BitplaneAggregator, FakeClock, MicroBatchScheduler,
                         SchedConfig)


class TickClock(FakeClock):
    """A FakeClock that moves 1 µs on every read, so that spans stamped
    one after another have lengths and time containment means nesting."""

    def now_us(self) -> float:
        self.advance_us(1.0)
        return super().now_us()


@pytest.fixture(scope="module")
def tiny_net():
    import jax

    from repro.models.mlp import (MLPConfig, final_masks, init_bn_state,
                                  init_mlp_params, to_logic)
    cfg = MLPConfig(name="tiny", n_inputs=6, features=(8, 5),
                    fanins=(3, 3), act_bits=(2, 3), in_bits=2,
                    n_classes=5, alpha=1.0)
    params = init_mlp_params(cfg, jax.random.PRNGKey(3))
    net = to_logic(cfg, params, final_masks(cfg, params),
                   init_bn_state(cfg))
    x = np.random.default_rng(0).normal(size=(96, 6)).astype(np.float32)
    return net, x


def _aggregator(net, engine):
    from repro.synth.executor import BitplaneNetwork
    bn = BitplaneNetwork.from_logic_network(net, engine=engine,
                                            interpret=True)
    return BitplaneAggregator(bn, 5)


def _x(evs, name):
    return [e for e in evs if e.ph == "X" and e.name == name]


def _inside(inner, outer):
    return (outer.ts_us <= inner.ts_us and inner.ts_us + inner.dur_us
            <= outer.ts_us + outer.dur_us and inner.tid == outer.tid)


def _served_trace(net, x, engine):
    clk = TickClock()
    tracer = SpanTracer(clock=clk)
    agg = _aggregator(net, engine)
    s = MicroBatchScheduler(agg, SchedConfig(max_batch=32, max_wait_us=50.0),
                            clock=clk, tracer=tracer)
    futs = [s.submit(x[i]) for i in range(64)]
    assert s.drain() == 64
    want = agg.bitnet.classify(x[:64], 5)
    np.testing.assert_array_equal([int(f.result(0)) for f in futs], want)
    return tracer.events()


@pytest.mark.parametrize("engine,parent,children", [
    ("numpy", "aggregate_pack", ("quantize", "bitpack")),
    # the device call launches the program; its labels are awaited
    # after it (here, serially, inside the same exec span)
    ("pallas-streamed", "exec", ("device_exec", "fetch")),
    ("pallas-streamed", "device_exec", ("h2d",)),
], ids=["pack", "device_call", "launch"])
def test_spans_nest_inside_their_layer(tiny_net, engine, parent, children):
    net, x = tiny_net
    evs = _served_trace(net, x, engine)
    outers = _x(evs, parent)
    assert len(outers) == 2                      # 64 rows, max_batch 32
    for name in children:
        inner = _x(evs, name)
        assert len(inner) == len(outers)
        for i in inner:
            assert sum(_inside(i, o) for o in outers) == 1, name
    # the children are disjoint and in order inside each parent
    for o in outers:
        inner = [next(e for e in _x(evs, n) if _inside(e, o))
                 for n in children]
        for a, b in zip(inner, inner[1:]):
            assert a.ts_us + a.dur_us <= b.ts_us


def test_batch_spans_and_queue_waits_share_one_batch_id(tiny_net):
    net, x = tiny_net
    evs = _served_trace(net, x, "numpy")
    ids = {}
    for name in ("batch_form", "exec", "aggregate_pack", "device_exec",
                 "scatter"):
        spans = _x(evs, name)
        assert len(spans) == 2
        ids[name] = [e.args["batch"] for e in spans]
    assert len(set(map(tuple, ids.values()))) == 1
    first, second = ids["batch_form"]
    assert first is not None and first != second
    waits = [e for e in evs if e.ph == "e" and e.name == "queue_wait"]
    assert len(waits) == 64
    by_batch = {}
    for e in waits:
        by_batch.setdefault(e.args["batch"], []).append(e.scope_id)
    assert sorted(by_batch) == sorted(ids["batch_form"])
    assert [len(v) for v in by_batch.values()] == [32, 32]
    # the analyzer's buffer-order binding agrees with the ids
    rep = analyze_events(evs)
    for b, bid in zip(rep.batches, ids["batch_form"]):
        assert sorted(b.members) == sorted(by_batch[bid])


def test_gc_collect_under_an_enabled_tracer_is_one_gc_span():
    tracer = SpanTracer()
    was = gc.isenabled()
    gc.disable()                    # only the explicit collection below
    try:
        tracer.attach_process_hooks()
        try:
            gc.collect()
        finally:
            tracer.detach_process_hooks()
        gc.collect()                # after detach: not recorded
    finally:
        if was:
            gc.enable()
    spans = _x(tracer.events(), "gc")
    assert len(spans) == 1
    assert spans[0].args == {"generation": 2}
    assert spans[0].tid == threading.get_ident()
    assert spans[0].dur_us >= 0.0


def _jax_listeners():
    from jax._src import monitoring
    return list(monitoring._event_duration_secs_listeners)


@pytest.mark.parametrize("tracer", [None, SpanTracer(enabled=False)],
                         ids=["null_tracer", "disabled_tracer"])
def test_untraced_scheduler_installs_no_hooks(tracer):
    before_gc, before_jax = list(gc.callbacks), _jax_listeners()
    s = MicroBatchScheduler(lambda x: x.sum(axis=-1), tracer=tracer)
    s.start()
    try:
        assert gc.callbacks == before_gc
        assert _jax_listeners() == before_jax
        assert s.submit(np.ones(3, np.float32)).result(5) == 3.0
    finally:
        s.stop()
    assert gc.callbacks == before_gc and _jax_listeners() == before_jax
    # a disabled tracer asked directly installs nothing either
    NULL_TRACER.attach_process_hooks()
    SpanTracer(enabled=False).attach_process_hooks()
    assert gc.callbacks == before_gc and _jax_listeners() == before_jax


def test_traced_scheduler_hooks_live_from_start_to_stop():
    import jax
    import jax.numpy as jnp
    before_gc, before_jax = list(gc.callbacks), _jax_listeners()
    tracer = SpanTracer()
    s = MicroBatchScheduler(lambda x: x.sum(axis=-1), tracer=tracer)
    s.start()
    try:
        assert len(gc.callbacks) == len(before_gc) + 1
        assert len(_jax_listeners()) == len(before_jax) + 1
        jax.jit(lambda v: v * 7 - 3)(jnp.arange(11.0)).block_until_ready()
    finally:
        s.stop()
    assert gc.callbacks == before_gc and _jax_listeners() == before_jax
    comp = _x(tracer.events(), "compile")
    assert comp and all(e.dur_us > 0 and e.cat == "process" for e in comp)


def test_sched_wait_spans_on_the_dispatch_thread():
    tracer = SpanTracer()
    s = MicroBatchScheduler(lambda x: x.sum(axis=-1),
                            SchedConfig(max_batch=64, max_wait_us=20_000.0),
                            tracer=tracer)
    s.start()
    tid = s._thread.ident
    try:
        time.sleep(0.05)                         # nothing queued: empty
        futs = [s.submit(np.ones(3, np.float32)) for _ in range(3)]
        for f in futs:                           # waits out max_wait: fill
            assert f.result(5) == 3.0
        time.sleep(0.02)
    finally:
        s.stop()
    evs = tracer.events()
    waits = _x(evs, "sched_wait")
    assert waits and all(e.tid == tid for e in waits)
    reasons = {e.args["reason"] for e in waits}
    assert reasons == set(WAIT_REASONS)
    fill = [e for e in waits if e.args["reason"] == "fill"]
    assert max(e.dur_us for e in fill) > 10_000.0
    # waits never overlap the batch they precede, and the whole trace
    # passes the trace checks
    (form,) = _x(evs, "batch_form")
    assert all(e.ts_us + e.dur_us <= form.ts_us
               or e.ts_us >= form.ts_us + form.dur_us for e in waits)
    rep = check_trace(evs)
    check_phase_reconciliation(evs, report=rep)
    assert rep.ok, rep.format()


def test_threaded_traced_serving_passes_the_trace_checks(tiny_net):
    net, x = tiny_net
    tracer = SpanTracer()
    s = MicroBatchScheduler(_aggregator(net, "numpy"),
                            SchedConfig(max_batch=32, max_wait_us=500.0),
                            tracer=tracer)
    s.start()
    try:
        futs = [s.submit(x[i]) for i in range(48)]
        gc.collect()
        labels = [int(f.result(10)) for f in futs]
        futs = [s.submit(x[i: i + 8]) for i in range(0, 48, 8)]
        for f in futs:
            f.result(10)
    finally:
        s.stop()
    assert len(labels) == 48
    evs = tracer.events()
    assert _x(evs, "gc") and _x(evs, "sched_wait") and _x(evs, "bitpack")
    rep = check_trace(evs)
    check_phase_reconciliation(evs, report=rep)
    assert rep.ok, rep.format()
    assert not rep.errors


def test_trace_check_rejects_bad_batch_free_spans():
    evs = [TraceEvent("X", "sched_wait", "sched", 0.0, 5.0, 1, None,
                      {"reason": "bored"}),
           TraceEvent("X", "gc", "process", 10.0, 2.0, 1, None,
                      {"generation": 0, "batch": 4})]
    rep = check_trace(evs)
    codes = {i.code for i in rep.errors}
    assert codes == {"bad-wait-reason", "batch-on-unbatched"}


def test_thread_span_lands_in_the_profiler_trace(tmp_path):
    import jax
    tracer = SpanTracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracer.span("obs_probe_span", cat="test"):
            time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    hits = [ev for plane in pd.planes if plane.name.startswith("/host")
            for line in plane.lines for ev in line.events
            if ev.name == "obs_probe_span"]
    assert len(hits) == 1
    assert 5e6 < hits[0].duration_ns < 5e9
    (span,) = _x(tracer.events(), "obs_probe_span")
    assert span.dur_us == pytest.approx(hits[0].duration_ns * 1e-3, rel=0.5)
