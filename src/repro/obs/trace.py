"""Thread-safe span tracer for the request lifecycle.

Two event families, mirroring the Chrome trace-event model so export is
a straight mapping:

  * **thread spans** (``ph="X"``) — work done start-to-finish on one
    thread: batch formation, aggregate pack (``quantize``,
    ``bitpack``), device exec (``h2d`` and the launch), the wait for
    the labels (``fetch``; on the completion thread, inside ``collect``,
    when two batches are in flight), scatter, and the dispatch
    thread's ``sched_wait`` for arrivals or a flush deadline.
    Nested calls on the same thread nest in Perfetto by time
    containment, so the aggregator's ``pack``/``device_exec`` spans
    render inside the scheduler's ``exec`` span with no extra plumbing;
    the batch spans also carry the scheduler's ``args.batch`` id, which
    the requests' ``queue_wait`` ends share.
  * **async spans** (``ph="b"/"n"/"e"``) — one per *request*, keyed by
    a tracer-allocated id threaded through ``ServeRequest``/
    ``ServeFuture``: begun retroactively at the request's enqueue
    timestamp when the scheduler first touches it (dispatch / shed /
    drain — the submit fast path records nothing but the id), ended at
    complete/shed/error. Async spans cross threads — enqueue time is
    stamped on the client thread, all recording happens scheduler-side
    — which is exactly what thread spans cannot express.

While a ``jax.profiler`` trace is active, every thread span an enabled
tracer records is also a ``jax.profiler.TraceAnnotation`` of the same
name for its duration, so the program's own spans sit on the device
trace's clock beside the ``XLA Ops``; with no profile running an
annotation is a cheap no-op. ``attach_process_hooks`` adds two
process-wide sources while an enabled tracer is in use: interpreter
garbage collections (``gc``) and XLA backend compiles (``compile``).

Storage is a lock-free ring: events are plain tuples appended to a
``deque(maxlen=capacity)`` and counted with ``itertools.count`` — both
single C calls, atomic under the GIL — so concurrent recorders never
serialize on a mutex and old events fall off the ring (``n_dropped``
counts them). ``TraceEvent`` objects are only materialized on the cold
``events()`` read path. The clock is injectable (``FakeClock`` in
tests); when the tracer is disabled — or the shared ``NULL_TRACER`` is
in use — every record call is a single attribute check, so the serving
hot path pays ~nothing for the instrumentation points it carries.
"""
from __future__ import annotations

import gc
import threading
from collections import deque
from itertools import count as _monotonic_count
from threading import get_ident
from typing import Dict, List, NamedTuple, Optional, Tuple

# batch flush reasons annotated on batch-formation events; the trace
# validation pass (repro.check --passes trace) rejects anything else
FLUSH_REASONS = ("size", "deadline", "max_wait", "drain", "shed")
# why the dispatch thread waits (``sched_wait`` spans): no request is
# queued, or the queued ones are not yet due for a flush
WAIT_REASONS = ("empty", "fill")


class TraceEvent(NamedTuple):
    """One trace record (all times µs, from the tracer's clock).

    ``ph`` is the Chrome trace-event phase: ``X`` complete thread span
    (``dur_us`` set), ``b``/``n``/``e`` async begin/instant/end (keyed
    by ``scope_id``), ``i`` global instant.
    """

    ph: str
    name: str
    cat: str
    ts_us: float
    dur_us: float
    tid: int
    scope_id: Optional[int]
    args: Optional[Dict[str, object]]


class _Span:
    """Context manager recording one thread span on exit (and holding a
    ``TraceAnnotation`` of the same name open while it runs)."""

    __slots__ = ("_tracer", "_name", "_cat", "_args", "_t0", "_ann")

    def __init__(self, tracer: "SpanTracer", name: str, cat: str,
                 args: Optional[dict], t0_us: Optional[float] = None):
        self._tracer = tracer
        self._name = name
        self._cat = cat
        self._args = args
        self._t0 = t0_us

    def __enter__(self) -> "_Span":
        self._ann = self._tracer._annotation(self._name)
        self._ann.__enter__()
        if self._t0 is None:
            self._t0 = self._tracer._now()
        return self

    def __exit__(self, *exc) -> None:
        # inlined tracer.complete(): X spans fire per batch phase on
        # the scheduler thread, so every frame saved is throughput
        tr = self._tracer
        t1 = tr._now()
        self._ann.__exit__(None, None, None)
        next(tr._n)
        tr._buf.append(("X", self._name, self._cat, self._t0,
                        t1 - self._t0, get_ident(), None, self._args))


class _NullSpan:
    """Shared no-op context manager for the disabled paths."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass


_NULL_SPAN = _NullSpan()


class SpanTracer:
    """Ring-buffer span recorder with an injectable clock.

    ``capacity`` bounds memory: the buffer holds the most recent
    ``capacity`` events and ``n_dropped`` counts overwrites. All
    recording methods are thread-safe; ids from ``new_id`` are unique
    per tracer and are what requests carry across threads.
    """

    def __init__(self, clock=None, capacity: int = 1 << 16,
                 enabled: bool = True):
        if clock is None:
            from repro.serve.clock import SystemClock
            clock = SystemClock()
        assert capacity >= 1
        self.clock = clock
        self.enabled = enabled
        self._cap = capacity
        from jax.profiler import TraceAnnotation
        self._annotation = TraceAnnotation
        self._local = threading.local()     # the thread's current batch
        self._hooks = 0                     # attach_process_hooks depth
        self._gc_open: Optional[Tuple[float, object]] = None
        # the hot path is lock-free: deque.append with a maxlen and
        # next() on an itertools.count are both single C calls, atomic
        # under the GIL, so 64 submitter threads recording concurrently
        # never serialize on a mutex. Events are stored as plain tuples
        # and only materialized into TraceEvent on the cold read path.
        self._buf: deque = deque(maxlen=capacity)
        self._n = _monotonic_count()    # total events ever recorded
        self._now = clock.now_us
        self._ids = _monotonic_count(1)
        self._lock = threading.Lock()   # clear only, never the hot path

    # -- ids / time --------------------------------------------------------
    def now_us(self) -> float:
        return self._now()

    def new_id(self) -> int:
        return next(self._ids)

    @property
    def n_recorded(self) -> int:
        # itertools.count exposes its next value through __reduce__;
        # reading it there peeks the total without consuming a tick
        return self._n.__reduce__()[1][0]

    @property
    def n_dropped(self) -> int:
        return max(0, self.n_recorded - self._cap)

    @property
    def batch(self) -> Optional[int]:
        """The id of the batch the calling thread is executing (set by
        the scheduler around its executor call), or None."""
        return getattr(self._local, "batch", None)

    def set_batch(self, batch_id: Optional[int]) -> None:
        self._local.batch = batch_id

    # -- recording ---------------------------------------------------------
    def complete(self, name: str, t0_us: float, t1_us: float,
                 cat: str = "sched", args: Optional[dict] = None) -> None:
        """A finished thread span with explicit endpoints (for spans
        whose start was stamped on another code path)."""
        if not self.enabled:
            return
        next(self._n)
        self._buf.append(("X", name, cat, t0_us, t1_us - t0_us,
                          get_ident(), None, args))

    def span(self, name: str, cat: str = "sched",
             args: Optional[dict] = None, t0_us: Optional[float] = None):
        """``with tracer.span("exec"): ...`` — times the block on the
        current thread; ``t0_us`` starts it at a time stamped earlier
        instead of at entry."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, cat, args, t0_us)

    def instant(self, name: str, cat: str = "sched",
                args: Optional[dict] = None) -> None:
        if not self.enabled:
            return
        next(self._n)
        self._buf.append(("i", name, cat, self._now(), 0.0,
                          get_ident(), None, args))

    def abegin(self, name: str, scope_id: int, cat: str = "request",
               args: Optional[dict] = None,
               ts_us: Optional[float] = None) -> None:
        """Begin the async span ``scope_id`` (one per request)."""
        if not self.enabled:
            return
        next(self._n)
        self._buf.append(
            ("b", name, cat, self._now() if ts_us is None else ts_us,
             0.0, get_ident(), scope_id, args))

    def abegin_nested(self, outer: str, inner: str, scope_id: int,
                      ts_us: float, args: Optional[dict] = None) -> None:
        """Open an outer async span and an inner phase span at the same
        timestamp with one method dispatch — the submit-path fast path
        (``request`` + ``queue_wait``); ``args`` lands on the outer."""
        if not self.enabled:
            return
        next(self._n)
        next(self._n)
        tid = get_ident()
        self._buf.append(("b", outer, "request", ts_us, 0.0, tid,
                          scope_id, args))
        self._buf.append(("b", inner, "request", ts_us, 0.0, tid,
                          scope_id, None))

    def ainstant(self, name: str, scope_id: int, cat: str = "request",
                 args: Optional[dict] = None) -> None:
        if not self.enabled:
            return
        next(self._n)
        self._buf.append(("n", name, cat, self._now(), 0.0,
                          get_ident(), scope_id, args))

    def aend(self, name: str, scope_id: int, cat: str = "request",
             args: Optional[dict] = None,
             ts_us: Optional[float] = None) -> None:
        if not self.enabled:
            return
        next(self._n)
        self._buf.append(("e", name, cat,
                          self._now() if ts_us is None else ts_us, 0.0,
                          get_ident(), scope_id, args))

    # -- process-wide sources ----------------------------------------------
    def attach_process_hooks(self) -> None:
        """Record garbage collections (``gc``, on the collecting thread,
        ``args.generation``) and XLA backend compiles (``compile``, from
        a ``jax.monitoring`` duration listener) as thread spans until
        the matching ``detach_process_hooks``. Counted, so schedulers
        sharing one tracer may each attach; a disabled tracer installs
        nothing."""
        if not self.enabled:
            return
        with self._lock:
            self._hooks += 1
            if self._hooks > 1:
                return
            import jax.monitoring
            gc.callbacks.append(self._on_gc)
            jax.monitoring.register_event_duration_secs_listener(
                self._on_duration)

    def detach_process_hooks(self) -> None:
        with self._lock:
            if self._hooks == 0:
                return
            self._hooks -= 1
            if self._hooks:
                return
            import jax.monitoring
            gc.callbacks.remove(self._on_gc)
            jax.monitoring.unregister_event_duration_listener(
                self._on_duration)

    def _on_gc(self, phase: str, info: dict) -> None:
        # collections never nest, and start and stop run on the same
        # thread, so one open slot suffices
        if phase == "start":
            ann = self._annotation("gc")
            ann.__enter__()
            self._gc_open = (self._now(), ann)
        elif self._gc_open is not None:
            t0, ann = self._gc_open
            self._gc_open = None
            t1 = self._now()
            ann.__exit__(None, None, None)
            self.complete("gc", t0, t1, cat="process",
                          args={"generation": info["generation"]})

    def _on_duration(self, event: str, secs: float, **kw) -> None:
        if event.endswith("backend_compile_duration"):
            t1 = self._now()
            self.complete("compile", t1 - secs * 1e6, t1, cat="process",
                          args={"fun_name": kw.get("fun_name")})

    # -- reading -----------------------------------------------------------
    def events(self) -> List[TraceEvent]:
        """Snapshot of the retained events in recording order."""
        # list(deque) is one atomic C call; the maxlen ring keeps
        # oldest-to-newest order by construction
        return [TraceEvent(*t) for t in list(self._buf)]

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()
            self._n = _monotonic_count()


class NullTracer:
    """Disabled tracer: same surface as ``SpanTracer``, every call a
    no-op. The scheduler default, so untraced serving carries only an
    ``if tracer.enabled`` per instrumentation point."""

    enabled = False
    clock = None

    def now_us(self) -> float:
        return 0.0

    def new_id(self) -> int:
        return 0

    @property
    def n_recorded(self) -> int:
        return 0

    @property
    def n_dropped(self) -> int:
        return 0

    batch = None

    def set_batch(self, batch_id) -> None:
        pass

    def complete(self, name, t0_us, t1_us, cat="sched", args=None) -> None:
        pass

    def span(self, name, cat="sched", args=None, t0_us=None):
        return _NULL_SPAN

    def instant(self, name, cat="sched", args=None) -> None:
        pass

    def abegin(self, name, scope_id, cat="request", args=None,
               ts_us=None) -> None:
        pass

    def abegin_nested(self, outer, inner, scope_id, ts_us,
                      args=None) -> None:
        pass

    def ainstant(self, name, scope_id, cat="request", args=None) -> None:
        pass

    def aend(self, name, scope_id, cat="request", args=None,
             ts_us=None) -> None:
        pass

    def attach_process_hooks(self) -> None:
        pass

    def detach_process_hooks(self) -> None:
        pass

    def events(self) -> List[TraceEvent]:
        return []

    def clear(self) -> None:
        pass


NULL_TRACER = NullTracer()
