"""One run of one cell: set-up, the measured window, the check, the
result line. ``bench/run.py`` is the command; see its docstring."""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time
import traceback
from typing import Callable, Dict, Optional

from . import check, device, model, profile, spec, traffic

WARM_S = 1.0            # warm-up traffic through the whole path (set-up)
PROFILE_S = 2.0         # device trace length (at most 40% of the window)
PROFILE_TAIL_S = 0.5    # ... ending this long before the window closes
SPAN_CAPACITY = 1 << 22
TRACE_DIR = os.path.join(spec.BENCH_DIR, ".traces")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Context:
    """What a metric reader may read."""

    cell: dict
    cfg: dict
    tr: dict
    seconds: float
    setup_s: float
    served: traffic.Served
    netlist: dict
    peak: dict                      # peaks.json entry of this chip
    host_window: tuple = (0.0, 0.0)  # (t0_us, t1_us): the window up to
    # the device trace, where host spans run free of the profiler
    spans: Optional[list] = None    # repro.obs TraceEvents (traced run)
    trace: Optional[profile.Reduced] = None   # device trace (traced run)


class _Annotated:
    """Executor wrapper for traced runs: each batch the scheduler hands
    down is a ``bench.exec`` host span in the device trace."""

    def __init__(self, inner):
        self._inner = inner
        self.n_features = getattr(inner, "n_features", None)

    def __call__(self, x, deadline_us=None):
        import jax
        with jax.profiler.TraceAnnotation("bench.exec"):
            return self._inner(x, deadline_us=deadline_us)

    def set_tracer(self, tracer) -> None:
        self._inner.set_tracer(tracer)


def _annotate_layers(bitnets, aggregators) -> Callable[[], None]:
    """Mark the pack and the device call of every replica as
    ``bench.pack`` / ``bench.device_exec`` host spans; returns the call
    that takes the marks off again."""
    import jax

    def wrap(fn, name):
        def inner(*a, **kw):
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **kw)
        return inner

    marked = ([(a, "pack_requests", "bench.pack") for a in aggregators]
              + [(b, "classify_packed", "bench.device_exec") for b in bitnets])
    before = [(obj, attr, obj.__dict__.get(attr)) for obj, attr, _ in marked]
    for obj, attr, name in marked:
        setattr(obj, attr, wrap(getattr(obj, attr), name))

    def restore():
        for obj, attr, prev in before:
            if prev is None:
                del obj.__dict__[attr]
            else:
                setattr(obj, attr, prev)
    return restore


class GcWatch:
    """Times the interpreter's garbage collections, by generation."""

    def __init__(self):
        self._t0 = 0.0
        self.ms = {0: [], 1: [], 2: []}
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.ms[info["generation"]].append(
                (time.perf_counter() - self._t0) * 1e3)

    def summary(self) -> str:
        return ", ".join(f"gen{g} n={len(v)} max={max(v, default=0):.1f}ms "
                         f"sum={sum(v):.1f}ms" for g, v in self.ms.items())

    def close(self) -> None:
        gc.callbacks.remove(self._cb)


class CompileCounter:
    """Counts XLA backend compilations while open (a window should have
    none)."""

    def __init__(self):
        self.n = 0

    def _on_event(self, key, secs, **kw):
        if key.endswith("backend_compile_duration"):
            self.n += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_event)


@dataclasses.dataclass
class Built:
    """The served path after set-up (see ``model.build``)."""

    executor: object
    bitnets: list
    weights: dict
    stats: dict


def build(cfg: dict, hook: Optional[Callable] = None) -> Built:
    """Weights, compile to logic, synthesis and kernel warm-up.
    ``hook(executor, bitnets)``, if given, runs last: the fault tests
    break the timed path there."""
    executor, bitnets, weights, stats = model.build(cfg, log)
    if hook is not None:
        hook(executor, bitnets)
    return Built(executor, bitnets, weights, stats)


@dataclasses.dataclass
class Window:
    """One measured window and what was recorded during it."""

    served: traffic.Served
    t_ready: float                  # perf_counter s when warm-up ended
    host_window: tuple              # (t0_us, t1_us) the span readers use
    spans: Optional[list] = None
    trace: Optional[profile.Reduced] = None


def profile_slot(seconds: float) -> tuple:
    """(offset, length) in seconds of the device trace inside a window
    of ``seconds``: near its end, so that the host spans before it are
    read free of the profiler's cost."""
    length = min(PROFILE_S, 0.4 * seconds)
    return seconds - length - min(PROFILE_TAIL_S, 0.1 * seconds), length


def serve_window(built: Built, cfg: dict, tr: dict, seed: int,
                 seconds: float, trace: bool = False,
                 devices: int = 1) -> Window:
    """A scheduler over ``built``, 1 s of warm-up traffic, then the
    traffic ``tr`` for ``seconds``; every request is waited for. With
    ``trace`` the scheduler records ``repro.obs`` spans and the profiler
    traces ``devices`` chips near the window's end."""
    from repro.obs import SpanTracer
    from repro.serve import MicroBatchScheduler, SchedConfig

    pool = traffic.make_pool(tr, seed)
    executor = built.executor
    tracer = SpanTracer(capacity=SPAN_CAPACITY) if trace else None
    if trace:
        aggs = ([r.fn for r in executor.replicas]
                if hasattr(executor, "replicas") else [executor])
        restore = _annotate_layers(built.bitnets, aggs)
        executor = _Annotated(executor)
    sc = cfg["serve"]["sched"]
    sched = MicroBatchScheduler(
        executor, SchedConfig(max_batch=int(sc["max_batch"]),
                              max_queue=int(sc["max_queue"])),
        tracer=tracer)
    sched.start()
    cap = None
    try:
        warm = traffic.drive(sched, pool, tr, seed ^ 0x5EED, WARM_S)
        log(f"warm-up: {warm.n} requests, {len(warm.errors)} unanswered")
        if tracer is not None:
            tracer.clear()
        t_ready = time.perf_counter()
        if trace:
            off, length = profile_slot(seconds)
            cap = profile.Capture(TRACE_DIR, (t_ready + off) * 1e6, length)
        gcw = GcWatch()
        try:
            with CompileCounter() as compiles:
                served = traffic.drive(sched, pool, tr, seed, seconds)
        finally:
            gcw.close()
    finally:
        sched.stop(drain=True)
        if trace:
            restore()
    log(f"window: {served.n} requests, {len(served.errors)} unanswered, "
        f"{compiles.n} compilation(s) inside it")
    log(f"garbage collections in the window: {gcw.summary()}")
    for e in served.errors[:5]:
        log(f"unanswered: {e}")
    spans = tracer.events() if tracer is not None else None
    if tracer is not None and tracer.n_dropped:
        log(f"span ring dropped {tracer.n_dropped} events")
    red = cap.result(devices) if cap is not None else None
    t1 = cap.t_start_us if cap is not None else served.t1_us
    return Window(served, t_ready, (served.t0_us, t1), spans, red)


def run_cell(w: dict, metrics: list, seed: int, seconds: float,
             trace: bool, t_proc0: float, devs,
             hook: Optional[Callable] = None) -> dict:
    """Set up, measure and check one run; returns the result line."""
    cfg, tr = w["cfg"], w["tr"]
    chips = int(w["chips"])
    used = list(devs[:chips])
    peaks = spec.peaks()
    kind = used[0].device_kind
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    readers = {m["name"]: spec.reader(m["name"]) for m in metrics}

    built = build(cfg, hook)
    win = serve_window(built, cfg, tr, seed, seconds, trace, chips)
    setup_s = win.t_ready - t_proc0
    log(f"set-up {setup_s:.3f}s")
    dev = device.describe(devs)
    dev["memory_peak_bytes"] = device.memory_peak(used)
    weights, stats, served = built.weights, built.stats, win.served
    del built
    gc.collect()

    t = time.perf_counter()
    pool = traffic.make_pool(tr, seed)
    ref = spec.reference(cfg).labels(cfg, weights, pool)
    readings = check.compare(served, ref)
    log(f"reference over {pool.shape[0]} pool rows in "
        f"{time.perf_counter() - t:.2f}s; compared "
        f"{readings['compared_labels']} served labels")

    ctx = Context(cell=w, cfg=cfg, tr=tr, seconds=seconds, setup_s=setup_s,
                  served=served, netlist=stats, peak=peaks[kind],
                  host_window=win.host_window, spans=win.spans,
                  trace=win.trace)
    units = {m["name"]: m["unit"] for m in metrics}
    values: Dict[str, dict] = {}
    for name, read in readers.items():
        v = read(ctx)
        if v is None:
            log(f"metric {name}: nothing to read")
            continue
        values[name] = {"value": float(v), "unit": units[name]}
    out = {"correct": check.verdict(readings), "attempted": int(served.n),
           "failed": int(readings["unanswered"]), "metrics": values,
           "device": dev}
    red = win.trace
    if red is not None:
        dev["busy_s"] = red.mean_busy_s()
        dev["window_s"] = red.window_ns * 1e-9
        top = sorted(red.op_seconds().items(), key=lambda kv: -kv[1])
        gaps = sorted(red.idle_gaps().items(), key=lambda kv: -kv[1])
        out["breakdown"] = {"device_ops": [list(kv) for kv in top[:10]],
                            "idle_gaps": [list(kv) for kv in gaps[:10]]}
    out["checks"] = check.table(readings)
    return out


def parse(argv):
    ap = argparse.ArgumentParser(
        prog="bench/run.py",
        description="Run one benchmark cell on the chip and print its "
                    "result line.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, t_proc0: Optional[float] = None) -> int:
    t_proc0 = time.perf_counter() if t_proc0 is None else t_proc0
    args = parse(argv)
    try:
        bench = spec.load_benchmark()
        w = spec.cell(bench, args.workload)
        metrics = spec.metric_entries(bench, w, bool(args.trace))
    except (OSError, KeyError, ValueError) as e:
        log(f"cannot load the cell: {type(e).__name__}: {e}")
        return 2
    # tile geometry is the spec default: no autotune file may change
    # what is compiled
    os.environ["REPRO_AUTOTUNE_CACHE"] = ""
    import jax

    from repro.launch.cache import enable_compile_cache
    log(f"jax {jax.__version__}, compile cache {enable_compile_cache()}")
    try:
        devs = device.require(int(w["chips"]), log)
    except device.NoChip as e:
        log(str(e))
        return 3
    try:
        out = run_cell(w, metrics, args.seed, args.seconds,
                       bool(args.trace), t_proc0, devs)
    except Exception:
        traceback.print_exc()
        return 1
    for k, v in out["checks"].items():
        log(f"check {k}: {v['value']} (limit {v['limit']})")
    print(json.dumps(out), flush=True)
    return 0
