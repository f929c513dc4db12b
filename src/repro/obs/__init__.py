"""repro.obs — the measurement substrate for the serving stack.

NullaNet Tiny's whole pitch is latency, so latency has to be visible
*with structure*, not just as end-to-end histograms:

  trace      — thread-safe ring-buffer span tracer (injectable clock,
               near-zero overhead when disabled); every request carries
               submit → queue-wait → batch-formation (with flush
               reason) → pack (quantize, bitpack) → dispatch →
               device-exec (h2d, launch) → fetch → scatter spans joined
               by one batch id, beside the dispatch thread's waits, gc
               pauses and compiles. While a ``jax.profiler`` trace is active,
               every thread span is also a ``TraceAnnotation`` of the
               same name, so it lands in the device trace beside the
               ``XLA Ops``;
  export     — Chrome trace-event JSON (opens in Perfetto / chrome://
               tracing) and structured JSONL event export;
  registry   — one counters/gauges/histograms registry that
               ``ServeMetrics``, ``ReplicaSet`` and
               ``BitplaneAggregator`` publish into, with a single
               ``snapshot()`` surface;
  analyze    — trace artifacts back into per-request phase breakdowns
               ("where did the time go"), reconciliation against the
               scheduler-stamped latency, and trace-vs-trace diffing
               (``python -m repro.obs.analyze --trace ...``);
  window     — streaming tumbling/sliding-window aggregation: per-lane
               QPS / p50 / p99 / SLO-attainment *time series* instead
               of one end-of-run snapshot;
  slo        — multi-window SLO burn-rate monitor with alert callbacks,
               the scheduler's optional degradation hook;
  promexport — Prometheus text-exposition rendering of a registry
               snapshot plus a stdlib pull endpoint
               (``launch.serve --metrics-port``).

``benchmarks/loadgen.py --trace PATH`` and
``repro.launch.serve --trace PATH`` wire the tracer through the whole
request path; ``python -m repro.check --passes trace`` validates trace
well-formedness (monotonic spans, no orphans, valid flush reasons).
"""
from .trace import (FLUSH_REASONS, NULL_TRACER, NullTracer, SpanTracer,
                    TraceEvent)
from .export import (load_trace_events, to_chrome_trace, to_jsonl,
                     write_chrome_trace, write_jsonl)
from .registry import Counter, Gauge, MetricsRegistry
from .analyze import TraceReport, analyze_events, analyze_trace
from .window import BucketRing, WindowedMetrics
from .slo import BurnAlert, BurnRateMonitor
from .promexport import MetricsServer, to_prometheus_text

__all__ = [
    "FLUSH_REASONS", "NULL_TRACER", "NullTracer", "SpanTracer",
    "TraceEvent",
    "load_trace_events", "to_chrome_trace", "to_jsonl",
    "write_chrome_trace", "write_jsonl",
    "Counter", "Gauge", "MetricsRegistry",
    "TraceReport", "analyze_events", "analyze_trace",
    "BucketRing", "WindowedMetrics",
    "BurnAlert", "BurnRateMonitor",
    "MetricsServer", "to_prometheus_text",
]
