"""Serving launcher: logic-network classification or LM decode.

  # paper's product: compiled fixed-function logic serving
  PYTHONPATH=src python -m repro.launch.serve --mode logic --jsc jsc-s

  # async micro-batching scheduler with 2 replicas under open-loop load,
  # mapped netlist executed on-device via the kernels/lut_eval kernel
  PYTHONPATH=src python -m repro.launch.serve --mode logic --sched \
      --replicas 2 --loadgen open --qps 20000 --backend bitplane \
      --engine pallas-streamed

  # continuous-batching LM decode on a smoke config
  PYTHONPATH=src python -m repro.launch.serve --mode lm --arch glm4-9b \
      --smoke --requests 8
"""
from __future__ import annotations

import argparse
import os
import sys

import jax
import numpy as np

from repro.configs import get_arch

# benchmarks/ lives at the repo root, one level above src/
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def serve_logic(jsc_name: str, train_steps: int, n_requests: int,
                use_pallas: bool, backend: str = "gather",
                engine: str = "numpy", sched: bool = False,
                replicas: int = 1, qps: float = None, loadgen: str = None,
                slo_us: tuple = None, check: bool = False,
                trace: str = None, metrics_port: int = None):
    from repro.configs.jsc import JSC
    from repro.data.jsc import train_test
    from repro.models.mlp import to_logic
    from repro.serving.engine import LogicEngine
    from repro.train.jsc_trainer import train_jsc

    cfg = JSC[jsc_name]
    print(f"[serve] training {jsc_name} with QAT+FCP ({train_steps} steps)")
    res = train_jsc(cfg, steps=train_steps)
    print(f"  test acc: {res.test_acc:.4f}")
    print("[serve] compiling to fixed-function logic ...")
    net = to_logic(cfg, res.params, res.masks, res.bn_state)
    if backend == "bitplane":
        print(f"[serve] synthesizing mapped 6-LUT netlist (repro.synth, "
              f"engine={engine}) ...")
    eng = LogicEngine(net, cfg.n_classes, use_pallas=use_pallas,
                      backend=backend, engine=engine)
    if backend == "bitplane":
        print(f"  mapped: {eng.bitnet.mapped.n_luts} LUTs, "
              f"depth {eng.bitnet.mapped.depth}")
    if check:
        # preflight: refuse to serve a netlist that fails lint, plan
        # validation, or the valid-code equivalence spot-check
        from repro.check import preflight
        if backend != "bitplane":
            print("[serve] --check: nothing to verify for backend "
                  f"{backend!r} (mapped-netlist checks need --backend "
                  f"bitplane)")
        else:
            rep = preflight(eng.bitnet)
            print(rep.format())
            if not rep.ok:
                raise SystemExit(2)
    (_, _), (xte, yte) = train_test()

    # pull-based metrics endpoint (Prometheus text exposition on
    # /metrics, raw snapshot on /metrics.json), alive for the duration
    # of the serving run; daemon thread, so an exception path cannot
    # wedge process exit
    mserver = None
    registry = None
    if metrics_port is not None:
        from repro.obs import MetricsRegistry, MetricsServer
        registry = MetricsRegistry()
        mserver = MetricsServer(registry, port=metrics_port)
        print(f"[serve] metrics endpoint: {mserver.url}")

    if loadgen:                         # full benchmark harness
        if _REPO_ROOT not in sys.path:
            sys.path.insert(0, _REPO_ROOT)
        from benchmarks import loadgen as lg
        out = lg.run(fast=True, backends=(backend,), n_requests=n_requests,
                     qps=qps, loadgen=loadgen, n_replicas=replicas,
                     steps=train_steps, engine=engine, slo_us=slo_us,
                     trace=trace, registry=registry)
        rec = out["backends"][backend]
        mode = "open_loop" if "open_loop" in rec else "closed_loop"
        print(f"[serve] {mode}: {rec[mode]['qps']:.0f} qps "
              f"p95={rec[mode]['p95_us']:.1f}us "
              f"occ={rec[mode]['mean_batch_occupancy']:.2f}")
        if "slo_lanes" in rec:
            for lane, lr in rec["slo_lanes"]["lanes"].items():
                print(f"[serve] slo lane {lane} "
                      f"({rec['slo_lanes']['slo_us'][int(lane)]:.0f}us): "
                      f"attainment={lr['slo_attainment']:.3f} "
                      f"miss_rate={lr['deadline_miss_rate']:.3f} "
                      f"shed={lr['shed']} p99={lr['p99_us']:.0f}us")
        if mserver is not None:
            mserver.close()
        return rec

    tracer = None
    if trace:
        from repro.obs import SpanTracer
        tracer = SpanTracer()

    if sched:                           # scheduler + replica dispatch
        from repro.serve import (MicroBatchScheduler, RequestRejected,
                                 SchedConfig, build_logic_replicas)
        executor = eng.scheduler_executor()
        if replicas > 1:                # independent data-parallel engines
            executor = build_logic_replicas(
                net, cfg.n_classes, n_replicas=replicas, backend=backend,
                max_batch=eng.max_batch,
                policy="least_slack" if slo_us else "least_loaded",
                engine=engine)
        s = MicroBatchScheduler(
            executor, SchedConfig(max_batch=eng.max_batch,
                                  max_queue=4 * n_requests * 64,
                                  n_priorities=max(2, len(slo_us or ())),
                                  lane_slo_us=slo_us),
            tracer=tracer)
        if registry is not None:        # live pull endpoint content
            from repro.obs import WindowedMetrics
            s.metrics.publish(registry, "serve")
            if hasattr(executor, "publish"):
                executor.publish(registry)
            wm = WindowedMetrics()
            s.metrics.add_sink(wm)
            wm.publish(registry, "windows")
        s.start()
        futs = [s.submit(xte[i % xte.shape[0]])
                for i in range(n_requests * 64)]
        s.stop(drain=True)
        got = np.full((len(futs),), -1, np.int32)
        for i, f in enumerate(futs):
            try:
                got[i] = int(f.result(timeout=30))
            except RequestRejected:
                pass                    # shed past its lane SLO
        served = got >= 0
        acc = float(np.mean(
            got[served] == yte[np.arange(len(got)) % yte.shape[0]][served]
        )) if served.any() else 0.0
        snap = s.metrics.snapshot()
        if tracer is not None:
            _export_trace(trace, tracer, s, executor)
        print(f"[serve] sched x{replicas}: {len(futs)} requests "
              f"acc={acc:.4f} p50={snap['p50_us']:.1f}us "
              f"p95={snap['p95_us']:.1f}us qps={snap['qps']:.0f} "
              f"occ={snap['mean_batch_occupancy']:.2f} "
              f"shed={snap['shed']} "
              f"miss_rate={snap['deadline_miss_rate']:.3f}")
        if mserver is not None:
            mserver.close()
        return snap

    reqs = [xte[i * 64: (i + 1) * 64] for i in range(n_requests)]
    results, stats = eng.serve_queue(reqs, tracer=tracer)
    if tracer is not None:
        from repro.obs import write_chrome_trace
        write_chrome_trace(trace, tracer)
        print(f"[serve] trace: {tracer.n_recorded} events -> {trace}")
    acc = float(np.mean(np.concatenate(results)
                        == yte[: sum(len(r) for r in reqs)]))
    print(f"[serve] {n_requests} requests: acc={acc:.4f} "
          f"p50={stats['p50_us']:.1f}us p95={stats['p95_us']:.1f}us")
    if mserver is not None:
        mserver.close()
    return stats


def _export_trace(path: str, tracer, sched, executor) -> None:
    """Write the Chrome trace with a full metrics-registry snapshot as
    ``otherData`` (scheduler metrics + replica/aggregator stats)."""
    from repro.obs import MetricsRegistry, write_chrome_trace

    reg = MetricsRegistry()
    sched.metrics.publish(reg, "serve")
    if hasattr(executor, "publish"):
        executor.publish(reg)
    write_chrome_trace(path, tracer, other_data=reg.snapshot())
    print(f"[serve] trace: {tracer.n_recorded} events "
          f"({tracer.n_dropped} dropped) -> {path}")


def serve_lm(arch: str, smoke: bool, n_requests: int, max_new: int):
    from repro.models import lm
    from repro.serve.clock import SystemClock
    from repro.serving.engine import LMEngine, LMRequest

    clock = SystemClock()
    cfg = get_arch(arch, smoke=smoke)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    eng = LMEngine(cfg, params, n_slots=4, max_seq=256, clock=clock)
    rng = np.random.default_rng(0)
    reqs = [LMRequest(prompt=rng.integers(0, cfg.vocab_size, 32,
                                          dtype=np.int32),
                      max_new_tokens=max_new) for _ in range(n_requests)]
    t0_us = clock.now_us()
    done = eng.run(reqs)
    dt = (clock.now_us() - t0_us) * 1e-6
    tok = sum(len(r.out_tokens) for r in done)
    print(f"[serve] {len(done)} requests, {tok} tokens in {dt:.2f}s "
          f"({tok/dt:.1f} tok/s)")
    return done


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["logic", "lm"], default="logic")
    ap.add_argument("--jsc", default="jsc-s")
    ap.add_argument("--arch", default="glm4-9b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--train-steps", type=int, default=400)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--pallas", action="store_true")
    ap.add_argument("--backend", choices=["gather", "pallas", "bitplane"],
                    default="gather",
                    help="logic inference path (bitplane = mapped netlist)")
    from repro.synth import ENGINES
    ap.add_argument("--engine", choices=ENGINES, default="numpy",
                    help="bitplane netlist executor: the host fold "
                         "(numpy) or the kernels/lut_eval device engine "
                         "(pallas-streamed)")
    ap.add_argument("--sched", action="store_true",
                    help="serve through the repro.serve micro-batch "
                         "scheduler instead of the blocking loop")
    ap.add_argument("--replicas", type=int, default=1,
                    help="data-parallel engine replicas behind the "
                         "scheduler (least-loaded dispatch)")
    ap.add_argument("--qps", type=float, default=None,
                    help="offered open-loop arrival rate for --loadgen")
    ap.add_argument("--loadgen", choices=["open", "closed", "both"],
                    default=None,
                    help="drive the scheduler with the benchmarks/"
                         "loadgen.py harness and report p50/p95/p99+QPS")
    ap.add_argument("--slo-us", default=None,
                    help="comma list of per-lane SLO deadline budgets in "
                         "µs (lane 0 first, e.g. '100,1000'); requests "
                         "past their lane budget are shed with a typed "
                         "DEADLINE_EXCEEDED reject")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record the request lifecycle with repro.obs and "
                         "write a Chrome trace-event JSON (open in "
                         "ui.perfetto.dev) with the metrics-registry "
                         "snapshot embedded as otherData")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve a pull-based metrics endpoint on this "
                         "port for the duration of the run: Prometheus "
                         "text exposition on /metrics, raw registry "
                         "snapshot on /metrics.json (0 = ephemeral port, "
                         "printed at startup)")
    ap.add_argument("--check", action="store_true",
                    help="repro.check preflight before serving (bitplane "
                         "backend): netlist lint, DevicePlan validation, "
                         "mapped<->plan miter, valid-code equivalence; "
                         "exit 2 on any error")
    args = ap.parse_args(argv)
    slo_us = (tuple(float(v) for v in args.slo_us.split(","))
              if args.slo_us else None)
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    if args.mode == "logic":
        serve_logic(args.jsc, args.train_steps, args.requests, args.pallas,
                    backend=args.backend, engine=args.engine,
                    sched=args.sched, replicas=args.replicas, qps=args.qps,
                    loadgen=args.loadgen, slo_us=slo_us, check=args.check,
                    trace=args.trace, metrics_port=args.metrics_port)
    else:
        serve_lm(args.arch, args.smoke, args.requests, args.max_new)


if __name__ == "__main__":
    main()
