#!/usr/bin/env python3
"""Smoke run of the served logic path on a TPU. Not a benchmark.

One process, through the entry points a user calls: train JSC-S with
the serving launcher's defaults, compile it to fixed-function logic,
synthesize the mapped 6-LUT netlist, and serve seeded test rows through
``MicroBatchScheduler`` -> ``BitplaneAggregator`` -> the compiled
``kernels/lut_eval`` engine (``pallas-streamed``). Every label must
equal the numpy host fold's on the same netlist, and the engine must
have run as a compiled Mosaic kernel, not in the Pallas interpreter.

    python chip_smoke.py             # one chip, one replica
    python chip_smoke.py --chips 4   # four one-chip replicas vs one

Exits non-zero unless JAX's first device is a TPU. The last line of
standard output is the JSON verdict; nothing is printed there on failure.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

N_REQUESTS = 4096
MAX_BATCH = 256          # loadgen's serving batch: pad_rows -> W = 8 words
TRAIN_STEPS = 400        # repro.launch.serve --train-steps default
SEED = 0
ENGINE = "pallas-streamed"


def check_device(n_chips: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    print(f"[smoke] devices: {devs}")
    print(f"[smoke] platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    if d.platform != "tpu":
        raise SystemExit(f"[smoke] no TPU: JAX's first device is "
                         f"{d.platform!r}; this script has no CPU fallback")
    if len(devs) < n_chips:
        raise SystemExit(f"[smoke] --chips {n_chips} needs {n_chips} "
                         f"devices, JAX sees {len(devs)}")
    return d


def train_logic_net():
    """JSC-S trained as ``launch.serve`` does, compiled to logic."""
    from repro.configs.jsc import JSC_S
    from repro.data.jsc import train_test
    from repro.models.mlp import to_logic
    from repro.train.jsc_trainer import train_jsc

    t0 = time.perf_counter()
    data = train_test(seed=SEED)
    res = train_jsc(JSC_S, steps=TRAIN_STEPS, seed=SEED, data=data)
    net = to_logic(JSC_S, res.params, res.masks, res.bn_state)
    print(f"[smoke] trained JSC-S {TRAIN_STEPS} steps in "
          f"{time.perf_counter() - t0:.1f}s: float test acc "
          f"{res.test_acc:.4f}", flush=True)
    (_, _), (xte, yte) = data
    reps = -(-N_REQUESTS // xte.shape[0])
    xs = np.tile(xte, (reps, 1))[:N_REQUESTS]
    ys = np.tile(yte, reps)[:N_REQUESTS]
    return JSC_S, net, xs, ys


def serve(executor, xs):
    """Every row one request through the threaded micro-batch scheduler;
    returns (labels, scheduler metrics snapshot)."""
    from repro.serve import MicroBatchScheduler, SchedConfig

    sched = MicroBatchScheduler(executor, SchedConfig(
        max_batch=MAX_BATCH, max_queue=2 * len(xs), n_priorities=1))
    sched.start()
    futs = [sched.submit(x) for x in xs]
    sched.stop(drain=True)
    labels = np.array([int(f.result(timeout=60)) for f in futs], np.int32)
    return labels, sched.metrics.snapshot()


def numpy_labels(bitnet, xs, n_classes: int):
    """The host-fold reference on the very netlist ``bitnet`` serves."""
    from repro.synth.executor import BitplaneNetwork
    ref = BitplaneNetwork(bitnet.net, bitnet.mapped, engine="numpy")
    return ref.classify(xs, n_classes)


def require_compiled(bitnet, n_classes: int):
    """The engine ran its Mosaic kernel: not interpreted, the gather
    mode the plan's size gives, and a ``tpu_custom_call`` in the
    lowered classify program."""
    ex = bitnet.executor
    if ex.interpret is not False:
        raise AssertionError(f"{bitnet.engine}: interpret={ex.interpret}")
    from repro.kernels.lut_eval.lut_eval import default_gather
    want = default_gather(ex.tp, False, ex.spec.tile.block_w)
    if ex.gather != want:
        raise AssertionError(f"{bitnet.engine}: gather={ex.gather}, "
                             f"the plan's size gives {want}")
    words = ex._put(np.zeros((bitnet.mapped.n_pis, MAX_BATCH // 32),
                             np.int32))
    hlo = ex._argmax_words.lower(words, n_classes=n_classes).as_text()
    if "tpu_custom_call" not in hlo:
        raise AssertionError(f"{bitnet.engine}: no tpu_custom_call in HLO")


def report(tag, labels, ref, ys, snap):
    same = int((labels == ref).sum())
    print(f"[smoke] {tag}: {len(labels)} requests, labels == numpy "
          f"{same}/{len(labels)}, acc {float((labels == ys).mean()):.4f}, "
          f"batches {snap['n_batches']}, p50 {snap['p50_us']:.1f}us "
          f"p99 {snap['p99_us']:.1f}us (smoke run, not a benchmark)",
          flush=True)
    if same != len(labels):
        raise AssertionError(f"{tag}: {len(labels) - same} labels differ "
                             f"from the numpy engine")


def one_chip():
    from repro.serving.engine import LogicEngine

    cfg, net, xs, ys = train_logic_net()
    t0 = time.perf_counter()
    eng = LogicEngine(net, cfg.n_classes, max_batch=MAX_BATCH,
                      backend="bitplane", engine=ENGINE)
    print(f"[smoke] {ENGINE}: {eng.bitnet.mapped.n_luts} LUTs, depth "
          f"{eng.bitnet.mapped.depth}, gather {eng.bitnet.executor.gather}, "
          f"synthesized and warmed in {time.perf_counter() - t0:.1f}s",
          flush=True)
    require_compiled(eng.bitnet, cfg.n_classes)
    ref = numpy_labels(eng.bitnet, xs, cfg.n_classes)
    labels, snap = serve(eng.scheduler_executor(), xs)
    report(f"engine={ENGINE}", labels, ref, ys, snap)


def four_chips():
    import jax

    from repro.serve import build_logic_replicas

    cfg, net, xs, ys = train_logic_net()
    runs = {}
    for n in (1, 4):
        rs = build_logic_replicas(net, cfg.n_classes, n_replicas=n,
                                  backend="bitplane", max_batch=MAX_BATCH,
                                  engine=ENGINE)
        nets = [r.fn.bitnet for r in rs.replicas]
        for bn in nets:
            require_compiled(bn, cfg.n_classes)
        labels, snap = serve(rs, xs)
        ref = numpy_labels(nets[0], xs, cfg.n_classes)
        report(f"replicas={n}", labels, ref, ys, snap)
        runs[n] = labels
        if n == 4:
            want = jax.devices()[:4]
            words = np.zeros((nets[0].mapped.n_pis, MAX_BATCH // 32),
                             np.uint32)
            for st, bn in zip(rs.stats(), nets):
                out = bn.executor.device_labels(words, cfg.n_classes)
                print(f"[smoke] replica {st['rid']}: served {st['served']} "
                      f"batches on {bn.device}, output committed="
                      f"{out.committed} on {sorted(out.devices(), key=str)}",
                      flush=True)
                if st["served"] == 0:
                    raise AssertionError(f"replica {st['rid']} served none")
                if not out.committed or out.devices() != {bn.device}:
                    raise AssertionError(f"replica {st['rid']} output not "
                                         f"committed on {bn.device}")
            if [bn.device for bn in nets] != list(want):
                raise AssertionError(f"replica devices "
                                     f"{[bn.device for bn in nets]} != "
                                     f"{list(want)}")
    same = int((runs[4] == runs[1]).sum())
    print(f"[smoke] replicas=4 labels == replicas=1 labels "
          f"{same}/{len(runs[1])}", flush=True)
    if same != len(runs[1]):
        raise AssertionError("four replicas disagree with one replica")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-replica path and the "
                         "one-replica run it is compared with")
    args = ap.parse_args(argv)

    import jax

    from repro.launch.cache import enable_compile_cache
    print(f"[smoke] jax {jax.__version__}, compile cache "
          f"{enable_compile_cache()}")
    d = check_device(args.chips)
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    print(f"[smoke] all phases passed in {time.perf_counter() - t0:.1f}s",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
