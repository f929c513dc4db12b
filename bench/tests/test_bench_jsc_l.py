"""The JSC-L configuration: a net whose wire plane passes the VMEM
budget, served from its prebuilt netlist on the staged-DMA kernel path,
and the ``lut_eval_ns_per_staged_row`` reader."""
import glob
import importlib
import json
import os
import shutil
import sys
import time
import types

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

from harness import profile, runner, spec  # noqa: E402

SEED = 2 ** 31 + 1919
DATA = os.path.join(BENCH, "tests", "data")
_PEAKS = spec.peaks()


def _cfg(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


def _metric(name):
    return spec.load_module(os.path.join(BENCH, "metrics", name + ".py"),
                            "test_jsc_l_metric_" + name.replace(".", "_"))


def _store(monkeypatch, tmp_path):
    """The test netlists as the store, and no synthesis at set-up."""
    import repro.synth as synth
    from repro.synth import store

    for f in glob.glob(os.path.join(DATA, "netlists", "*.npz")):
        shutil.copy(f, tmp_path)
    monkeypatch.setattr(store, "DEFAULT_ROOT", str(tmp_path))

    def no_synthesis(*a, **kw):
        raise AssertionError("set-up synthesized")
    monkeypatch.setattr(synth, "synthesize", no_synthesis)


def _force_gather(monkeypatch, gather):
    """``"dma"``: a core with no VMEM to spare keeps every plane in HBM;
    ``"vmem"``: the v5e core the CPU suite interprets for."""
    if gather == "dma":
        lut_eval = importlib.import_module("repro.kernels.lut_eval.lut_eval")
        monkeypatch.setattr(lut_eval, "vmem_capacity_bytes",
                            lambda interpret: 0)


@pytest.mark.parametrize("gather", ["vmem", "dma"])
def test_tiny_l_prebuilt_run_is_correct(monkeypatch, tmp_path, gather):
    """A six-layer, fan-in-4, 3-bit net in JSC-L's pattern served from
    a store in ``tmp_path`` through ``runner.run_cell`` agrees with
    ``mlp_reference`` on every label in either gather mode, and its
    ``fetch`` spans say which. The netlist is the one
    ``bench/make_netlist.py --config bench/tests/data/tiny-l.json
    --root bench/tests/data/netlists`` wrote on the CPU."""
    import jax

    _store(monkeypatch, tmp_path)
    _force_gather(monkeypatch, gather)
    kind = jax.devices()[0].device_kind
    monkeypatch.setattr(spec, "peaks",
                        lambda: {kind: _PEAKS["TPU v5 lite"]})
    monkeypatch.setattr(runner, "WARM_S", 0.2)
    monkeypatch.setattr(runner, "TRACE_DIR", str(tmp_path / "traces"))
    windows = []
    serve_window = runner.serve_window

    def kept(*a, **kw):
        windows.append(serve_window(*a, **kw))
        return windows[-1]
    monkeypatch.setattr(runner, "serve_window", kept)
    cell = {"name": "tiny-l.batch", "chips": 1,
            "cfg": _cfg(DATA, "tiny-l.json"),
            "tr": {"loop": "closed", "clients": 2, "rows_per_request": 256,
                   "pool_rows": 4096}}
    metrics = [m for m in spec.load_benchmark()["per_layer"]
               if m["name"] in ("fetch_us.batch",
                                "lut_eval_ns_per_staged_row")]
    out = runner.run_cell(cell, metrics, SEED, 1.0, True,
                          time.perf_counter(), jax.devices())
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["wrong_labels"]["value"] == 0
    assert "fetch_us.batch" in out["metrics"]
    # no chip, so no device time to divide
    assert "lut_eval_ns_per_staged_row" not in out["metrics"]
    fetches = [e.args for e in windows[0].spans
               if e.ph == "X" and e.name == "fetch"]
    assert fetches
    assert {a["gather"] for a in fetches} == {gather}
    (staged,) = {a["staged_rows"] for a in fetches}
    assert (staged > 0) is (gather == "dma")
    (luts,) = {a["luts"] for a in fetches}
    assert luts > 0


def test_tiny_l_gather_modes_write_the_same_plane():
    """The staged-DMA and the resident kernel write the tiny-l netlist's
    whole wire plane bit for bit alike, pad rows included."""
    import jax

    from harness import model
    from repro.core.lutcost import LUT_K
    from repro.kernels.lut_eval import lut_eval_streamed
    from repro.serving.engine import LogicEngine
    from repro.synth import store
    from repro.synth.executor import _compile_plan, compile_tile_plan
    from repro.synth.simulate import random_words

    cfg = _cfg(DATA, "tiny-l.json")
    net = model.to_logic(cfg, model.make_weights(cfg, jax.devices()[0]))
    mapped = store.load(net, LogicEngine.synth_effort, LUT_K,
                        root=os.path.join(DATA, "netlists"))
    tp = compile_tile_plan(_compile_plan(mapped), mapped.n_pis, mapped.k)
    assert tp.n_tiles > 1
    words = random_words(mapped.n_pis, 8, seed=19)
    planes = {g: lut_eval_streamed(words, tp, gather=g, interpret=True)
              for g in ("vmem", "dma")}
    np.testing.assert_array_equal(planes["vmem"], planes["dma"])


def test_jsc_l_flops_per_event():
    # 2 x (32 + 64 + 192 + 192 + 16 + 5) neurons x fan-in 4
    cfg = _cfg(BENCH, "configs", "jsc-l.json")
    assert _metric("serve_mfu").flops_per_event(cfg) == 4008


def test_committed_jsc_l_netlist_loads():
    """The netlist named in ``jsc-l.json`` is in the store, is what its
    name says, and has JSC-L's interface: 16 inputs x 3 bits in, 5
    outputs x 3 bits out, 6-LUTs. Its plan's plane passes a v5e core's
    VMEM budget, so the served kernel stages its leaves by DMA."""
    from repro.check.plan_check import V5E_VMEM_BYTES, gather_mode
    from repro.synth import store
    from repro.synth.executor import _compile_plan, compile_tile_plan
    cfg = _cfg(BENCH, "configs", "jsc-l.json")
    (line,) = [a for a in cfg["assumed"] if "netlists/" in a]
    fp = line.split("netlists/")[1].split(".npz")[0]
    stored, mapped = store.read(store.path_of(fp))
    assert stored == fp
    assert mapped.n_pis == 48 and len(mapped.outputs) == 15
    assert mapped.k == 6 and all(len(l.leaves) <= 6 for l in mapped.luts)
    assert mapped.n_luts == 139_296
    tp = compile_tile_plan(_compile_plan(mapped), mapped.n_pis, mapped.k)
    assert gather_mode(tp, V5E_VMEM_BYTES) == "dma"


def _ctx(spans, ops):
    dev = profile.DeviceTrace("/device:TPU:0", ops, [])
    return types.SimpleNamespace(
        trace=profile.Reduced((0, 10_000_000), [dev], []), spans=spans)


def _fetch(args):
    from repro.obs.trace import TraceEvent
    return TraceEvent(ph="X", name="fetch", cat="exec", ts_us=1.0,
                      dur_us=5.0, tid=0, scope_id=None, args=args)


def test_lut_eval_ns_per_staged_row_reads_spans_and_trace():
    counts = {"luts": 2_000, "tiles": 70, "gather": "dma",
              "staged_rows": 800}
    ops = [("%lut_eval_streamed_pallas.1", 0, 30_000),
           ("%lut_eval_streamed_pallas.1", 50_000, 50_000),
           ("%copy.4", 200_000, 9_000)]
    read = _metric("lut_eval_ns_per_staged_row").read
    # 40 us per call over 800 staged rows
    assert read(_ctx([_fetch(counts), _fetch(counts)], ops)) == (
        pytest.approx(50.0))
    # no device trace
    assert read(types.SimpleNamespace(trace=None,
                                      spans=[_fetch(counts)])) is None
    assert read(_ctx([_fetch(counts)], [("%copy.4", 0, 9_000)])) is None
    # no fetch span carries the counter
    assert read(_ctx([_fetch(None), _fetch({"luts": 2_000})], ops)) is None
    assert read(_ctx([], ops)) is None
    assert read(_ctx(None, ops)) is None
    # a plane resident in VMEM stages nothing
    vmem = dict(counts, gather="vmem", staged_rows=0)
    assert read(_ctx([_fetch(vmem)], ops)) is None
