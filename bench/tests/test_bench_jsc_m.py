"""The JSC-M configuration: its netlist built ahead of time, the
``bitplane-prebuilt`` served path against the plain reference, and the
``lut_eval_ns_per_lut`` reader."""
import glob
import json
import os
import shutil
import sys
import time
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

from harness import profile, runner, spec  # noqa: E402

SEED = 2 ** 31 + 1515
DATA = os.path.join(BENCH, "tests", "data")
_PEAKS = spec.peaks()


def _cfg(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


def _metric(name):
    return spec.load_module(os.path.join(BENCH, "metrics", name + ".py"),
                            "test_jsc_m_metric_" + name.replace(".", "_"))


def test_tiny_m_prebuilt_run_is_correct(monkeypatch, tmp_path):
    """A 3-bit, fan-in-4 net served from a store in ``tmp_path``
    through ``runner.run_cell`` agrees with ``mlp_reference`` on every
    label, and set-up never synthesizes. The netlist is the one
    ``bench/make_netlist.py --config bench/tests/data/tiny-m.json
    --root bench/tests/data/netlists`` wrote on the CPU (its synthesis
    takes about two minutes)."""
    import jax

    import repro.synth as synth
    from repro.synth import store

    for f in glob.glob(os.path.join(DATA, "netlists", "*.npz")):
        shutil.copy(f, tmp_path)
    monkeypatch.setattr(store, "DEFAULT_ROOT", str(tmp_path))

    def no_synthesis(*a, **kw):
        raise AssertionError("set-up synthesized")
    monkeypatch.setattr(synth, "synthesize", no_synthesis)
    kind = jax.devices()[0].device_kind
    monkeypatch.setattr(spec, "peaks",
                        lambda: {kind: _PEAKS["TPU v5 lite"]})
    monkeypatch.setattr(runner, "WARM_S", 0.2)
    cell = {"name": "tiny-m.batch", "chips": 1,
            "cfg": _cfg(DATA, "tiny-m.json"),
            "tr": {"loop": "closed", "clients": 2, "rows_per_request": 256,
                   "pool_rows": 4096}}
    metrics = [m for m in spec.load_benchmark()["end_to_end"]
               if m["name"] in ("events_per_s", "setup_s")]
    out = runner.run_cell(cell, metrics, SEED, 1.0, False,
                          time.perf_counter(), jax.devices())
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["wrong_labels"]["value"] == 0


def test_jsc_m_flops_per_event():
    # 2 x (64 + 32 + 32 + 32 + 5) neurons x fan-in 4
    cfg = _cfg(BENCH, "configs", "jsc-m.json")
    assert _metric("serve_mfu").flops_per_event(cfg) == 1320


def test_committed_jsc_m_netlist_loads():
    """The netlist named in ``jsc-m.json`` is in the store, is what its
    name says, and has JSC-M's interface: 16 inputs x 3 bits in, 5
    outputs x 3 bits out, 6-LUTs."""
    from repro.synth import store
    cfg = _cfg(BENCH, "configs", "jsc-m.json")
    (line,) = [a for a in cfg["assumed"] if "netlists/" in a]
    fp = line.split("netlists/")[1].split(".npz")[0]
    stored, mapped = store.read(store.path_of(fp))
    assert stored == fp
    assert mapped.n_pis == 48 and len(mapped.outputs) == 15
    assert mapped.k == 6 and all(len(l.leaves) <= 6 for l in mapped.luts)
    assert mapped.n_luts == 60_415


def _ctx(spans, ops):
    dev = profile.DeviceTrace("/device:TPU:0", ops, [])
    return types.SimpleNamespace(
        trace=profile.Reduced((0, 10_000_000), [dev], []), spans=spans)


def test_lut_eval_ns_per_lut_reads_spans_and_trace():
    from repro.obs.trace import TraceEvent

    def fetch(args):
        return TraceEvent(ph="X", name="fetch", cat="exec", ts_us=1.0,
                          dur_us=5.0, tid=0, scope_id=None, args=args)
    counts = {"luts": 2_000, "tiles": 70, "staged_rows": 900}
    ops = [("%lut_eval_streamed_pallas.1", 0, 30_000),
           ("%lut_eval_streamed_pallas.1", 50_000, 50_000),
           ("%copy.4", 200_000, 9_000)]
    read = _metric("lut_eval_ns_per_lut").read
    # 40 us per call over 2,000 LUTs
    assert read(_ctx([fetch(counts), fetch(counts)], ops)) == pytest.approx(
        20.0)
    assert read(_ctx([fetch(None), fetch({"batch": 3})], ops)) is None
    assert read(_ctx([], ops)) is None
    assert read(_ctx([fetch(counts)], [("%copy.4", 0, 9_000)])) is None
    assert read(types.SimpleNamespace(trace=None,
                                      spans=[fetch(counts)])) is None
