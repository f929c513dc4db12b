"""bench/run.py end to end on the CPU: it refuses to measure without a
TPU, and its check catches a broken timed path and the control."""
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

from harness import runner, spec  # noqa: E402

SEED = 2 ** 31 + 777
_PEAKS = spec.peaks()


def _run_cli(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, script, "--workload", "jsc-s.batch", "--seed",
         str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return False
        except (ValueError, TypeError):
            pass
    return True


def test_run_exits_nonzero_without_tpu():
    p = _run_cli(ROOT, os.path.join(BENCH, "run.py"))
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "not a TPU" in p.stderr


def test_run_exits_nonzero_in_bare_checkout(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".traces", ".scratch",
                                                  "__pycache__"))
    p = _run_cli(tmp_path, os.path.join("bench", "run.py"))
    assert p.returncode != 0
    assert _no_result(p.stdout)


def _tiny_cell():
    with open(os.path.join(BENCH, "tests", "data", "tiny.json")) as f:
        cfg = json.load(f)
    return {"name": "tiny.batch", "chips": 1, "cfg": cfg,
            "tr": {"loop": "closed", "clients": 2, "rows_per_request": 256,
                   "pool_rows": 4096}}


def _alter_one_label(executor, bitnets):
    for bn in bitnets:
        inner = bn.classify_packed

        def altered(words, n_rows, n_classes, inner=inner):
            lab = np.array(inner(words, n_rows, n_classes))
            lab[0] = (lab[0] + 1) % n_classes
            return lab
        bn.classify_packed = altered


def _drop_half_batch(executor, bitnets):
    for bn in bitnets:
        inner = bn.classify_packed

        def half(words, n_rows, n_classes, inner=inner):
            # the second half of the lanes is never evaluated: its rows
            # get the first half's answers
            lab = np.array(inner(words, n_rows, n_classes))
            h = n_rows // 2
            lab[h: 2 * h] = lab[:h]
            return lab
        bn.classify_packed = half


@pytest.mark.parametrize("fault,want", [(None, True),
                                        (_alter_one_label, False),
                                        (_drop_half_batch, False)],
                         ids=["sound", "answer_altered", "half_batch_left_out"])
def test_check_catches_broken_timed_path(monkeypatch, fault, want):
    import jax
    kind = jax.devices()[0].device_kind
    monkeypatch.setattr(spec, "peaks",
                        lambda: {kind: _PEAKS["TPU v5 lite"]})
    monkeypatch.setattr(runner, "WARM_S", 0.2)
    bench = spec.load_benchmark()
    metrics = [m for m in bench["end_to_end"]
               if m["name"] in ("events_per_s", "setup_s")]
    out = runner.run_cell(_tiny_cell(), metrics, SEED, 1.0, False,
                          time.perf_counter(), jax.devices(), hook=fault)
    assert out["correct"] is want
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert (out["checks"]["wrong_labels"]["value"] == 0) is want
    assert set(out["metrics"]) == {"events_per_s", "setup_s"}


def test_traced_run_reads_spans_before_the_device_trace(monkeypatch,
                                                       tmp_path):
    """A traced run on the CPU: the host-span readers report, the
    device-trace readers find no chip and are left out, and the result
    carries the trace's window and breakdown."""
    import jax
    kind = jax.devices()[0].device_kind
    monkeypatch.setattr(spec, "peaks",
                        lambda: {kind: _PEAKS["TPU v5 lite"]})
    monkeypatch.setattr(runner, "WARM_S", 0.2)
    monkeypatch.setattr(runner, "TRACE_DIR", str(tmp_path / "traces"))
    bench = spec.load_benchmark()
    metrics = [m for m in bench["per_layer"]
               if "jsc-s.batch" in m.get("workloads", [])]
    out = runner.run_cell(_tiny_cell(), metrics, SEED, 2.0, True,
                          time.perf_counter(), jax.devices())
    assert out["correct"] is True
    assert {"pack_us.batch", "device_exec_us.batch",
            "serve_mfu"} <= set(out["metrics"])
    assert "lut_eval_device_us" not in out["metrics"]
    assert 0.0 < out["device"]["window_s"] < 1.0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(out)[-1] == "checks"


def test_profile_slot_ends_inside_the_window():
    for seconds in (1.0, 5.0, 30.0):
        off, length = runner.profile_slot(seconds)
        assert 0.0 < off and 0.0 < length <= 0.4 * seconds
        assert off + length < seconds


def test_control_in_lower_precision_fails_the_check():
    """The reference in bfloat16 (the control) disagrees with the
    float64 reference on JSC-S's weights; float64 agrees with the
    program's netlist on the same rows."""
    import jax
    import ml_dtypes

    from harness import check, model, traffic
    from repro.synth import compile_logic_network

    with open(os.path.join(BENCH, "configs", "jsc-s.json")) as f:
        cfg = json.load(f)
    ref = spec.reference(cfg)
    weights = model.make_weights(cfg, jax.devices()[0])
    pool = traffic.make_pool({"pool_rows": 16384}, SEED)
    want = ref.labels(cfg, weights, pool)
    ctrl = ref.labels(cfg, weights, pool, ml_dtypes.bfloat16)
    served = traffic.Served(
        start=np.arange(0, 16384, 256), rows=np.full(64, 256),
        due_us=np.zeros(64), submit_us=np.zeros(64), done_us=np.zeros(64),
        answered=np.ones(64, bool), labels=ctrl, errors=[], t0_us=0.0,
        t1_us=1.0)
    r = check.compare(served, want)
    assert r["compared_labels"] == 16384
    assert r["wrong_labels"] > 0 and not check.verdict(r)
    net = model.to_logic(cfg, weights)
    prog = compile_logic_network(net, engine="numpy").classify(pool, 5)
    np.testing.assert_array_equal(prog, want)


def test_compile_counter_counts_only_while_open():
    import jax
    import jax.numpy as jnp
    with runner.CompileCounter() as c:
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
    n = c.n
    jax.jit(lambda x: x * 5 - 2)(jnp.arange(9.0)).block_until_ready()
    assert n >= 1 and c.n == n
