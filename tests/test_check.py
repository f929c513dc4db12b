"""repro.check: every pass must flag seeded corruption and stay silent
on clean artifacts.

Mutation style: build a real pipeline artifact (AIG / mapped netlist /
DevicePlan), corrupt it the way a buggy transform would (flip an INIT
bit, swap leaf wires, drop a LUT, point a leaf at the dump row), and
assert the checker reports it — with a *valid* counterexample where the
corruption is functional. Functional mutations are guarded by an
independent exhaustive simulation: a flipped INIT bit on an unreachable
leaf pattern does NOT change the function, and the checker must then
stay silent rather than cry wolf.
"""
import copy
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
from hyp_compat import given, settings, st

from repro.check import (CheckFailure, CheckReport, check_concurrency,
                         check_duplicate_definitions, equiv_aig_mapped,
                         equiv_aigs, equiv_mapped_plan,
                         equiv_network_mapped, execute_plan_host, lint_aig,
                         lint_mapped, plan_fingerprint, require_ok,
                         validate_device_plan)
from repro.check.concurrency import check_reject_coverage
from repro.synth import (AIG, CONST0, CONST1, compile_device_plan, lit,
                         lit_var, map_aig, optimize, synthesize)
from repro.synth.executor import execute_packed
from repro.synth.lutmap import MappedLUT
from repro.synth.simulate import input_patterns, pack_bits, simulate

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def random_aig(seed, n_pis=6, n_ands=30):
    rng = np.random.default_rng(seed)
    a = AIG(n_pis)
    lits = [lit(p + 1) for p in range(n_pis)]
    for _ in range(n_ands):
        i, j = rng.choice(len(lits), 2, replace=False)
        lits.append(a.and2(lits[i] ^ int(rng.integers(2)),
                           lits[j] ^ int(rng.integers(2))))
    outs = [l for l in lits[n_pis:] if lit_var(l) != 0][-3:]
    a.outputs = outs or [lits[-1]]
    return a


def mapped_fn(mapped, n_pis):
    """Ground-truth output words of a mapped net on all 2^n inputs."""
    return execute_packed(mapped, input_patterns(n_pis))


def eval_on_bits(fn_words, bits):
    """Evaluate a packed evaluator on one explicit PI bit pattern."""
    words = pack_bits(np.asarray(bits, np.uint8)[:, None])
    return (np.asarray(fn_words(words))[:, 0] & 1).astype(int)


# ---------------------------------------------------------------------------
# report plumbing
# ---------------------------------------------------------------------------

def test_report_merge_errors_and_require_ok():
    r = CheckReport("a")
    r.warn("lint", "w", "just a warning")
    assert r.ok and len(r.warnings) == 1
    r2 = CheckReport("b")
    r2.error("equiv", "stage", "boom", where="lut 3")
    r.merge(r2)
    assert not r.ok and r.errors[0].code == "stage"
    assert "FAIL" in r.format()
    with pytest.raises(CheckFailure) as ei:
        require_ok(r)
    assert "boom" in str(ei.value)


# ---------------------------------------------------------------------------
# pass 1: netlist lint
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(4))
def test_lint_clean_on_unmutated(seed):
    a = random_aig(seed)
    assert lint_aig(a).ok
    opt = optimize(a, rounds=1)
    assert lint_aig(opt).ok
    m = map_aig(opt, k=4)
    rep = lint_mapped(m)
    assert rep.ok, rep.format()


def _codes(rep):
    return {i.code for i in rep.errors}


def test_lint_aig_flags_structural_corruption():
    a = random_aig(1)
    n = a.n_nodes

    bad = copy.deepcopy(a)
    bad._level[n - 1] += 1                       # broken levelization
    assert "level" in _codes(lint_aig(bad))

    bad = copy.deepcopy(a)
    bad._f0[n - 1] = lit(n - 1)                  # self/forward reference
    assert "cycle" in _codes(lint_aig(bad))

    bad = copy.deepcopy(a)
    f0, f1 = bad._f0[n - 1], bad._f1[n - 1]
    bad._f0[n - 1], bad._f1[n - 1] = f1, f0      # de-canonicalised operands
    assert "operand-order" in _codes(lint_aig(bad))

    bad = copy.deepcopy(a)
    bad._f0.append(bad._f0[n - 1])               # strash violation
    bad._f1.append(bad._f1[n - 1])
    bad._level.append(bad._level[n - 1])
    assert "duplicate-and" in _codes(lint_aig(bad))

    bad = copy.deepcopy(a)
    bad._f0[n - 1] = CONST1                      # un-propagated constant
    assert "const-fanin" in _codes(lint_aig(bad))

    bad = copy.deepcopy(a)
    bad.outputs[0] = lit(n + 7)                  # dangling output wire
    assert "bad-output" in _codes(lint_aig(bad))


def test_lint_mapped_flags_corruption():
    m = map_aig(optimize(random_aig(2), rounds=1), k=4)
    assert len(m.luts) >= 2, "need a multi-LUT net for these mutations"

    bad = dataclasses.replace(m, luts=list(m.luts))
    l = bad.luts[-1]
    bad.luts[-1] = MappedLUT(l.root, l.leaves, 1 << (1 << len(l.leaves)))
    assert "init-width" in _codes(lint_mapped(bad))   # INIT wider than 2^m

    bad = dataclasses.replace(m, luts=list(m.luts))
    l0, l1 = bad.luts[0], bad.luts[-1]
    bad.luts[0] = MappedLUT(l0.root, (l1.root,) + l0.leaves[1:],
                            l0.tt)                    # reads a later wire
    assert "undefined-leaf" in _codes(lint_mapped(bad))

    bad = dataclasses.replace(m, luts=list(m.luts))
    l = bad.luts[-1]
    bad.luts[-1] = MappedLUT(bad.luts[0].root, l.leaves, l.tt)
    assert "duplicate-root" in _codes(lint_mapped(bad))

    bad = dataclasses.replace(m, luts=list(m.luts))
    l = bad.luts[0]
    wide = tuple(range(1, m.k + 2))
    bad.luts[0] = MappedLUT(l.root, wide, 0)          # fanin > k
    assert "fanin-width" in _codes(lint_mapped(bad))

    # dropped LUT (a "level edge" removed): its root becomes undefined
    used_roots = {x for l in m.luts for x in l.leaves if x > m.n_pis}
    victim = next(i for i, l in enumerate(m.luts) if l.root in used_roots)
    bad = dataclasses.replace(
        m, luts=[l for i, l in enumerate(m.luts) if i != victim])
    rep = lint_mapped(bad)
    assert {"undefined-leaf", "undefined-output"} & _codes(rep)


# ---------------------------------------------------------------------------
# pass 2: equivalence
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(4))
def test_equiv_clean_pipeline(seed):
    a = random_aig(seed)
    opt = optimize(a, rounds=2)
    assert equiv_aigs(a, opt).ok
    m = map_aig(opt, k=4)
    assert equiv_aig_mapped(opt, m).ok
    dp = compile_device_plan(m)
    assert equiv_mapped_plan(m, dp).ok


def test_equiv_reports_valid_exhaustive_counterexample():
    a = random_aig(3)
    dut = copy.deepcopy(a)
    dut.outputs = [dut.outputs[0] ^ 1] + dut.outputs[1:]
    rep = equiv_aigs(a, dut)
    assert not rep.ok
    cex = rep.errors[0].counterexample
    assert cex is not None and cex.exhaustive
    assert len(cex.inputs) == a.n_pis
    # the witness must actually separate the two networks
    got = eval_on_bits(lambda w: simulate(dut, w), cex.inputs)
    want = eval_on_bits(lambda w: simulate(a, w), cex.inputs)
    assert got[cex.output] == cex.got and want[cex.output] == cex.want
    assert cex.got != cex.want


def test_equiv_wide_cone_uses_sampled_vectors():
    a = random_aig(4, n_pis=24, n_ands=60)      # > EXHAUSTIVE_LIMIT
    dut = copy.deepcopy(a)
    dut.outputs = [dut.outputs[0] ^ 1] + dut.outputs[1:]
    rep = equiv_aigs(a, dut)
    assert not rep.ok
    assert rep.errors[0].counterexample is not None
    assert not rep.errors[0].counterexample.exhaustive
    assert equiv_aigs(a, copy.deepcopy(a)).ok   # clean stays clean


def test_equiv_interface_mismatch():
    a, b = random_aig(0, n_pis=4), random_aig(0, n_pis=5)
    assert "aig-rewrite" in _codes(equiv_aigs(a, b))


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), which=st.integers(0, 3),
       row=st.integers(0, 63))
def test_mutation_flip_init_bit_killrate(seed, which, row):
    """Flip one INIT bit of one LUT: the miter must flag the corruption
    exactly when the function actually changed (unreachable leaf
    patterns make some flips no-ops — the checker must not cry wolf)."""
    a = optimize(random_aig(seed, n_pis=5, n_ands=25), rounds=1)
    m = map_aig(a, k=4)
    if not m.luts:
        return
    i = which % len(m.luts)
    l = m.luts[i]
    r = row % (1 << len(l.leaves))
    bad = dataclasses.replace(m, luts=list(m.luts))
    bad.luts[i] = MappedLUT(l.root, l.leaves, l.tt ^ (1 << r))
    changed = not np.array_equal(mapped_fn(m, a.n_pis),
                                 mapped_fn(bad, a.n_pis))
    rep = equiv_aig_mapped(a, bad)
    assert rep.ok == (not changed), rep.format()
    if changed:
        cex = rep.errors[0].counterexample
        got = eval_on_bits(lambda w: execute_packed(bad, w), cex.inputs)
        want = eval_on_bits(lambda w: simulate(a, w), cex.inputs)
        assert got[cex.output] != want[cex.output]


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), which=st.integers(0, 3))
def test_mutation_swap_leaves_killrate(seed, which):
    """Swap two leaf wires of one LUT (same guard: symmetric truth
    tables make some swaps function-preserving)."""
    a = optimize(random_aig(seed, n_pis=5, n_ands=25), rounds=1)
    m = map_aig(a, k=4)
    multi = [i for i, l in enumerate(m.luts) if len(l.leaves) >= 2]
    if not multi:
        return
    i = multi[which % len(multi)]
    l = m.luts[i]
    leaves = list(l.leaves)
    leaves[0], leaves[1] = leaves[1], leaves[0]
    bad = dataclasses.replace(m, luts=list(m.luts))
    bad.luts[i] = MappedLUT(l.root, tuple(leaves), l.tt)
    changed = not np.array_equal(mapped_fn(m, a.n_pis),
                                 mapped_fn(bad, a.n_pis))
    rep = equiv_aig_mapped(a, bad)
    assert rep.ok == (not changed), rep.format()


@pytest.mark.parametrize("seed", range(6))
def test_mutation_killrate_deterministic(seed):
    """Hypothesis-free version of the kill-rate property (the @given
    variants above skip when the optional dep is absent): every LUT of
    every net gets one INIT-bit flip and one leaf swap, checked with
    the same changed-function guard."""
    a = optimize(random_aig(seed, n_pis=5, n_ands=25), rounds=1)
    m = map_aig(a, k=4)
    ref = mapped_fn(m, a.n_pis)
    for i, l in enumerate(m.luts):
        muts = [MappedLUT(l.root, l.leaves, l.tt ^ 1)]
        if len(l.leaves) >= 2:
            lv = list(l.leaves)
            lv[0], lv[1] = lv[1], lv[0]
            muts.append(MappedLUT(l.root, tuple(lv), l.tt))
        for mut in muts:
            bad = dataclasses.replace(m, luts=list(m.luts))
            bad.luts[i] = mut
            changed = not np.array_equal(ref, mapped_fn(bad, a.n_pis))
            assert equiv_aig_mapped(a, bad).ok == (not changed)


def test_constant_output_network():
    """Constant nets (zero LUTs, outputs on the const wire) must pass
    every pass clean — and the const-vs-const miter path must work."""
    a = AIG(3)
    a.outputs = [CONST0, CONST1, lit(1)]        # const0, const1, pi0
    m = map_aig(a, k=4)
    assert m.n_luts == 0
    assert lint_mapped(m).ok
    assert equiv_aig_mapped(a, m).ok
    dp = compile_device_plan(m)
    assert validate_device_plan(dp, use_cache=False).ok
    assert equiv_mapped_plan(m, dp).ok

    z = AIG(0)                                   # zero-PI network
    z.outputs = [CONST1]
    mz = map_aig(z, k=4)
    assert equiv_aig_mapped(z, mz).ok


# ---------------------------------------------------------------------------
# pass 3: device-plan validation
# ---------------------------------------------------------------------------

def _plan(seed=5, k=4):
    a = optimize(random_aig(seed, n_pis=6, n_ands=40), rounds=1)
    m = map_aig(a, k=k)
    return m, compile_device_plan(m)


def _fresh(dp):
    return validate_device_plan(dp, use_cache=False)


def test_plan_clean_and_cached():
    m, dp = _plan()
    rep = validate_device_plan(dp)
    assert rep.ok and rep.info["vmem_bytes"] > 0
    assert validate_device_plan(dp) is rep          # cache hit by hash
    assert validate_device_plan(dp, use_cache=False) is not rep
    dp2 = compile_device_plan(m)
    assert plan_fingerprint(dp) == plan_fingerprint(dp2)
    dp2.tt_bits[0, 0, 0] ^= 0xFFFFFFFF
    assert plan_fingerprint(dp) != plan_fingerprint(dp2)


def test_plan_corruptions_caught():
    _, dp = _plan()

    bad = copy.deepcopy(dp)
    bad.leaf_idx[0, 0, 0] = bad.n_wires             # reads the dump row
    assert "leaf-range" in _codes(_fresh(bad))

    bad = copy.deepcopy(dp)
    bad.tt_bits[0, 0, 0] = 5                        # not a bitplane mask
    assert "tt-encoding" in _codes(_fresh(bad))

    bad = copy.deepcopy(dp)
    real = np.argwhere(bad.out_wires != bad.n_wires)
    (l0, s0), (l1, s1) = real[0], real[-1]
    bad.out_wires[l1, s1] = bad.out_wires[l0, s0]   # wire written twice
    assert "wire-cover" in _codes(_fresh(bad))

    bad = copy.deepcopy(dp)
    bad.out_idx[0] = bad.n_wires + 3
    assert "out-idx" in _codes(_fresh(bad))

    bad = dataclasses.replace(dp, leaf_idx=dp.leaf_idx.astype(np.int64))
    assert "dtype" in _codes(_fresh(bad))

    rep = validate_device_plan(dp, vmem_budget_bytes=1, use_cache=False)
    assert "vmem-budget" in _codes(rep)


def test_plan_pad_slot_and_level_order():
    _, dp = _plan()
    pads = np.argwhere(dp.out_wires == dp.n_wires)
    if pads.size:                                   # ragged level widths
        l, s = pads[0]
        bad = copy.deepcopy(dp)
        bad.tt_bits[l, s, 0] = 0xFFFFFFFF           # pad slot would write
        assert "pad-slot" in _codes(_fresh(bad))
        bad = copy.deepcopy(dp)
        bad.leaf_idx[l, s, 0] = 2                   # pad slot reads a wire
        assert "pad-slot" in _codes(_fresh(bad))
    # same-level read: point a slot's leaf at a wire its own level writes
    for l in range(dp.n_levels):
        real = np.nonzero(dp.out_wires[l] != dp.n_wires)[0]
        if len(real) >= 2:
            bad = copy.deepcopy(dp)
            bad.leaf_idx[l, real[0], 0] = bad.out_wires[l, real[1]]
            assert "level-order" in _codes(_fresh(bad))
            break


def test_execute_plan_host_is_independent_reference():
    for seed in range(3):
        a = optimize(random_aig(seed, n_pis=6, n_ands=40), rounds=1)
        m = map_aig(a, k=4)
        dp = compile_device_plan(m)
        words = input_patterns(a.n_pis)
        np.testing.assert_array_equal(execute_plan_host(dp, words),
                                      execute_packed(m, words))


# ---------------------------------------------------------------------------
# pass 4: concurrency lint
# ---------------------------------------------------------------------------

_VIOLATING = textwrap.dedent('''
    import threading

    class S:
        _GUARDED_BY = {"_stopping": "_cond"}
        _LOCKED_METHODS = ("_flush_locked",)

        def __init__(self):
            self._cond = threading.Condition()
            self._stopping = False      # __init__ is exempt

        def start(self):
            self._stopping = False      # BUG: write outside the lock

        def loop(self):
            with self._cond:
                ok = self._stopping     # fine
            return self.poll(self._stopping)    # BUG: read outside

        def callback_leak(self):
            with self._cond:
                return lambda: self._stopping   # BUG: runs lock-free later

        def bad_call(self):
            self._flush_locked()        # BUG: requires the lock held

        def _flush_locked(self):
            return self._stopping       # exempt via _LOCKED_METHODS
''')

_CLEAN = textwrap.dedent('''
    import threading

    class S:
        _GUARDED_BY = {"_stopping": "_cond"}
        _LOCKED_METHODS = ("_flush_locked",)

        def __init__(self):
            self._cond = threading.Condition()
            self._stopping = False

        def start(self):
            with self._cond:
                self._stopping = False
                if self._stopping:
                    self._flush_locked()

        def _flush_locked(self):
            return self._stopping
''')


def test_concurrency_lint_flags_violations(tmp_path):
    p = tmp_path / "mod.py"
    p.write_text(_VIOLATING)
    rep = check_concurrency(files=[p])
    codes = [(i.code, i.where) for i in rep.errors]
    assert sum(c == "unlocked-access" for c, _ in codes) == 3
    assert sum(c == "unlocked-call" for c, _ in codes) == 1
    lines = {int(w.split(":")[1]) for _, w in codes}
    assert len(lines) == 4              # four distinct source lines


def test_concurrency_lint_silent_on_clean(tmp_path):
    p = tmp_path / "mod.py"
    p.write_text(_CLEAN)
    rep = check_concurrency(files=[p])
    assert rep.ok, rep.format()
    assert rep.checked > 0              # it actually looked


def test_reject_reason_coverage(tmp_path):
    serve = tmp_path / "serve"
    tests = tmp_path / "tests"
    serve.mkdir(), tests.mkdir()
    (serve / "sched.py").write_text(textwrap.dedent('''
        class RejectReason:
            QUEUE_FULL = "queue_full"
            GHOST = "ghost"
        def submit():
            raise RuntimeError(RejectReason.QUEUE_FULL)
    '''))
    (tests / "test_s.py").write_text(
        "def test_full():\n    assert 'queue_full'\n")
    rep = CheckReport("rr")
    check_reject_coverage(serve, tests, rep)
    codes = {(i.code, i.where) for i in rep.errors}
    assert ("unraisable-reason", "GHOST") in codes    # no code path
    assert ("untested-reason", "GHOST") in codes      # no test
    assert not any(w == "QUEUE_FULL" for _, w in codes)


def test_real_serve_stack_is_clean():
    rep = check_concurrency()
    assert rep.ok, rep.format()
    assert "MicroBatchScheduler" in rep.info["guarded_classes"]
    assert rep.checked > 10


def test_obs_classes_are_linted():
    """The lint covers repro.obs: the shared-mutable window/burn-rate
    classes must carry (and satisfy) lock annotations."""
    rep = check_concurrency()
    assert rep.ok, rep.format()
    for cls in ("WindowedMetrics", "BurnRateMonitor", "BucketRing"):
        assert cls in rep.info["guarded_classes"], cls


def test_lock_free_annotation_exempts_field(tmp_path):
    p = tmp_path / "mod.py"
    p.write_text(textwrap.dedent('''
        import threading

        class S:
            _GUARDED_BY = {"_q": "_lock"}
            _LOCK_FREE = ("_hwm",)

            def __init__(self):
                self._lock = threading.Lock()
                self._q = []
                self._hwm = 0.0

            def push(self, t):
                self._hwm = max(self._hwm, t)   # declared benign race
                with self._lock:
                    self._q.append(t)
    '''))
    rep = check_concurrency(files=[p])
    assert rep.ok, rep.format()


def test_conflicting_annotation_rejected(tmp_path):
    p = tmp_path / "mod.py"
    p.write_text(textwrap.dedent('''
        import threading

        class S:
            _GUARDED_BY = {"_q": "_lock"}
            _LOCK_FREE = ("_q",)            # BUG: both annotations

            def __init__(self):
                self._lock = threading.Lock()
                self._q = []
    '''))
    rep = check_concurrency(files=[p])
    assert any(i.code == "conflicting-annotation" for i in rep.errors)


# ---------------------------------------------------------------------------
# srclint + satellites
# ---------------------------------------------------------------------------

def test_srclint_flags_duplicate_definition(tmp_path):
    src = tmp_path / "pkg"
    src.mkdir()
    (src / "a.py").write_text("LUT_K = 6\n")
    (src / "b.py").write_text("LUT_K = 4\n")
    rep = check_duplicate_definitions(src_dir=src)
    assert "duplicate-definition" in _codes(rep)
    assert not check_duplicate_definitions().errors    # real repo clean


def test_lut_cost_single_source():
    """The dedup satellite: both mappers report through core.lutcost."""
    from repro.core import lutcost, lutmap
    from repro.synth import lutmap as synth_lutmap
    assert lutmap.MapReport is lutcost.MapReport
    assert synth_lutmap.LUT_K is lutcost.LUT_K
    assert lutmap.logicnets_lut_cost is lutcost.logicnets_lut_cost
    m = map_aig(random_aig(0), k=4)
    r = m.report(ffs=7)
    assert (r.luts, r.depth, r.ffs) == (m.n_luts, m.depth, 7)
    assert r.fmax_mhz > 0


def _run_regression(args, cwd=None):
    return subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "benchmarks",
                                      "check_regression.py")] + args,
        capture_output=True, text=True, cwd=cwd or REPO_ROOT)


def test_check_regression_unparsable_baseline_is_actionable(tmp_path):
    (tmp_path / "BENCH_kernels.json").write_text("{nope")
    p = _run_regression(["--files", "BENCH_kernels.json",
                         "--baseline-dir", str(tmp_path)])
    assert p.returncode == 2
    assert "not valid JSON" in p.stdout
    assert "Traceback" not in p.stdout + p.stderr


def test_check_regression_unparsable_fresh_is_actionable(tmp_path):
    (tmp_path / "BENCH_kernels.json").write_text("{nope")
    p = _run_regression(["--files", "BENCH_kernels.json",
                         "--fresh-dir", str(tmp_path)])
    assert p.returncode == 2
    assert "not valid JSON" in p.stdout
    assert "Traceback" not in p.stdout + p.stderr


def test_check_regression_missing_baseline_skips(tmp_path):
    doc = {"section": "kernels", "results": {"x_us": 1.0}}
    (tmp_path / "BENCH_new_thing.json").write_text(json.dumps(doc))
    p = _run_regression(["--files", "BENCH_new_thing.json",
                         "--fresh-dir", str(tmp_path)])
    assert p.returncode == 0
    assert "no baseline" in p.stdout


# ---------------------------------------------------------------------------
# verify= hooks
# ---------------------------------------------------------------------------

def test_verify_flag_passes_clean_and_raises_on_corruption():
    a = random_aig(6)
    m = synthesize(a, effort=1, verify=True)           # should not raise
    dp = compile_device_plan(m, verify=True)
    from repro.check.pipeline import verify_plan
    bad = copy.deepcopy(dp)
    bad.tt_bits[bad.tt_bits != 0] ^= 0xFFFFFFFF        # break every LUT
    with pytest.raises(CheckFailure):
        verify_plan(m, bad)


# ---------------------------------------------------------------------------
# LogicNetwork-level checks (SOP stage + valid-code oracle)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_net():
    jnp = pytest.importorskip("jax.numpy")
    from repro.core import fcp
    from repro.core.logic_infer import LogicNetwork
    from repro.core.quant import ActQuantSpec
    from repro.core.truthtable import extract_layer_tables

    rng = np.random.default_rng(7)
    spec = ActQuantSpec("sign", 1)
    alpha = 2.0
    n_in, n_out, fanin = 6, 4, 3
    w = jnp.asarray(rng.normal(size=(n_out, n_in)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(n_out,)) * 0.1, jnp.float32)
    mask = fcp.topk_row_mask(w, fanin)
    lt = extract_layer_tables(w, b, mask, spec, spec, alpha, alpha, fanin)
    return LogicNetwork([lt], spec, alpha, n_in, n_out)


def test_full_pipeline_check_on_logic_network(tiny_net):
    from repro.check import check_synth_pipeline
    rep = check_synth_pipeline(net=tiny_net, fast=True)
    assert rep.ok, rep.format()
    assert rep.checked > 100


def test_network_oracle_catches_mapped_corruption(tiny_net):
    from repro.synth.from_sop import network_to_aig
    a = network_to_aig(tiny_net)
    m = synthesize(a, effort=1)
    assert equiv_network_mapped(tiny_net, m, n_samples=128).ok
    bad = dataclasses.replace(m, outputs=[m.outputs[0] ^ 1]
                              + m.outputs[1:])
    rep = equiv_network_mapped(tiny_net, bad, n_samples=128)
    assert not rep.ok
    cex = rep.errors[0].counterexample
    assert cex is not None
    # the counterexample is an input *code* row; replaying it through
    # the oracle and the netlist must reproduce the disagreement
    codes = np.asarray(cex.inputs)[None, :]
    want = np.asarray(tiny_net.apply_codes(codes))[0]
    from repro.synth.executor import BitplaneNetwork
    got = BitplaneNetwork(tiny_net, bad).apply_codes(codes)[0]
    assert got[cex.output] != want[cex.output]


def test_preflight_on_bitplane_network(tiny_net):
    from repro.check import preflight
    from repro.synth import compile_logic_network
    bn = compile_logic_network(tiny_net, verify=True)  # full verify path
    rep = preflight(bn, n_samples=64)
    assert rep.ok, rep.format()
