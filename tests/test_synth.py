"""repro.synth: AIG construction, rewriting, k-LUT mapping, bit-parallel
simulation, the bitplane executor, and the end-to-end JSC-S equivalence
of the mapped netlist against the truth-table oracle."""
import jax.numpy as jnp
import numpy as np
import pytest
from hyp_compat import given, settings, st

from repro.synth import (AIG, CONST0, CONST1, compile_logic_network,
                         emit_verilog, exhaustive_equiv, execute_packed,
                         input_patterns, lit_not, map_aig, network_to_aig,
                         optimize, pack_bits, random_equiv, random_words,
                         simulate, synthesize, unpack_bits)
from repro.synth.from_sop import table_to_aig
from repro.synth.rewrite import balance, rewrite


def _tt_onset(tt: int, n: int) -> np.ndarray:
    return np.array([(tt >> r) & 1 for r in range(1 << n)], bool)


def _build_tt(tt: int, n: int) -> AIG:
    aig = AIG(n)
    aig.outputs = [table_to_aig(aig, _tt_onset(tt, n), None,
                                [2 * (i + 1) for i in range(n)])]
    return aig


# ---------------------------------------------------------------------------
# AIG invariants
# ---------------------------------------------------------------------------

def test_aig_constant_propagation_and_hashing():
    aig = AIG(2)
    a, b = 2, 4
    assert aig.and2(a, CONST0) == CONST0
    assert aig.and2(a, CONST1) == a
    assert aig.and2(a, a) == a
    assert aig.and2(a, lit_not(a)) == CONST0
    n1 = aig.and2(a, b)
    n2 = aig.and2(b, a)            # operand order canonicalised
    assert n1 == n2
    assert aig.n_ands == 1
    assert aig.or2(lit_not(a), lit_not(b)) == lit_not(n1)  # shared via strash
    assert aig.n_ands == 1


def test_aig_simulation_semantics():
    aig = AIG(2)
    a, b = 2, 4
    aig.outputs = [aig.and2(a, b), aig.or2(a, b), aig.xor2(a, b),
                   lit_not(aig.and2(a, b))]
    out = unpack_bits(simulate(aig, input_patterns(2)), 4)
    np.testing.assert_array_equal(out[0], [0, 0, 0, 1])   # and
    np.testing.assert_array_equal(out[1], [0, 1, 1, 1])   # or
    np.testing.assert_array_equal(out[2], [0, 1, 1, 0])   # xor
    np.testing.assert_array_equal(out[3], [1, 1, 1, 0])   # nand


def test_compact_drops_dead_nodes():
    aig = AIG(3)
    a, b, c = 2, 4, 6
    keep = aig.and2(a, b)
    aig.and2(b, c)                 # dead
    aig.outputs = [keep]
    small = aig.compact()
    assert small.n_ands == 1 and aig.n_ands == 2
    assert random_equiv(aig, small, n_words=4)


# ---------------------------------------------------------------------------
# Rewriting / balancing
# ---------------------------------------------------------------------------

def test_balance_reduces_chain_depth():
    aig = AIG(8)
    acc = 2
    for i in range(1, 8):          # a linear AND chain, depth 7
        acc = aig.and2(acc, 2 * (i + 1))
    aig.outputs = [acc]
    assert aig.depth() == 7
    bal = balance(aig)
    assert bal.depth() == 3        # balanced 8-leaf tree
    assert random_equiv(aig, bal, n_words=8)


def test_rewrite_preserves_function_and_size(rng):
    n = 8
    tt = int.from_bytes(rng.bytes(32), "little")
    aig = _build_tt(tt, n)
    opt = optimize(aig)
    assert opt.n_ands <= aig.n_ands
    assert exhaustive_equiv(opt, [tt])


@settings(max_examples=25, deadline=None)
@given(n=st.integers(1, 6), data=st.data())
def test_tt_pipeline_property(n, data):
    """Random K<=6 truth tables survive SOP -> AIG -> rewrite -> 6-LUT
    mapping with exhaustive-simulation equivalence (and fit one 6-LUT)."""
    tt = data.draw(st.integers(0, (1 << (1 << n)) - 1))
    aig = _build_tt(tt, n)
    assert exhaustive_equiv(aig, [tt])
    opt = optimize(aig)
    assert exhaustive_equiv(opt, [tt])
    mapped = synthesize(aig)
    assert mapped.n_luts <= 1
    got = unpack_bits(execute_packed(mapped, input_patterns(n)), 1 << n)
    np.testing.assert_array_equal(got[0], _tt_onset(tt, n).astype(np.uint8))


# ---------------------------------------------------------------------------
# Mapping + executor + Verilog
# ---------------------------------------------------------------------------

def test_multi_lut_mapping_exhaustive(rng):
    n = 9
    onset = rng.random(1 << n) < 0.4
    tt = sum(int(v) << r for r, v in enumerate(onset))
    aig = _build_tt(tt, n)
    mapped = synthesize(aig)
    assert mapped.n_luts > 1
    assert all(len(l.leaves) <= 6 for l in mapped.luts)
    assert mapped.depth >= 2
    got = unpack_bits(execute_packed(mapped, input_patterns(n)), 1 << n)
    np.testing.assert_array_equal(got[0], onset.astype(np.uint8))


def test_verilog_emission(rng):
    n = 8
    tt = int.from_bytes(rng.bytes(32), "little")
    mapped = synthesize(_build_tt(tt, n))
    v = emit_verilog(mapped, "tiny_mapped")
    assert "module tiny_mapped" in v
    assert v.count("_init = 64'h") == mapped.n_luts
    assert f"[{n - 1}:0] x" in v


def test_pallas_aig_sim_matches_numpy(rng):
    n = 7
    tts = [int.from_bytes(rng.bytes(16), "little") for _ in range(3)]
    aig = AIG(n)
    aig.outputs = [table_to_aig(aig, _tt_onset(t, n), None,
                                [2 * (i + 1) for i in range(n)])
                   for t in tts]
    words = random_words(n, 8, seed=5)
    np.testing.assert_array_equal(
        simulate(aig, words, use_pallas=False),
        simulate(aig, words, use_pallas=True))


def test_pack_unpack_roundtrip(rng):
    bits = (rng.random((5, 100)) < 0.5).astype(np.uint8)
    np.testing.assert_array_equal(unpack_bits(pack_bits(bits), 100), bits)


# ---------------------------------------------------------------------------
# End-to-end: JSC-S mapped netlist vs the truth-table oracle
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def jsc_s():
    from repro.configs.jsc import JSC_S
    from repro.data.jsc import train_test
    from repro.models.mlp import to_logic
    from repro.train.jsc_trainer import train_jsc
    data = train_test(3000, 800, seed=1)
    res = train_jsc(JSC_S, steps=200, batch=128, data=data)
    net = to_logic(JSC_S, res.params, res.masks, res.bn_state)
    return net, data


def test_jsc_s_mapped_netlist_matches_oracle(jsc_s):
    """The paper-flow acceptance check: the synthesized+mapped 6-LUT
    netlist reproduces LogicNetwork.__call__ bit-exactly on real data."""
    net, data = jsc_s
    bit = compile_logic_network(net, effort=1)
    assert bit.mapped.n_luts > 0 and bit.mapped.depth >= 1
    assert all(len(l.leaves) <= 6 for l in bit.mapped.luts)
    (xte, _) = data[1]
    x = jnp.asarray(xte[:700])
    np.testing.assert_array_equal(bit(x), np.asarray(net(x)))


def test_jsc_s_structural_report(jsc_s):
    from repro.core.lutmap import structural_report
    net, _ = jsc_s
    rep, per_layer, backend = structural_report(net)
    assert backend == "synth"
    assert rep.luts > 0 and rep.depth >= 1 and rep.ffs > 0
    assert len(per_layer) == len(net.layers)
    assert rep.luts == sum(r.luts for r in per_layer)


def test_jsc_s_bitplane_engine_matches_gather(jsc_s):
    """gather, numpy-bitplane and device-bitplane backends are
    argmax-identical end to end, including the ragged final flush
    through the aggregator's ``pad_rows`` (600 = 4*128 + 88)."""
    from repro.serving.engine import LogicEngine
    net, data = jsc_s
    (xte, _) = data[1]
    gather = LogicEngine(net, 5, max_batch=128)
    bitplane = LogicEngine(net, 5, max_batch=128, backend="bitplane")
    pallas = LogicEngine(net, 5, max_batch=128, backend="bitplane",
                         engine="pallas-streamed")
    want = gather.classify(xte[:600])
    np.testing.assert_array_equal(want, bitplane.classify(xte[:600]))
    np.testing.assert_array_equal(want, pallas.classify(xte[:600]))


def test_jsc_s_pallas_engine_bit_identical(jsc_s):
    """The fused lut_eval device pipeline (``pallas-streamed``) is
    *bit*-identical to the numpy fold (codes and packed words, not just
    argmax)."""
    from repro.synth.executor import BitplaneNetwork
    from repro.synth.simulate import pack_bits
    net, data = jsc_s
    bit = compile_logic_network(net, effort=1)
    dev = BitplaneNetwork(net, bit.mapped, engine="pallas-streamed")
    (xte, _) = data[1]
    for n in (64, 97):                       # full + ragged lane words
        codes = np.asarray(net.quantize_inputs(jnp.asarray(xte[:n])))
        np.testing.assert_array_equal(bit.apply_codes(codes),
                                      dev.apply_codes(codes))
        planes = np.empty((codes.shape[1] * bit.in_bits, n), np.uint8)
        for b in range(bit.in_bits):
            planes[b::bit.in_bits] = ((codes >> b) & 1).T
        words = pack_bits(planes)
        np.testing.assert_array_equal(
            bit.classify_packed(words, n, 5),
            dev.classify_packed(words, n, 5))


def test_emit_mapped_network(jsc_s):
    from repro.core.netlist import emit_mapped_network
    net, _ = jsc_s
    v = emit_mapped_network(net, "jsc_s_mapped", effort=0)
    assert "module jsc_s_mapped" in v
    assert "_init = 64'h" in v
