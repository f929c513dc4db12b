"""Netlists synthesized ahead of time (``repro.synth.store``) and the
``bitplane-prebuilt`` serving backend that loads them."""
import dataclasses
import os

import numpy as np
import pytest

import repro.synth as synth
from repro.core.logic_infer import LogicNetwork
from repro.core.quant import ActQuantSpec
from repro.core.truthtable import LayerTables
from repro.synth import store
from repro.synth import UnknownEngineError
from repro.synth.executor import BitplaneNetwork, compile_tile_plan, staged_rows
from repro.synth.lutmap import MappedLUT

SPEC = ActQuantSpec("signed", 2)        # 3 levels, 2-bit codes


def _small_net(seed=0, n_in=10, widths=(8, 5), fanin=3) -> LogicNetwork:
    """Random tables over 2-bit signed codes: a net that synthesizes in
    about a second."""
    rng = np.random.default_rng(seed)
    layers, d_in = [], n_in
    for i, n in enumerate(widths):
        idx = np.stack([rng.choice(d_in, fanin, replace=False)
                        for _ in range(n)]).astype(np.int32)
        tab = rng.integers(0, SPEC.n_levels, (n, SPEC.n_levels ** fanin))
        layers.append(LayerTables(idx, tab.astype(np.int8), SPEC, SPEC,
                                  in_alpha=1.0 if i == 0 else 0.9,
                                  out_alpha=0.9))
        d_in = n
    return LogicNetwork(layers, SPEC, 1.0, n_in, widths[-1])


@pytest.fixture(scope="module")
def built():
    net = _small_net()
    return net, synth.synthesize(synth.network_to_aig(net), effort=1, k=6)


def _no_synthesis(monkeypatch):
    def boom(*a, **kw):
        raise AssertionError("synthesize was called")
    monkeypatch.setattr(synth, "synthesize", boom)


def test_save_load_round_trip(built, tmp_path):
    net, mapped = built
    path = store.save(mapped, net, 1, 6, root=str(tmp_path))
    assert os.path.basename(path) == store.fingerprint(net, 1, 6) + ".npz"
    got = store.load(net, 1, 6, root=str(tmp_path))
    assert got == mapped
    assert got.n_luts == mapped.n_luts and got.depth == mapped.depth


def _alter_table(net):
    lt = net.layers[1]
    tab = lt.tables.copy()
    tab[0, 0] = (tab[0, 0] + 1) % SPEC.n_levels
    return dataclasses.replace(
        net, layers=[net.layers[0], dataclasses.replace(lt, tables=tab)])


def _alter_out_alpha(net):
    return dataclasses.replace(net, layers=[
        dataclasses.replace(net.layers[0], out_alpha=0.91), net.layers[1]])


@pytest.mark.parametrize("change", [
    lambda net, e, k: (_alter_table(net), e, k),
    lambda net, e, k: (dataclasses.replace(net, in_alpha=1.01), e, k),
    lambda net, e, k: (_alter_out_alpha(net), e, k),
    lambda net, e, k: (net, e + 1, k),
    lambda net, e, k: (net, e, k - 1)],
    ids=["table_entry", "in_alpha", "out_alpha", "effort", "k"])
def test_fingerprint_changes_with_what_the_netlist_implements(change):
    net = _small_net()
    fp = store.fingerprint(net, 1, 6)
    assert store.fingerprint(_small_net(), 1, 6) == fp
    assert store.fingerprint(*change(net, 1, 6)) != fp


def test_miss_raises_without_synthesis(monkeypatch, tmp_path):
    from repro.serving.engine import LogicEngine
    _no_synthesis(monkeypatch)
    net = _small_net()
    with pytest.raises(store.NetlistNotFound) as e:
        store.load(net, 1, 6, root=str(tmp_path))
    assert store.fingerprint(net, 1, 6) in str(e.value)
    assert "make_netlist.py" in str(e.value)
    monkeypatch.setattr(store, "DEFAULT_ROOT", str(tmp_path))
    with pytest.raises(store.NetlistNotFound):
        LogicEngine(net, 5, backend="bitplane-prebuilt",
                    engine="pallas-streamed")


def test_renamed_file_is_refused(built, tmp_path):
    net, mapped = built
    path = store.save(mapped, net, 1, 6, root=str(tmp_path))
    other = _alter_table(net)
    os.rename(path, store.path_of(store.fingerprint(other, 1, 6),
                                  str(tmp_path)))
    with pytest.raises(store.NetlistMismatch, match="holds the netlist of"):
        store.load(other, 1, 6, root=str(tmp_path))


def _flip_one_init_bit(mapped, net):
    """The first (output LUT, INIT bit) whose flip the load-time check's
    rows can see, and the netlist with that bit flipped."""
    rng = np.random.default_rng(store.CHECK_SEED)
    codes = rng.integers(0, net.in_spec.n_levels,
                         (store.CHECK_ROWS, net.n_inputs), dtype=np.int32)
    want = BitplaneNetwork(net, mapped).apply_codes(codes)
    out_vars = {o >> 1 for o in mapped.outputs}
    for i, lut in enumerate(mapped.luts):
        if lut.root not in out_vars:
            continue
        for bit in range(1 << len(lut.leaves)):
            luts = list(mapped.luts)
            luts[i] = MappedLUT(lut.root, lut.leaves, lut.tt ^ (1 << bit))
            bad = dataclasses.replace(mapped, luts=luts)
            if (BitplaneNetwork(net, bad).apply_codes(codes) != want).any():
                return bad
    raise AssertionError("no visible INIT bit")


def test_flipped_init_bit_fails_the_load_check(built, tmp_path):
    net, mapped = built
    store.save(_flip_one_init_bit(mapped, net), net, 1, 6,
               root=str(tmp_path))
    with pytest.raises(store.NetlistMismatch, match="disagrees with the "
                                                    "tables"):
        store.load(net, 1, 6, root=str(tmp_path))


def test_load_records_a_setup_span(built, tmp_path):
    from repro.obs import SpanTracer
    net, mapped = built
    path = store.save(mapped, net, 1, 6, root=str(tmp_path))
    tr = SpanTracer()
    store.load(net, 1, 6, root=str(tmp_path), tracer=tr)
    (ev,) = [e for e in tr.events() if e.name == "netlist_load"]
    assert ev.cat == "setup" and ev.ph == "X"
    assert ev.args["luts"] == mapped.n_luts
    assert ev.args["bytes"] == os.path.getsize(path)
    assert 0.0 <= ev.args["check_s"] <= ev.dur_us * 1e-6


def test_prebuilt_backend_serves_the_labels_of_bitplane(built, tmp_path,
                                                        monkeypatch):
    from repro.serving.engine import LogicEngine
    net, mapped = built
    monkeypatch.setattr(store, "DEFAULT_ROOT", str(tmp_path))
    store.save(mapped, net, LogicEngine.synth_effort, 6)
    x = np.random.default_rng(3).normal(size=(600, net.n_inputs)).astype(
        np.float32)
    want = LogicEngine(net, 5, max_batch=128, backend="bitplane",
                       engine="pallas-streamed").classify(x)
    _no_synthesis(monkeypatch)
    pre = LogicEngine(net, 5, max_batch=128, backend="bitplane-prebuilt",
                      engine="pallas-streamed")
    assert pre.bitnet.mapped == mapped
    np.testing.assert_array_equal(pre.classify(x), want)
    np.testing.assert_array_equal(
        np.concatenate([pre.scheduler_executor()(x[i:i + 100])
                        for i in range(0, 600, 100)]), want)


def test_unknown_engine_raises_before_synthesis_or_load(monkeypatch):
    _no_synthesis(monkeypatch)

    def no_load(*a, **kw):
        raise AssertionError("store.load was called")
    monkeypatch.setattr(store, "load", no_load)
    net = _small_net()
    with pytest.raises(UnknownEngineError):
        BitplaneNetwork.from_logic_network(net, engine="no-such-engine")
    with pytest.raises(UnknownEngineError):
        BitplaneNetwork.from_store(net, engine="no-such-engine")


def test_streamed_fetch_span_carries_the_plan_counts(built):
    from repro.obs import SpanTracer
    from repro.synth.simulate import pack_bits
    net, mapped = built
    bn = BitplaneNetwork(net, mapped, engine="pallas-streamed")
    tr = SpanTracer()
    bn.tracer = tr
    words = pack_bits(np.random.default_rng(1).integers(
        0, 2, (mapped.n_pis, 64)).astype(np.uint8))
    # the fetch span is the wait for the launched program's labels
    np.asarray(bn.classify_packed(words, 64, 5))
    np.asarray(bn.classify_packed(words, 64, 5))
    fetch = [e for e in tr.events() if e.name == "fetch"]
    assert len(fetch) == 2 and fetch[0].args is fetch[1].args
    tp = bn.executor.tp
    # the plane fits VMEM, so the kernel stages no leaf rows
    assert fetch[0].args == {"luts": mapped.n_luts, "tiles": tp.n_tiles,
                             "gather": "vmem", "staged_rows": 0}


@pytest.mark.parametrize("tile_rows", [1, 4, 32])
def test_staged_rows_counts_unique_leaf_rows_per_tile(built, tile_rows):
    net, mapped = built
    tp = compile_tile_plan(BitplaneNetwork(net, mapped)._plan,
                           mapped.n_pis, mapped.k, tile_rows)
    want = sum(np.unique(tp.leaf_tiles[t]).size for t in range(tp.n_tiles))
    assert staged_rows(tp) == want
    assert want <= tp.n_tiles * tp.gather_cap
