"""kernels/lut_eval: on-device mapped-netlist execution vs the numpy
fold, the jnp scan oracle, and the per-sample gather oracle (Pallas in
interpret mode on CPU, same pattern as kernels/aig_sim)."""
import importlib
import types

import jax.numpy as jnp
import numpy as np
import pytest
from hyp_compat import given, settings, st

from repro.kernels.lut_eval import lut_eval_gather_ref, lut_eval_ref
from repro.synth import (AIG, compile_device_plan, execute_packed,
                         input_patterns, random_words, synthesize,
                         unpack_bits)
from repro.synth.executor import _compile_plan
from repro.synth.from_sop import table_to_aig


def _random_mapped(seed: int, n_vars: int, n_outs: int, density=0.5):
    rng = np.random.default_rng(seed)
    aig = AIG(n_vars)
    aig.outputs = [
        table_to_aig(aig, rng.random(1 << n_vars) < density, None,
                     [2 * (i + 1) for i in range(n_vars)])
        for _ in range(n_outs)]
    return synthesize(aig)


def test_device_plan_shape_and_padding():
    mapped = _random_mapped(1, 8, 2)
    dp = compile_device_plan(mapped)
    lvl = mapped.levels()
    widths = {}
    for l in mapped.luts:
        widths[lvl[l.root]] = widths.get(lvl[l.root], 0) + 1
    assert dp.n_levels == len(widths)
    assert dp.level_width == max(widths.values())
    assert dp.leaf_idx.shape == (dp.n_levels, dp.level_width, mapped.k)
    assert dp.tt_bits.shape == (dp.n_levels, dp.level_width, 1 << mapped.k)
    # padded slots: all-zero masks, const leaves, dump-row output
    n_pad = dp.n_levels * dp.level_width - mapped.n_luts
    assert int((dp.out_wires == dp.n_wires).sum()) == n_pad
    assert not dp.tt_bits[dp.out_wires == dp.n_wires].any()
    assert not dp.leaf_idx[dp.out_wires == dp.n_wires].any()


def test_scan_and_gather_oracles_match():
    mapped = _random_mapped(2, 9, 2)
    dp = compile_device_plan(mapped, _compile_plan(mapped))
    words = random_words(mapped.n_pis, 5, seed=3)
    want = execute_packed(mapped, words)

    plane = np.asarray(lut_eval_ref(
        jnp.asarray(words.view(np.int32)),
        jnp.asarray(dp.leaf_idx.reshape(-1, dp.k), jnp.int32),
        jnp.asarray(np.ascontiguousarray(
            dp.tt_bits.reshape(-1, 1 << dp.k)).view(np.int32)),
        jnp.asarray(dp.out_wires.reshape(-1), jnp.int32),
        dp.n_pis, dp.n_wires)).view(np.uint32)
    out = plane[dp.out_idx]
    out[dp.out_neg] = ~out[dp.out_neg]
    np.testing.assert_array_equal(out, want)

    n_samples = 5 * 32
    bits = unpack_bits(words, n_samples).astype(np.int32)
    gplane = np.asarray(lut_eval_gather_ref(
        jnp.asarray(bits), jnp.asarray(dp.leaf_idx),
        jnp.asarray((dp.tt_bits & 1).astype(np.int32)),
        jnp.asarray(dp.out_wires), dp.n_pis, dp.n_wires))
    gout = gplane[dp.out_idx].astype(np.uint8)
    gout[dp.out_neg] = 1 - gout[dp.out_neg]
    np.testing.assert_array_equal(gout, unpack_bits(want, n_samples))


# ---------------------------------------------------------------------------
# Streamed/tiled kernel (TilePlan route) and the engine names
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("gather", ["dma", "vmem"])
@pytest.mark.parametrize("n_words", [1, 7, 130])
def test_streamed_matches_numpy_fold_ragged(n_words, gather):
    """Both gather modes of the streamed kernel match the host fold
    bit-exactly on ragged word counts, 130 taking two 128-lane
    blocks."""
    from repro.synth import execute_packed_streamed
    mapped = _random_mapped(0, 9, 3)
    words = random_words(mapped.n_pis, n_words, seed=n_words)
    np.testing.assert_array_equal(
        execute_packed(mapped, words),
        execute_packed_streamed(mapped, words, gather=gather))


def test_streamed_constant_network():
    from repro.synth import execute_packed_streamed
    aig = AIG(3)
    aig.outputs = [1]           # const-1 literal
    mapped = synthesize(aig)
    assert mapped.n_luts == 0
    words = random_words(3, 4, seed=0)
    np.testing.assert_array_equal(
        execute_packed(mapped, words),
        execute_packed_streamed(mapped, words))


@pytest.mark.parametrize("gather", ["dma", "vmem"])
def test_streamed_multi_tile_levels(gather):
    """tile_rows smaller than every level forces multi-tile bands (and
    gather reuse across tiles); results stay bit-identical."""
    from repro.synth import compile_tile_plan, execute_packed_streamed
    from repro.synth.executor import _compile_plan as cp
    mapped = _random_mapped(4, 10, 4)
    plan = cp(mapped)
    tp = compile_tile_plan(plan, mapped.n_pis, mapped.k, tile_rows=8)
    assert tp.n_tiles > len(plan.levels)     # levels actually split
    words = random_words(mapped.n_pis, 9, seed=2)
    np.testing.assert_array_equal(
        execute_packed(mapped, words),
        execute_packed_streamed(mapped, words, tplan=tp, gather=gather))


def test_pack_tile_meta_round_trips_the_tile_plan():
    """The streamed kernel's per-tile record holds exactly the plan's
    band base, leaves and INIT bits, in 128-lane rows: the staged
    remap (``leaf_loc`` into ``gather_rows``) with the plane in HBM,
    the plane rows themselves (``leaf_tiles``) with it in VMEM."""
    from repro.kernels.lut_eval.lut_eval import (LANES, _meta_layout,
                                                 pack_tile_meta)
    from repro.synth import compile_tile_plan
    from repro.synth.executor import _compile_plan as cp
    mapped = _random_mapped(4, 10, 4)
    tp = compile_tile_plan(cp(mapped), mapped.n_pis, mapped.k, tile_rows=8)
    T, k = tp.tile_rows, tp.k
    for gather in ("dma", "vmem"):
        G = 0 if gather == "vmem" else tp.gather_cap
        loc, grow, init, n_words, rows = _meta_layout(T, G, k)
        meta = pack_tile_meta(tp, gather)
        assert meta.shape == (tp.n_tiles, rows, LANES)
        assert meta.dtype == np.int32
        flat = meta.reshape(tp.n_tiles, -1)
        np.testing.assert_array_equal(flat[:, 0], tp.out_base)
        leaves = flat[:, loc:grow].reshape(tp.leaf_loc.shape)
        if gather == "vmem":
            assert grow == init
            np.testing.assert_array_equal(leaves, tp.leaf_tiles)
        else:
            np.testing.assert_array_equal(leaves, tp.leaf_loc)
            np.testing.assert_array_equal(flat[:, grow:init],
                                          tp.gather_rows)
        words = flat[:, init:init + T * n_words].view(np.uint32).reshape(
            tp.n_tiles, T, n_words)
        r = np.arange(1 << k)
        bits = (words[:, :, r // 32] >> (r % 32)) & 1
        np.testing.assert_array_equal(bits, tp.tt_tiles & 1)
        assert not flat[:, init + T * n_words:].any()


def test_tile_plan_structure():
    from repro.synth import compile_tile_plan
    from repro.synth.executor import _compile_plan as cp
    mapped = _random_mapped(5, 9, 3)
    plan = cp(mapped)
    T = 16
    tp = compile_tile_plan(plan, mapped.n_pis, mapped.k, tile_rows=T)
    # bands are contiguous multiples of T starting after the PI rows
    assert tp.out_base[0] == 1 + mapped.n_pis
    assert ((np.diff(tp.out_base) % T) == 0).all()
    assert tp.n_rows == tp.out_base[-1] + T
    # staged-gather remap reproduces the direct leaf rows exactly
    staged = tp.gather_rows[np.arange(tp.n_tiles)[:, None, None],
                            tp.leaf_loc]
    np.testing.assert_array_equal(staged, tp.leaf_tiles)
    # every leaf row precedes its tile's band (topological tile order)
    assert (tp.leaf_tiles < tp.out_base[:, None, None]).all()
    # row_of_wire is a bijection onto real (non-pad) rows
    rows = tp.row_of_wire
    assert len(np.unique(rows)) == rows.shape[0]


@pytest.mark.parametrize("gather", ["dma", "vmem"])
@settings(max_examples=10, deadline=None)
@given(n=st.integers(1, 5), n_outs=st.integers(1, 3),
       tile_rows=st.sampled_from([1, 2, 8, 32]), data=st.data())
def test_streamed_exhaustive_property(gather, n, n_outs, tile_rows, data):
    """Random mapped netlists agree with the host fold on every input
    pattern through the streamed kernel, in every gather mode, at tile
    sizes from degenerate (1 slot/tile) to larger-than-any-level."""
    from repro.synth import compile_tile_plan, execute_packed_streamed
    from repro.synth.executor import _compile_plan as cp
    aig = AIG(n)
    aig.outputs = [
        table_to_aig(
            aig,
            np.array([bool((tt >> r) & 1) for r in range(1 << n)]),
            None, [2 * (i + 1) for i in range(n)])
        for tt in (data.draw(st.integers(0, (1 << (1 << n)) - 1))
                   for _ in range(n_outs))]
    mapped = synthesize(aig)
    tp = compile_tile_plan(cp(mapped), mapped.n_pis, mapped.k,
                           tile_rows=tile_rows)
    pats = input_patterns(n)
    np.testing.assert_array_equal(
        execute_packed(mapped, pats),
        execute_packed_streamed(mapped, pats, tplan=tp, gather=gather))


def _const_mapped():
    aig = AIG(3)
    aig.outputs = [1]           # const-1 literal
    return synthesize(aig)


@pytest.mark.parametrize("net,tile_rows,n_words", [
    ("one-tile", 8, 8), ("one-tile", 32, 256),
    ("multi-tile", 8, 8), ("multi-tile", 8, 33),
    ("multi-tile", 32, 33), ("multi-tile", 32, 256),
    ("constant", 8, 33)])
def test_streamed_vmem_gather_matches_dma_and_numpy(net, tile_rows, n_words):
    """The resident-plane mode writes the same wire plane as the
    staged-DMA mode, and its outputs equal the host fold: one tile and
    several, at 8 and 32 slots a tile, a padded word count (33) and two
    128-lane blocks (256, the VMEM scratch reused across grid steps),
    and the constant network. Every kernel compile here unrolls a whole
    tile, so the cases cover each value rather than every product."""
    from repro.kernels.lut_eval import lut_eval_streamed
    from repro.synth import compile_tile_plan
    from repro.synth.executor import _compile_plan as cp
    mapped = {"one-tile": lambda: _random_mapped(0, 3, 2),
              "multi-tile": lambda: _random_mapped(0, 9, 3),
              "constant": _const_mapped}[net]()
    tp = compile_tile_plan(cp(mapped), mapped.n_pis, mapped.k,
                           tile_rows=tile_rows)
    n_tiles = {"one-tile": tp.n_tiles == 1, "multi-tile": tp.n_tiles > 1,
               "constant": tp.n_tiles == 0}
    assert n_tiles[net], tp.n_tiles
    words = random_words(mapped.n_pis, n_words, seed=n_words)
    vmem = lut_eval_streamed(words, tp, gather="vmem")
    np.testing.assert_array_equal(
        vmem, lut_eval_streamed(words, tp, gather="dma"))
    out = vmem[tp.out_idx]
    out[tp.out_neg] = ~out[tp.out_neg]
    np.testing.assert_array_equal(out, execute_packed(mapped, words))


@pytest.mark.parametrize("block_w,lanes", [(8, 128), (128, 128),
                                           (256, 256)])
def test_gather_mode_at_the_vmem_budget(block_w, lanes):
    """The plane stays in VMEM up to half the core's capacity, counted
    with rows padded to 8 and words to whole 128-lane blocks; one more
    row block of plane takes the HBM (``dma``) path."""
    from repro.check.plan_check import gather_mode, resident_plane_bytes
    cap = 1 << 20
    rows_at_budget = cap // 2 // (lanes * 4)          # a multiple of 8
    at = types.SimpleNamespace(n_rows=rows_at_budget - 7)
    over = types.SimpleNamespace(n_rows=rows_at_budget + 1)
    assert resident_plane_bytes(at, block_w) == cap // 2
    assert resident_plane_bytes(over, block_w) == cap // 2 + 8 * lanes * 4
    assert gather_mode(at, cap, block_w) == "vmem"
    assert gather_mode(over, cap, block_w) == "dma"
    assert gather_mode(at, cap - 1, block_w) == "dma"


def _streamed_executor(mapped):
    from repro.synth.executor import _compile_plan, _DeviceExecutor
    # the executor reads only these attributes of its BitplaneNetwork
    bitnet = types.SimpleNamespace(
        mapped=mapped, _plan=_compile_plan(mapped), in_bits=1, out_bits=1,
        out_levels=np.arange(2, dtype=np.float32), device=None)
    return _DeviceExecutor(bitnet, interpret=True)


def test_streamed_executor_gather_counters(monkeypatch):
    """The engine takes the mode the plan's size gives (a v5e's VMEM
    when interpreting) and says so on its ``fetch`` spans:
    ``staged_rows`` is 0 where nothing is staged."""
    from repro.check.plan_check import (V5E_VMEM_BYTES, gather_mode,
                                        resident_plane_bytes)
    from repro.synth.executor import staged_rows
    lut_eval = importlib.import_module("repro.kernels.lut_eval.lut_eval")
    mapped = _random_mapped(0, 9, 3)
    ex = _streamed_executor(mapped)
    assert ex.gather == gather_mode(ex.tp, V5E_VMEM_BYTES) == "vmem"
    assert ex.fetch_args == {"luts": mapped.n_luts, "tiles": ex.tp.n_tiles,
                             "gather": "vmem", "staged_rows": 0}
    # a core whose budget the plane overflows keeps the plane in HBM
    monkeypatch.setattr(lut_eval, "vmem_capacity_bytes",
                        lambda interpret: resident_plane_bytes(ex.tp))
    small = _streamed_executor(mapped)
    assert small.gather == "dma"
    assert small.fetch_args["gather"] == "dma"
    assert small.fetch_args["staged_rows"] == staged_rows(small.tp) > 0


def test_over_vmem_netlist_runs_streamed():
    """A budget below the whole wire plane still passes plan validation,
    which checks the tile step's working set, and the netlist runs with
    the plane in HBM bit-identically to the numpy fold (the whole point
    of tiling)."""
    from repro.check import estimate_tile_vmem_bytes, validate_device_plan
    from repro.check.plan_check import resident_plane_bytes
    from repro.synth import compile_device_plan, execute_packed_streamed
    mapped = _random_mapped(6, 10, 16)
    dp = compile_device_plan(mapped, tile_rows=8)
    plane = resident_plane_bytes(dp.tiles)
    tiled = estimate_tile_vmem_bytes(dp.tiles)
    assert tiled < plane          # tiling shrinks the working set
    rep = validate_device_plan(dp, vmem_budget_bytes=(plane + tiled) // 2,
                               use_cache=False)
    assert rep.ok, [str(i) for i in rep.issues]
    words = random_words(mapped.n_pis, 33, seed=7)
    np.testing.assert_array_equal(
        execute_packed(mapped, words),
        execute_packed_streamed(mapped, words, tplan=dp.tiles,
                                gather="dma"))


def test_plan_check_tile_budget_reject():
    """Tile working sets over budget are rejected with the tile-aware
    message; corrupted tile schedules are caught structurally."""
    from repro.check import validate_device_plan
    from repro.synth import compile_device_plan
    mapped = _random_mapped(7, 9, 3)
    dp = compile_device_plan(mapped, tile_rows=32)
    rep = validate_device_plan(dp, vmem_budget_bytes=1024,
                               use_cache=False)
    assert any(i.code == "vmem-budget" and "tile" in i.message
               for i in rep.issues)
    # corrupt the staged-gather remap: structural tile check fires
    dp.tiles.gather_rows = dp.tiles.gather_rows.copy()
    dp.tiles.gather_rows[0, 0] = dp.tiles.gather_rows[0, 0] + 1 \
        if dp.tiles.gather_cap > 0 else 0
    rep2 = validate_device_plan(dp, use_cache=False)
    assert any(i.code == "tile-gather" for i in rep2.issues)


def _build_with_engine(how, engine):
    """Build a network (or serving engine) of a small net on ``engine``
    the way each public entry point does."""
    from repro.core.logic_infer import LogicNetwork
    from repro.core.quant import ActQuantSpec
    from repro.core.truthtable import LayerTables
    from repro.serving.engine import LogicEngine
    from repro.synth import BitplaneNetwork, store

    spec = ActQuantSpec("signed", 2)
    rng = np.random.default_rng(0)
    idx = np.stack([rng.choice(6, 3, replace=False)
                    for _ in range(5)]).astype(np.int32)
    tab = rng.integers(0, spec.n_levels, (5, spec.n_levels ** 3))
    net = LogicNetwork([LayerTables(idx, tab.astype(np.int8), spec, spec,
                                    1.0, 0.9)], spec, 1.0, 6, 5)
    if how == "BitplaneNetwork":
        return BitplaneNetwork(net, _random_mapped(0, 3, 2), engine=engine)
    if how == "from_logic_network":
        return BitplaneNetwork.from_logic_network(net, engine=engine)
    if how == "from_store":
        return BitplaneNetwork.from_store(net, engine=engine)
    return LogicEngine(net, 5, backend="bitplane", engine=engine)


@pytest.mark.parametrize("how", ["BitplaneNetwork", "from_logic_network",
                                 "from_store", "LogicEngine"])
def test_unknown_engine_typed_error(how, monkeypatch):
    """The engine names are ``ENGINES``; any other, the deleted
    monolithic ``"pallas"`` engine among them, raises the typed error
    naming both, before any synthesis or netlist load."""
    import repro.synth as synth
    from repro.synth import ENGINES, UnknownEngineError, store

    def forbidden(*a, **kw):
        raise AssertionError("synthesis or a netlist load was started")
    monkeypatch.setattr(synth, "synthesize", forbidden)
    monkeypatch.setattr(store, "load", forbidden)
    assert ENGINES == ("numpy", "pallas-streamed")
    for name in ("pallas", "definitely-not-an-engine"):
        with pytest.raises(UnknownEngineError) as e:
            _build_with_engine(how, name)
        assert e.value.name == name
        assert "numpy" in str(e.value) and "pallas-streamed" in str(e.value)


@pytest.mark.parametrize("entry", ["lut_eval_streamed",
                                   "lut_eval_streamed_pallas"])
def test_unknown_gather_mode_raises(entry):
    """Only ``dma`` and ``vmem`` exist: any other mode raises instead of
    running one of them."""
    from repro.kernels.lut_eval import lut_eval_streamed
    from repro.kernels.lut_eval.lut_eval import (GATHER_MODES,
                                                 lut_eval_streamed_pallas,
                                                 pack_tile_meta)
    from repro.synth import compile_tile_plan
    from repro.synth.executor import _compile_plan as cp
    assert GATHER_MODES == ("dma", "vmem")
    mapped = _random_mapped(0, 9, 3)
    tp = compile_tile_plan(cp(mapped), mapped.n_pis, mapped.k)
    words = random_words(mapped.n_pis, 8, seed=0)
    with pytest.raises(ValueError, match="'dma', 'vmem'"):
        if entry == "lut_eval_streamed":
            lut_eval_streamed(words, tp, gather="gather")
        else:
            lut_eval_streamed_pallas(
                jnp.asarray(words.view(np.int32)),
                jnp.asarray(pack_tile_meta(tp, "dma")), n_pis=tp.n_pis,
                n_tiles=tp.n_tiles, tile_rows=tp.tile_rows,
                gather_cap=tp.gather_cap, n_rows=tp.n_rows, k=tp.k,
                gather="gather")
