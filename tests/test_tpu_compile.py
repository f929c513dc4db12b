"""The served path's kernels compile for a TPU v5e at real widths.

Nothing runs here: each test lowers and compiles for a described (not
attached) v5e chip, which refuses what the Pallas interpreter lets
through — slices not aligned to the (8, 128) tiling, vector access to
HBM refs, too much SMEM or VMEM. The topology is described inside a
fixture, never at import, so that only the worker given this file loads
the TPU compiler.
"""
import importlib
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.lut_eval.lut_eval import (_meta_layout,
                                             lut_eval_streamed_pallas,
                                             record_gather_cap)

K = 6
N_CLASSES = 5
# JSC-S as served: 16 inputs x 2 bits = 32 PIs, ~90 LUTs in 4 levels;
# loadgen's max_batch=256 rows pack into W = 8 words.
JSC_S = dict(n_pis=32, n_tiles=4, gather_cap=96)
# JSC-L-sized tile plan: 16 inputs x 3 bits = 48 PIs, ~12k LUT slots.
JSC_L = dict(n_pis=48, n_tiles=380, gather_cap=192)
# JSC-M as served (bench/configs/jsc-m.json): 48 PIs, 60,415 LUTs in
# 1,902 tiles of 32 slots, up to 159 staged leaf rows per tile.
JSC_M = dict(n_pis=48, n_tiles=1902, gather_cap=159)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:        # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _custom_call_hlo(compiled) -> str:
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    return text


def _compile_streamed(sharding, n_pis, n_tiles, gather_cap, w, gather,
                      tile_rows=32):
    n_rows = 1 + n_pis + n_tiles * tile_rows
    rows = _meta_layout(tile_rows, record_gather_cap(gather, gather_cap),
                        K)[-1]

    def run(words, meta):
        return lut_eval_streamed_pallas(
            words, meta, n_pis=n_pis, n_tiles=n_tiles, tile_rows=tile_rows,
            gather_cap=gather_cap, n_rows=n_rows, k=K, block_w=min(w, 128),
            gather=gather, interpret=False)

    return jax.jit(run).lower(_shape(sharding, n_pis, w),
                              _shape(sharding, n_tiles, rows, 128)).compile()


@pytest.mark.parametrize("gather", ["dma", "vmem"])
@pytest.mark.parametrize("w", [8, 128])
def test_streamed_dma_kernel_compiles_jsc_s(one_chip, w, gather):
    """The serving shape (W = 8) and a lane-aligned one (W = 128), with
    the plane in HBM (staged-DMA leaves) and resident in VMEM."""
    _custom_call_hlo(_compile_streamed(
        one_chip, JSC_S["n_pis"], JSC_S["n_tiles"], JSC_S["gather_cap"], w,
        gather))


@pytest.mark.parametrize("gather", ["dma", "vmem"])
@pytest.mark.parametrize("w", [8, 256])
def test_streamed_dma_kernel_compiles_jsc_l(one_chip, w, gather):
    """A JSC-L-sized plan, with the plane in HBM and in VMEM."""
    _custom_call_hlo(_compile_streamed(
        one_chip, JSC_L["n_pis"], JSC_L["n_tiles"], JSC_L["gather_cap"], w,
        gather))


@pytest.mark.parametrize("gather", ["dma", "vmem"])
@pytest.mark.parametrize("w", [8, 256])
def test_streamed_kernel_compiles_jsc_m(one_chip, w, gather):
    """JSC-M's served plan: its 60,913-row plane (31.2 MB at 128 lanes)
    resident in VMEM, and the same plan with the plane in HBM; W = 256
    runs two grid steps over one scratch."""
    _custom_call_hlo(_compile_streamed(
        one_chip, JSC_M["n_pis"], JSC_M["n_tiles"], JSC_M["gather_cap"], w,
        gather))


def _random_netlist(n_pis: int, n_ands: int, n_outs: int, seed: int = 0):
    from repro.synth import AIG, synthesize
    rng = np.random.default_rng(seed)
    aig = AIG(n_pis)
    lits = [2 * (i + 1) for i in range(n_pis)]
    for _ in range(n_ands):
        a, b = rng.choice(len(lits), 2, replace=False)
        lits.append(aig.and2(lits[a] ^ int(rng.integers(2)),
                             lits[b] ^ int(rng.integers(2))))
    tail = lits[n_pis + n_ands // 2:]
    aig.outputs = [int(tail[i]) for i in rng.choice(len(tail), n_outs,
                                                    replace=False)]
    return synthesize(aig)


@pytest.mark.parametrize("w", [8, 128])
def test_streamed_executor_classify_compiles(one_chip, monkeypatch, w):
    """The fused classify jit the aggregator calls (pack -> streamed
    kernel -> complement -> decode -> argmax) at the serving shape
    (W = 8, 256 rows) and a full-lane pack (W = 128, 4,096 rows), in
    the gather mode the plan's size gives on a v5e core, and with the
    plane in HBM where a core's budget is smaller than the plane."""
    from repro.check.plan_check import (V5E_VMEM_BYTES, gather_mode,
                                        resident_plane_bytes)
    from repro.synth.executor import _compile_plan, _DeviceExecutor
    lut_eval = importlib.import_module("repro.kernels.lut_eval.lut_eval")

    mapped = _random_netlist(JSC_S["n_pis"], 600, N_CLASSES * 3)
    assert mapped.n_luts > 32               # more than one tile
    # the executor reads only these attributes of its BitplaneNetwork
    bitnet = types.SimpleNamespace(
        mapped=mapped, _plan=_compile_plan(mapped), in_bits=2, out_bits=3,
        out_levels=np.arange(8, dtype=np.float32), device=None)
    # the described chip is a v5e; this process's default device is not
    monkeypatch.setattr(lut_eval, "vmem_capacity_bytes",
                        lambda interpret: V5E_VMEM_BYTES)
    ex = _DeviceExecutor(bitnet, interpret=False)
    assert ex.gather == gather_mode(ex.tp, V5E_VMEM_BYTES) == "vmem"
    monkeypatch.setattr(lut_eval, "vmem_capacity_bytes",
                        lambda interpret: resident_plane_bytes(ex.tp))
    over = _DeviceExecutor(bitnet, interpret=False)
    assert over.gather == "dma"
    for e in (ex, over):
        compiled = e._argmax_words.lower(
            _shape(one_chip, JSC_S["n_pis"], w),
            n_classes=N_CLASSES).compile()
        _custom_call_hlo(compiled)
