"""Kernel microbenchmarks: Pallas (interpret) vs jnp oracle, µs/call.

Interpret-mode timings on CPU are NOT TPU performance; the derived
column reports the work size (elements or MACs) so roofline reasoning
stays attached to each number.
"""
from __future__ import annotations

import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np


def _t(fn, *args, iters=20) -> float:
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def run() -> Dict:
    rng = np.random.default_rng(0)
    out = {}

    # lut_layer: JSC-M-ish layer
    from repro.kernels.lut_layer import lut_layer, lut_layer_ref
    B, n_in, N, K, L = 256, 64, 64, 4, 4
    codes = jnp.asarray(rng.integers(0, L, (B, n_in)), jnp.int32)
    idx = jnp.asarray(rng.integers(0, n_in, (N, K)), jnp.int32)
    tables = jnp.asarray(rng.integers(0, L, (N, L ** K)), jnp.int32)
    f_ref = jax.jit(lambda c: lut_layer_ref(c, idx, tables, L))
    f_pal = jax.jit(lambda c: lut_layer(c, idx, tables, L))
    out["lut_layer_ref_us"] = _t(f_ref, codes)
    out["lut_layer_pallas_us"] = _t(f_pal, codes)

    # aig_sim: bit-parallel simulation of a random-logic AIG, 8k samples
    from repro.kernels.aig_sim import aig_sim, aig_sim_ref
    from repro.synth import AIG
    from repro.synth.from_sop import table_to_aig
    n_vars = 8
    aig = AIG(n_vars)
    aig.outputs = [
        table_to_aig(aig, rng.random(1 << n_vars) < 0.5, None,
                     [2 * (i + 1) for i in range(n_vars)])
        for _ in range(4)]
    f0, f1 = aig.fanin_arrays()
    words = jnp.asarray(rng.integers(0, 1 << 31, (n_vars, 256)), jnp.int32)
    f0j, f1j = jnp.asarray(f0), jnp.asarray(f1)
    out["aig_sim_ref_us"] = _t(
        jax.jit(lambda w: aig_sim_ref(w, f0j, f1j, n_vars)), words)
    out["aig_sim_pallas_us"] = _t(
        lambda w: aig_sim(np.asarray(w).view(np.uint32), f0, f1, n_vars),
        words, iters=3)

    # lut_eval: whole mapped-netlist execution, 8k samples — the bitplane
    # Shannon fold (numpy host / jnp scan oracle / Pallas kernel) vs the
    # per-sample table-gather path on the same netlist
    from repro.kernels.lut_eval import (lut_eval_gather_ref, lut_eval_ref,
                                        lut_eval_streamed)
    from repro.synth import compile_device_plan, synthesize
    from repro.synth.executor import _compile_plan, execute_packed
    from repro.synth.simulate import unpack_bits
    n_vars = 10
    aig2 = AIG(n_vars)
    aig2.outputs = [
        table_to_aig(aig2, rng.random(1 << n_vars) < 0.5, None,
                     [2 * (i + 1) for i in range(n_vars)])
        for _ in range(4)]
    mapped = synthesize(aig2)
    plan = _compile_plan(mapped)
    dp = compile_device_plan(mapped, plan)
    lwords = rng.integers(0, 1 << 32, (n_vars, 256), dtype=np.uint32)
    out["lut_eval_numpy_us"] = _t(
        lambda w: execute_packed(mapped, w, plan=plan), lwords)
    flat_leaf = jnp.asarray(dp.leaf_idx.reshape(-1, dp.k), jnp.int32)
    flat_tt = jnp.asarray(np.ascontiguousarray(
        dp.tt_bits.reshape(-1, 1 << dp.k)).view(np.int32))
    flat_ow = jnp.asarray(dp.out_wires.reshape(-1), jnp.int32)
    out["lut_eval_ref_us"] = _t(
        jax.jit(lambda w: lut_eval_ref(w, flat_leaf, flat_tt, flat_ow,
                                       dp.n_pis, dp.n_wires)),
        jnp.asarray(lwords.view(np.int32)))
    # the served kernel over the same netlist's tile plan
    out["lut_eval_streamed_us"] = _t(
        lambda w: lut_eval_streamed(w, dp.tiles), lwords, iters=5)
    # dimensionless cross-kernel ratio (direction-aware CI gate; the
    # *_us rows drift with host load, the ratio should not)
    out["aig_sim_pallas_vs_ref_x"] = (
        out["aig_sim_pallas_us"] / out["aig_sim_ref_us"])
    lbits = jnp.asarray(unpack_bits(lwords, 256 * 32), jnp.int32)
    tt01 = jnp.asarray((dp.tt_bits & 1).astype(np.int32))
    li, ow = jnp.asarray(dp.leaf_idx), jnp.asarray(dp.out_wires)
    out["lut_eval_gather_us"] = _t(
        jax.jit(lambda b: lut_eval_gather_ref(b, li, tt01, ow,
                                              dp.n_pis, dp.n_wires)), lbits)

    # xnor: 256x4096 @ 4096x256
    from repro.kernels.xnor_popcount import (pack_bipolar, xnor_matmul,
                                             xnor_matmul_ref)
    x = jnp.asarray(rng.choice([-1.0, 1.0], (256, 4096)), jnp.float32)
    w = jnp.asarray(rng.choice([-1.0, 1.0], (256, 4096)), jnp.float32)
    out["xnor_ref_us"] = _t(jax.jit(xnor_matmul_ref), x, w)
    out["xnor_pallas_us"] = _t(jax.jit(xnor_matmul), x, w)

    # fanin_matmul: FCP layer 256 x (4096 -> 1024, K=8)
    from repro.kernels.fanin_matmul import fanin_matmul, fanin_matmul_ref
    xb = jnp.asarray(rng.normal(size=(256, 4096)), jnp.float32)
    idxb = jnp.asarray(rng.integers(0, 4096, (1024, 8)), jnp.int32)
    wb = jnp.asarray(rng.normal(size=(1024, 8)), jnp.float32)
    bias = jnp.zeros((1024,), jnp.float32)
    out["fanin_ref_us"] = _t(jax.jit(fanin_matmul_ref), xb, idxb, wb, bias)
    out["fanin_pallas_us"] = _t(jax.jit(fanin_matmul), xb, idxb, wb, bias)
    # dense equivalent cost at same shapes (what FCP saves)
    wd = jnp.asarray(rng.normal(size=(1024, 4096)), jnp.float32)
    out["fanin_dense_us"] = _t(jax.jit(lambda x: x @ wd.T), xb)

    # flash attention: 1k context, 4 heads
    from repro.kernels.flash_attention import flash_attention
    from repro.models.layers import full_attention
    q = jnp.asarray(rng.normal(size=(1, 1024, 4, 64)), jnp.float32)
    kk = jnp.asarray(rng.normal(size=(1, 1024, 2, 64)), jnp.float32)
    vv = jnp.asarray(rng.normal(size=(1, 1024, 2, 64)), jnp.float32)
    out["flash_ref_us"] = _t(jax.jit(
        lambda q, k, v: full_attention(q, k, v, causal=True)), q, kk, vv)
    out["flash_pallas_us"] = _t(jax.jit(
        lambda q, k, v: flash_attention(q, k, v)), q, kk, vv, iters=3)

    for k, v in out.items():
        print(f"[kernels] {k}: {v:.1f}")
    return out


if __name__ == "__main__":
    run()
