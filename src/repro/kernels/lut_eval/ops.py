"""Public jit'd wrappers for the lut_eval Pallas kernels (pad + unpad).

``lut_eval`` launches the monolithic kernel over stacked ``DevicePlan``
tensors; ``lut_eval_streamed`` launches the streamed/tiled kernel over a
``repro.synth.executor.TilePlan``. Both take an optional ``spec=``
(``repro.kernels.spec.KernelSpec``) carrying tile geometry and the
interpret pin — the shared launch surface kernels_bench, kernelprof and
the autotuner sweep.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..spec import DEFAULT_SPEC, KernelSpec, default_interpret  # noqa: F401
from .lut_eval import DEFAULT_BW, lut_eval_pallas, lut_eval_streamed_pallas


def lut_eval(pi_words: np.ndarray, leaf_idx: np.ndarray,
             tt_bits: np.ndarray, out_wires: np.ndarray,
             n_pis: int, n_wires: int,
             interpret: Optional[bool] = None,
             spec: Optional[KernelSpec] = None) -> np.ndarray:
    """Evaluate a padded mapped-netlist plan on packed words; returns
    the (n_wires + 1, W) uint32 wire plane (row n_wires is the padded
    slots' dump row).

    pi_words: (n_pis, W) uint32. Plan tensors may be level-stacked
    ((n_levels, Lw, ...), as ``compile_device_plan`` builds them) or
    already flattened to (n_slots, ...); level-major flattening is a
    topological order, so both execute identically.
    """
    spec = DEFAULT_SPEC if spec is None else spec
    pi_words = np.ascontiguousarray(pi_words, np.uint32)
    leaf_idx = np.ascontiguousarray(leaf_idx, np.int32).reshape(
        -1, np.asarray(leaf_idx).shape[-1])
    tt_bits = np.ascontiguousarray(tt_bits, np.uint32).reshape(
        -1, np.asarray(tt_bits).shape[-1])
    out_wires = np.ascontiguousarray(out_wires, np.int32).reshape(-1)
    n_slots, k = leaf_idx.shape
    w = pi_words.shape[1]
    interpret = spec.resolve_interpret(interpret)
    if n_slots == 0 or n_pis == 0 or w == 0:
        vals = np.zeros((n_wires + 1, w), np.uint32)
        vals[1: n_pis + 1] = pi_words
        return vals
    bw = spec.tile.clamp_block_w(w)
    pad = (-w) % bw
    if pad:
        pi_words = np.concatenate(
            [pi_words, np.zeros((n_pis, pad), np.uint32)], axis=1)
    out = lut_eval_pallas(
        jnp.asarray(pi_words.view(np.int32)), jnp.asarray(leaf_idx),
        jnp.asarray(tt_bits.view(np.int32)), jnp.asarray(out_wires),
        n_pis=n_pis, n_slots=n_slots, n_wires=n_wires, k=k,
        block_w=bw, interpret=interpret)
    return np.ascontiguousarray(np.asarray(out)[:, :w]).view(np.uint32)


def lut_eval_streamed(pi_words: np.ndarray, tplan,
                      gather: Optional[str] = None,
                      interpret: Optional[bool] = None,
                      spec: Optional[KernelSpec] = None) -> np.ndarray:
    """Evaluate a ``TilePlan`` on packed words through the streamed
    kernel; returns the renumbered (tplan.n_rows, W) uint32 wire plane
    (use ``tplan.out_idx`` / ``tplan.row_of_wire`` to pull outputs).

    pi_words: (n_pis, W) uint32. ``gather=None`` picks the mode the
    plan's size gives (``lut_eval.default_gather``);
    ``spec.tile.block_w`` sets the word tile (``tile_rows`` geometry is
    baked into the plan itself).
    """
    from .lut_eval import default_gather, pack_tile_meta

    spec = DEFAULT_SPEC if spec is None else spec
    pi_words = np.ascontiguousarray(pi_words, np.uint32)
    assert pi_words.shape[0] == tplan.n_pis, \
        (pi_words.shape, tplan.n_pis)
    w = pi_words.shape[1]
    interpret = spec.resolve_interpret(interpret)
    if gather is None:
        gather = default_gather(tplan, interpret, spec.tile.block_w)
    if tplan.n_tiles == 0 or tplan.n_pis == 0 or w == 0:
        vals = np.zeros((tplan.n_rows, w), np.uint32)
        vals[1: tplan.n_pis + 1] = pi_words
        return vals
    out = lut_eval_streamed_pallas(
        jnp.asarray(pi_words.view(np.int32)),
        jnp.asarray(pack_tile_meta(tplan, gather)),
        n_pis=tplan.n_pis, n_tiles=tplan.n_tiles,
        tile_rows=tplan.tile_rows, gather_cap=tplan.gather_cap,
        n_rows=tplan.n_rows, k=tplan.k,
        block_w=spec.tile.clamp_block_w(w), gather=gather,
        interpret=interpret)
    return np.ascontiguousarray(np.asarray(out)).view(np.uint32)
