"""Host-array wrapper for the lut_eval Pallas kernel (pad + unpad).

``lut_eval_streamed`` launches the streamed/tiled kernel over a
``repro.synth.executor.TilePlan``; an optional ``spec=``
(``repro.kernels.spec.KernelSpec``) carries the word tile and the
interpret pin.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from ..spec import DEFAULT_SPEC, KernelSpec, default_interpret  # noqa: F401
from .lut_eval import (check_gather, default_gather, lut_eval_streamed_pallas,
                       pack_tile_meta)


def lut_eval_streamed(pi_words: np.ndarray, tplan,
                      gather: Optional[str] = None,
                      interpret: Optional[bool] = None,
                      spec: Optional[KernelSpec] = None) -> np.ndarray:
    """Evaluate a ``TilePlan`` on packed words through the streamed
    kernel; returns the renumbered (tplan.n_rows, W) uint32 wire plane
    (use ``tplan.out_idx`` / ``tplan.row_of_wire`` to pull outputs).

    pi_words: (n_pis, W) uint32. ``gather=None`` picks the mode the
    plan's size gives (``lut_eval.default_gather``); any other mode
    than ``"dma"`` or ``"vmem"`` raises ``ValueError``.
    ``spec.tile.block_w`` sets the word tile (``tile_rows`` geometry is
    baked into the plan itself).
    """
    spec = DEFAULT_SPEC if spec is None else spec
    pi_words = np.ascontiguousarray(pi_words, np.uint32)
    assert pi_words.shape[0] == tplan.n_pis, \
        (pi_words.shape, tplan.n_pis)
    w = pi_words.shape[1]
    interpret = spec.resolve_interpret(interpret)
    if gather is None:
        gather = default_gather(tplan, interpret, spec.tile.block_w)
    check_gather(gather)
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
