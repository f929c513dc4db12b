"""Pallas kernel: bit-parallel AIG simulation.

The AIG node list is a linear program of bitwise ops: node i reads two
earlier value rows, complements per the edge literals, ANDs them, and
writes row i. The kernel keeps the whole value plane (n_nodes, block_w)
resident as its VMEM output block and walks the node list with a
``fori_loop`` of dynamic row loads/stores; fanin literals sit in SMEM so
the per-node address arithmetic is scalar. Words pack 32 samples per
int32 lane, and the grid tiles the word (sample) axis — each program
simulates the full netlist on its own slice of samples, so sample
throughput scales with the grid while the sequential node walk stays
on-chip.

Edge complement trick: literal l = 2*node + c, and XOR with ``-(l & 1)``
(0 or all-ones in two's complement) applies the complement branch-free.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BW = 128   # word (packed-sample) tile, lane-aligned


def _kernel(f0_ref, f1_ref, pis_ref, out_ref, *, n_pis: int, n_ands: int):
    bw = pis_ref.shape[1]
    out_ref[0, :] = jnp.zeros((bw,), jnp.int32)          # const-0 row
    out_ref[1: n_pis + 1, :] = pis_ref[...]

    def body(i, carry):
        l0 = f0_ref[i]
        l1 = f1_ref[i]
        v0 = out_ref[pl.ds(l0 >> 1, 1), :]
        v1 = out_ref[pl.ds(l1 >> 1, 1), :]
        v0 = v0 ^ (-(l0 & 1))
        v1 = v1 ^ (-(l1 & 1))
        out_ref[pl.ds(1 + n_pis + i, 1), :] = v0 & v1
        return carry

    jax.lax.fori_loop(0, n_ands, body, 0)


@functools.partial(
    jax.jit, static_argnames=("n_pis", "n_ands", "block_w", "interpret"))
def aig_sim_pallas(pi_words: jax.Array, f0: jax.Array, f1: jax.Array,
                   n_pis: int, n_ands: int, block_w: int = DEFAULT_BW,
                   interpret: bool = True) -> jax.Array:
    """pi_words: (n_pis, W) int32 packed samples; f0/f1: (n_ands,) int32
    fanin literals (node ids offset as in repro.synth.aig). Returns the
    full value plane (1 + n_pis + n_ands, W) int32 — row 0 is const-0,
    rows 1..n_pis echo the inputs, the rest are AND node values."""
    _, w = pi_words.shape
    assert w % block_w == 0, (w, block_w)
    n_total = 1 + n_pis + n_ands
    grid = (w // block_w,)
    return pl.pallas_call(
        functools.partial(_kernel, n_pis=n_pis, n_ands=n_ands),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((n_pis, block_w), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((n_total, block_w), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((n_total, w), jnp.int32),
        interpret=interpret,
    )(f0, f1, pi_words)
