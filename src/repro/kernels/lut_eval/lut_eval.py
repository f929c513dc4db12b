"""Pallas kernel: whole-netlist evaluation of a mapped k-LUT network.

``lut_eval_streamed_pallas`` walks a level-major tile plan
(``repro.synth.executor.compile_tile_plan``): every tile of ``T`` slots
writes one contiguous row band, and slot i gathers its k leaf planes
and folds its 2^k-entry INIT vector over them (a Shannon-cofactor fold:
k select steps, each one AND/ANDN/OR over the whole word tile). Each
tile's scalars (band base, INIT bits, leaf indices) travel as one
packed ``(R, 128)`` record (``pack_tile_meta``) that streams HBM→SMEM
through a two-slot buffer: tile ``t+1``'s record DMA starts before tile
``t``'s fold, so the plan fetch hides behind compute.

Where the wire plane lives, and so how leaves are read, is the one
mode-dependent step (``gather=``, one of ``GATHER_MODES``), chosen from
the plan's size (``repro.check.plan_check.gather_mode``):

  * ``"vmem"`` — the whole plane of one 128-lane word block is a 2-D
    VMEM scratch: the head rows are vector stores, every leaf is a
    one-row vector load at the plane row its record names, every
    slot's output a one-row vector store, and the finished plane goes
    to HBM in one DMA per grid step. Chosen whenever the padded plane
    fits the core's VMEM budget.
  * ``"dma"`` — the plane stays in HBM (``memory_space=ANY``), for
    nets too large for VMEM: each tile's unique leaf rows are staged
    HBM→VMEM by per-row async copies into a two-slot stage buffer,
    slots fold from stage-local indices read as SMEM scalars, and the
    band is stored with one contiguous DMA. Mosaic slices HBM refs only
    at whole (8, 128) tiles and only DMAs may touch an ``ANY`` ref, so
    this plane is 3-D, ``(rows, 1, W)``, with the word axis padded to
    128 lanes: one wire row is then a leading-dim slice that a DMA may
    move, and the const-0 and PI rows are written by DMA too.

Both modes share the fold and write the same plane bit for bit.
Levelization guarantees every leaf lives on a strictly earlier level,
so tile-order execution is a topological order; padded slots inside a
band read the constant-0 row with all-zero INIT masks and write 0 to
their own (never-read) pad row — no dump-row branch needed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BW = 128   # word (packed-sample) tile, lane-aligned

GATHER_MODES = ("dma", "vmem")

# VMEM the resident plane's kernel may take beyond the plane itself
# (the PI block, fold state and the compiler's own scratch)
VMEM_MARGIN = 16 << 20


def vmem_capacity_bytes(interpret: bool) -> int:
    """One core's VMEM: the attached chip's when compiling for it, a
    v5e's when interpreting, so the CPU suite takes the chip's
    decisions."""
    from repro.check.plan_check import V5E_VMEM_BYTES
    if interpret:
        return V5E_VMEM_BYTES
    return pltpu.get_tpu_info().vmem_capacity_bytes


def check_gather(gather: str) -> None:
    """Refuse a gather mode the kernel does not have."""
    if gather not in GATHER_MODES:
        raise ValueError(f"unknown gather mode {gather!r} "
                         f"(expected one of {GATHER_MODES})")


def default_gather(tplan, interpret: bool, block_w: int = DEFAULT_BW) -> str:
    """The gather mode the plan's size gives (``gather_mode``):
    ``"vmem"`` where its padded plane fits the core's VMEM budget,
    ``"dma"`` otherwise."""
    from repro.check.plan_check import gather_mode
    return gather_mode(tplan, vmem_capacity_bytes(interpret), block_w)


LANES = 128   # Mosaic slices HBM/VMEM refs only at whole 128-lane tiles


def _meta_layout(T: int, G: int, k: int):
    """Offsets of one tile's scalar record in the ``meta`` operand:
    out_base, then the slots' leaves (T*k), ``gather_rows`` (G) and the
    INIT bits packed as ``n_words`` int32 words per slot."""
    if k > 6:
        raise ValueError(f"k={k}: a slot's INIT bits must fit two words")
    n_words = -(-(1 << k) // 32)
    loc = 1
    grow = loc + T * k
    init = grow + G
    size = init + T * n_words
    return loc, grow, init, n_words, -(-size // LANES)


def record_gather_cap(gather: str, gather_cap: int) -> int:
    """The ``gather_rows`` a tile's record holds in a gather mode: none
    under ``"vmem"``, whose record names each leaf's plane row itself."""
    return 0 if gather == "vmem" else gather_cap


def pack_tile_meta(tplan, gather: str = "dma") -> np.ndarray:
    """A ``TilePlan``'s per-tile scalars as the streamed kernel's
    ``meta`` operand for a gather mode: (n_tiles, R, 128) int32, one
    record per tile laid out by ``_meta_layout``. Under ``"vmem"`` the
    leaves are plane rows (``leaf_tiles``), read in one SMEM read each;
    under ``"dma"`` they index the tile's ``gather_rows``
    (``leaf_loc``). A 128-lane minor dim is what lets one DMA per tile
    move the whole record into SMEM on the chip."""
    n_tiles, T, k = tplan.n_tiles, tplan.tile_rows, tplan.k
    G = record_gather_cap(gather, tplan.gather_cap)
    loc, grow, init, n_words, rows = _meta_layout(T, G, k)
    flat = np.zeros((n_tiles, rows * LANES), np.int64)
    flat[:, 0] = tplan.out_base
    leaves = tplan.leaf_tiles if G == 0 else tplan.leaf_loc
    flat[:, loc:grow] = leaves.reshape(n_tiles, T * k)
    flat[:, grow:init] = tplan.gather_rows[:, :G]
    bits = (np.asarray(tplan.tt_tiles) & 1).astype(np.int64)  # (n, T, 2^k)
    bits = np.pad(bits, ((0, 0), (0, 0), (0, n_words * 32 - bits.shape[2])))
    words = (bits.reshape(n_tiles, T, n_words, 32)
             << np.arange(32, dtype=np.int64)).sum(axis=3)
    flat[:, init:init + T * n_words] = words.reshape(n_tiles, -1)
    return flat.astype(np.uint32).view(np.int32).reshape(n_tiles, rows, LANES)


def _slot_fold(words, leaf, sh, k: int):
    """Shannon fold of one slot: ``words`` its packed INIT words (SMEM
    scalars), ``leaf(j)`` its j-th leaf row (1, bw), ``sh`` the shift
    iota ``31 - row`` of shape (min(2^k, 32), bw) -> the slot's output
    row (1, bw). Row i's INIT mask is bit i of its word, moved to the
    sign bit and spread to 0 / -1 by an arithmetic shift; with two
    words (k = 6) the first fold step selects between them directly."""
    masks = [(w << sh) >> 31 for w in words]
    if len(masks) == 2:
        sel = leaf(k - 1)
        state = (masks[0] & ~sel) | (masks[1] & sel)
        size, top = 32, k - 2
    else:
        state, size, top = masks[0], 1 << k, k - 1
    for j in range(top, -1, -1):
        half = size // 2
        sel = leaf(j)
        state = (state[:half] & ~sel) | (state[half:size] & sel)
        size = half
    return state


def _fold_tile(out, words, leaf, sh, *, T: int, k: int):
    """Fold a tile's ``T`` slots into ``out[s]`` (1, bw) rows: slot
    ``s`` folds ``words(s)`` over ``leaf(s, j)``. The slots are unrolled
    with static ``s``, so every record offset is a constant, and the
    slots of one tile are independent (their leaves lie on earlier
    levels), so the scheduler interleaves their reads and folds."""
    for s in range(T):
        out[s] = _slot_fold(words(s), functools.partial(leaf, s), sh, k)


def _streamed_kernel(pi_ref, meta_hbm, plane_ref, *, n_pis: int,
                     n_tiles: int, T: int, G: int, k: int, bw: int,
                     gather: str):
    n_tt = 1 << k
    loc_at, grow_at, init_at, n_words, _ = _meta_layout(
        T, record_gather_cap(gather, G), k)
    cols = pl.ds(pl.program_id(0) * bw, bw)

    def scalar(metabuf, slot, i):
        return metabuf[slot, i // LANES, i % LANES]

    def init_words(metabuf, slot, s):
        return [scalar(metabuf, slot, init_at + s * n_words + i)
                for i in range(n_words)]

    sh = 31 - jax.lax.broadcasted_iota(jnp.int32, (min(n_tt, 32), bw), 0)

    if gather == "vmem":
        # The plane of this word block is a VMEM scratch: head rows,
        # leaves and slot outputs are one-row vector accesses, and the
        # finished plane leaves in one DMA.
        def resident(plane, outbuf, metabuf, meta_sem, out_sem):
            def meta_dma(slot, t):
                return pltpu.make_async_copy(meta_hbm.at[t], metabuf.at[slot],
                                             meta_sem.at[slot])

            meta_dma(0, 0).start()
            plane[pl.ds(0, 1), :] = jnp.zeros((1, bw), jnp.int32)
            plane[pl.ds(1, n_pis), :] = pi_ref[...]

            def tile_step(t, carry):
                slot = jax.lax.rem(t, 2)

                @pl.when(t + 1 < n_tiles)
                def _():
                    meta_dma(1 - slot, t + 1).start()

                meta_dma(slot, t).wait()

                def leaf(s, j):
                    row = scalar(metabuf, slot, loc_at + s * k + j)
                    return plane[pl.ds(row, 1), :]

                # the fold writes a separate buffer, so no slot's store
                # orders another slot's leaf loads; then the band lands
                _fold_tile(outbuf, functools.partial(init_words, metabuf,
                                                     slot), leaf, sh,
                           T=T, k=k)
                base = scalar(metabuf, slot, 0)
                for s in range(T):
                    plane[pl.ds(base + s, 1), :] = outbuf[s]
                return carry

            jax.lax.fori_loop(0, n_tiles, tile_step, 0)
            out = pltpu.make_async_copy(plane, plane_ref.at[:, cols], out_sem)
            out.start()
            out.wait()

        pl.run_scoped(resident,
                      plane=pltpu.VMEM((plane_ref.shape[0], bw), jnp.int32),
                      outbuf=pltpu.VMEM((T, 1, bw), jnp.int32),
                      metabuf=pltpu.SMEM((2,) + meta_hbm.shape[1:],
                                         jnp.int32),
                      meta_sem=pltpu.SemaphoreType.DMA((2,)),
                      out_sem=pltpu.SemaphoreType.DMA)
        return

    # The plane lives in HBM (ANY), which only DMAs may touch: write the
    # const-0 row from a zeroed VMEM row and the PI rows straight from
    # the VMEM input block, and land both before any tile reads them.
    def write_head(zero, sems):
        zero[...] = jnp.zeros((1, 1, bw), jnp.int32)
        const_row = pltpu.make_async_copy(
            zero, plane_ref.at[pl.ds(0, 1), :, cols], sems.at[0])
        pi_rows = pltpu.make_async_copy(
            pi_ref, plane_ref.at[pl.ds(1, n_pis), :, cols], sems.at[1])
        const_row.start()
        pi_rows.start()
        const_row.wait()
        pi_rows.wait()

    pl.run_scoped(write_head, zero=pltpu.VMEM((1, 1, bw), jnp.int32),
                  sems=pltpu.SemaphoreType.DMA((2,)))

    # gather == "dma": stage each tile's unique leaf rows HBM->VMEM by
    # per-row async copies; slots fold from stage-local SMEM indices.
    def body(metabuf, stage, outbuf, meta_sem, stage_sem, st_sem):
        def meta_dma(slot, t):
            return pltpu.make_async_copy(meta_hbm.at[t], metabuf.at[slot],
                                         meta_sem.at[slot])

        def stage_row_dma(slot, g):
            row = scalar(metabuf, slot, grow_at + g)
            return pltpu.make_async_copy(
                plane_ref.at[pl.ds(row, 1), :, cols],
                stage.at[slot, pl.ds(g, 1)], stage_sem.at[slot])

        def issue_stage(slot):
            def start_one(g, carry):
                stage_row_dma(slot, g).start()
                return carry
            jax.lax.fori_loop(0, G, start_one, 0)

        def wait_stage(slot):
            def wait_one(g, carry):
                stage_row_dma(slot, g).wait()
                return carry
            jax.lax.fori_loop(0, G, wait_one, 0)

        # warmup: tile 0's record, then its staged leaf rows (the head
        # rows they may read have landed above)
        meta_dma(0, 0).start()
        meta_dma(0, 0).wait()
        issue_stage(0)

        def tile_step(t, carry):
            slot = jax.lax.rem(t, 2)
            nxt = 1 - slot

            @pl.when(t + 1 < n_tiles)
            def _():
                meta_dma(nxt, t + 1).start()

            wait_stage(slot)

            def leaf(s, j):
                return stage[slot, scalar(metabuf, slot, loc_at + s * k + j)]

            _fold_tile(outbuf, functools.partial(init_words, metabuf, slot),
                       leaf, sh, T=T, k=k)
            st = pltpu.make_async_copy(
                outbuf,
                plane_ref.at[pl.ds(scalar(metabuf, slot, 0), T), :, cols],
                st_sem)
            st.start()
            st.wait()     # band landed: tile t+1 may stage-read any row

            @pl.when(t + 1 < n_tiles)
            def _():
                meta_dma(nxt, t + 1).wait()
                issue_stage(nxt)
            return carry

        jax.lax.fori_loop(0, n_tiles, tile_step, 0)

    pl.run_scoped(body,
                  metabuf=pltpu.SMEM((2,) + meta_hbm.shape[1:], jnp.int32),
                  stage=pltpu.VMEM((2, G, 1, bw), jnp.int32),
                  outbuf=pltpu.VMEM((T, 1, bw), jnp.int32),
                  meta_sem=pltpu.SemaphoreType.DMA((2,)),
                  stage_sem=pltpu.SemaphoreType.DMA((2,)),
                  st_sem=pltpu.SemaphoreType.DMA)


@functools.partial(
    jax.jit,
    static_argnames=("n_pis", "n_tiles", "tile_rows", "gather_cap",
                     "n_rows", "k", "block_w", "gather", "interpret"))
def lut_eval_streamed_pallas(pi_words: jax.Array, meta: jax.Array,
                             n_pis: int, n_tiles: int, tile_rows: int,
                             gather_cap: int, n_rows: int, k: int,
                             block_w: int = DEFAULT_BW,
                             gather: str = "dma",
                             interpret: bool = True) -> jax.Array:
    """Streamed walk over a level-major tile plan (see
    ``repro.synth.executor.compile_tile_plan`` for the plan itself).

    pi_words: (n_pis, W) int32; meta: (n_tiles, R, 128) int32 per-tile
    records from ``pack_tile_meta`` for the same ``gather``. Returns the
    renumbered wire plane (n_rows, W) int32 — row 0 const-0, rows
    1..n_pis the inputs, then one band of ``T`` rows per tile (pad rows
    hold 0).

    Inside, the word axis is padded to whole 128-lane tiles. Under
    ``"vmem"`` the plane is 2-D with its rows padded to a multiple of 8
    (the copy-out DMA moves whole (8, 128) tiles), and the kernel may
    take that plane plus ``VMEM_MARGIN`` of VMEM. Under ``"dma"``
    each wire row is its own ``(1, words)`` slab of a 3-D plane, so
    every DMA the kernel makes slices only whole tiles. A row still
    costs one vreg row at any W <= 128, so the padding adds DMA bytes,
    not folds.
    """
    check_gather(gather)
    rows = _meta_layout(tile_rows, record_gather_cap(gather, gather_cap),
                        k)[-1]
    if meta.shape[1:] != (rows, LANES):
        raise ValueError(f"meta records {meta.shape[1:]} are not the "
                         f"{gather!r} layout ({rows}, {LANES}): pack them "
                         f"with pack_tile_meta(tplan, {gather!r})")
    _, w = pi_words.shape
    bw = -(-max(block_w, 1) // LANES) * LANES
    wp = -(-w // bw) * bw
    words = jnp.pad(pi_words, ((0, 0), (0, wp - w)))
    if gather == "vmem":
        plane_rows = -(-n_rows // 8) * 8
        pi_spec = pl.BlockSpec((n_pis, bw), lambda i: (0, i))
        plane_shape = (plane_rows, wp)
        params = pltpu.CompilerParams(
            vmem_limit_bytes=plane_rows * bw * 4 + VMEM_MARGIN)
    else:
        words = words.reshape(n_pis, 1, wp)
        pi_spec = pl.BlockSpec((n_pis, 1, bw), lambda i: (0, 0, i))
        plane_shape = (n_rows, 1, wp)
        params = None
    plane = pl.pallas_call(
        functools.partial(_streamed_kernel, n_pis=n_pis, n_tiles=n_tiles,
                          T=tile_rows, G=gather_cap, k=k, bw=bw,
                          gather=gather),
        grid=(wp // bw,),
        in_specs=[pi_spec,                                   # pi block
                  pl.BlockSpec(memory_space=pl.ANY)],        # meta
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(plane_shape, jnp.int32),
        compiler_params=params,
        interpret=interpret,
    )(words, meta)
    return plane.reshape(plane_shape[0], wp)[:n_rows, :w]
