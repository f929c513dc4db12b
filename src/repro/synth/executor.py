"""Mapped-netlist execution (packed bitplanes) and Verilog emission.

The mapped 6-LUT network is the serving representation: instead of one
table gather per neuron (``repro.core.logic_infer``), inference packs 32
samples per uint32 lane and evaluates each LUT *level* as vectorized
bitwise ops — a Shannon-cofactor fold of every LUT's 64-bit INIT vector
over its six input planes (6 select steps, each one AND/ANDN/OR over the
whole level). Per 32 samples, a LUT costs ~18 word ops regardless of
batch size — the TPU/CPU analogue of the FPGA's spatial LUT fabric.

``BitplaneNetwork(engine=...)`` takes one of two engines (``ENGINES``;
any other name raises ``UnknownEngineError``):

  * ``engine="numpy"``          — the host fold below
    (``execute_packed``), level-by-level vectorized bitwise ops: the
    reference;
  * ``engine="pallas-streamed"`` — the device engine:
    ``compile_tile_plan`` renumbers the wire plane level-major and
    tiles the slot walk, and the ``repro.kernels.lut_eval`` kernel
    folds a whole tile of LUTs per step, with the plane in VMEM where
    it fits and in HBM where it does not (``gather`` ``"vmem"`` or
    ``"dma"``, from the plan's size).

Both are bit-identical on every reachable input; the device engine
fuses bitplane pack, all levels, the output complement and the
per-request argmax into one jit, so nothing touches the host between
enqueue and verdict.

``emit_verilog`` prints the same netlist structurally (one INIT-indexed
assign per LUT), i.e. the post-mapping artifact the paper gets out of
Vivado, where ``repro.core.netlist`` only emitted pre-mapping SOPs.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

from repro.core.quant import encode_inputs_host

from .aig import lit_compl, lit_var, tt_expand
from .lutmap import MappedNetwork
from .simulate import WORD_BITS, pack_bits, unpack_bits

ENGINES = ("numpy", "pallas-streamed")

# wire numbering for execution/emission:
#   wire 0            = constant 0
#   wires 1..n_pis    = primary inputs
#   wires n_pis+1+i   = output of LUT i
_CONST_WIRE = 0

_DEFAULT_TILE_ROWS = 32     # mirrors repro.kernels.spec without importing it


@dataclasses.dataclass
class _LevelArrays:
    leaf_idx: np.ndarray     # (L, k) int32 wire indices (const-padded)
    tt_bits: np.ndarray      # (L, 2^k) uint32 0 / 0xFFFFFFFF masks
    out_wires: np.ndarray    # (L,) int32 wire index written


@dataclasses.dataclass
class _Plan:
    """Precompiled execution plan — everything per-call execution needs
    that does not depend on the batch (built once, reused per batch)."""
    levels: List[_LevelArrays]
    out_idx: np.ndarray      # (n_outputs,) int32 wire index per output
    out_neg: np.ndarray      # (n_outputs,) bool complement flags


def _wire_of(mapped: MappedNetwork, node: int, lut_pos: dict) -> int:
    if node == 0:
        return _CONST_WIRE
    if node <= mapped.n_pis:
        return node
    return mapped.n_pis + 1 + lut_pos[node]


def _compile_plan(mapped: MappedNetwork) -> _Plan:
    k = mapped.k
    lut_pos = {l.root: i for i, l in enumerate(mapped.luts)}
    lvl = mapped.levels()
    by_level: dict = {}
    for i, l in enumerate(mapped.luts):
        by_level.setdefault(lvl[l.root], []).append(i)
    levels: List[_LevelArrays] = []
    for level in sorted(by_level):
        idxs = by_level[level]
        leaf_idx = np.zeros((len(idxs), k), np.int32)
        tt_bits = np.zeros((len(idxs), 1 << k), np.uint32)
        out_wires = np.zeros((len(idxs),), np.int32)
        for row, i in enumerate(idxs):
            l = mapped.luts[i]
            m = len(l.leaves)
            for j, x in enumerate(l.leaves):
                leaf_idx[row, j] = _wire_of(mapped, x, lut_pos)
            tt = tt_expand(l.tt, m, k)     # pad slots read the const wire
            for r in range(1 << k):
                if (tt >> r) & 1:
                    tt_bits[row, r] = 0xFFFFFFFF
            out_wires[row] = mapped.n_pis + 1 + i
        levels.append(_LevelArrays(leaf_idx, tt_bits, out_wires))
    out_idx = np.array([_wire_of(mapped, lit_var(o), lut_pos)
                        for o in mapped.outputs], np.int32)
    out_neg = np.array([bool(lit_compl(o)) for o in mapped.outputs], bool)
    return _Plan(levels, out_idx, out_neg)


def execute_packed(mapped: MappedNetwork, pi_words: np.ndarray,
                   plan: Optional[_Plan] = None) -> np.ndarray:
    """pi_words: (n_pis, W) uint32 -> output words (n_outputs, W)."""
    pi_words = np.asarray(pi_words, np.uint32)
    assert pi_words.shape[0] == mapped.n_pis
    w = pi_words.shape[1]
    if plan is None:
        plan = _compile_plan(mapped)
    wires = np.zeros((mapped.n_pis + 1 + mapped.n_luts, w), np.uint32)
    wires[1: mapped.n_pis + 1] = pi_words
    for la in plan.levels:
        ins = wires[la.leaf_idx]                       # (L, k, W)
        state = np.broadcast_to(
            la.tt_bits[:, :, None], la.tt_bits.shape + (w,)).copy()
        half = state.shape[1] // 2
        for j in range(la.leaf_idx.shape[1] - 1, -1, -1):
            sel = ins[:, j:j + 1, :]                   # (L, 1, W)
            state = (state[:, :half] & ~sel) | (state[:, half:] & sel)
            half //= 2
        wires[la.out_wires] = state[:, 0, :]
    out = wires[plan.out_idx]
    out[plan.out_neg] = ~out[plan.out_neg]
    return out


# ---------------------------------------------------------------------------
# Tile plan: level-major renumbering + slot tiling for the streamed kernel
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TilePlan:
    """The mapped netlist as a streamed tile schedule.

    Wires are renumbered *level-major*: row 0 stays the constant-0
    plane, rows 1..n_pis the primary inputs, then each LUT level
    occupies one contiguous band of rows, padded up to a multiple of
    ``tile_rows`` so every tile writes exactly one contiguous band of
    ``tile_rows`` rows (``out_base[t]`` is its first row). Pad slots
    read the constant row with all-zero INIT masks and therefore write
    0 to their own (never-read) pad row — no per-slot validity branch
    and no dump row.

    ``leaf_tiles`` holds plane-row leaf indices, which the kernel reads
    with the plane in VMEM and the reference that ``repro.check`` holds
    the staged remap to; ``gather_rows``/``leaf_loc`` are that remap,
    which the kernel reads with the plane in HBM
    (``pack_tile_meta``): ``gather_rows[t]`` lists the tile's unique
    leaf rows (padded by re-reading row 0) and
    ``leaf_loc[t, s, j]`` is slot ``s``'s position of leaf ``j`` inside
    that staged buffer. ``row_of_wire`` maps the original executor wire
    numbering (const/PIs/LUT outputs) to renumbered plane rows, so
    callers can pull any original wire out of the streamed plane.
    """

    tt_tiles: np.ndarray     # (n_tiles, T, 2^k) uint32 INIT masks
    leaf_tiles: np.ndarray   # (n_tiles, T, k) int32 plane-row leaves
    leaf_loc: np.ndarray     # (n_tiles, T, k) int32 staged-buffer index
    gather_rows: np.ndarray  # (n_tiles, G) int32 unique rows staged/tile
    out_base: np.ndarray     # (n_tiles,) int32 first row of tile's band
    level_of_tile: np.ndarray  # (n_tiles,) int32 source netlist level
    out_idx: np.ndarray      # (n_outputs,) int32 renumbered output rows
    out_neg: np.ndarray      # (n_outputs,) bool complement flags
    row_of_wire: np.ndarray  # (n_wires,) int32 original wire -> plane row
    n_pis: int
    n_rows: int              # renumbered plane height (incl. pad rows)
    tile_rows: int           # T — LUT slots folded per kernel step
    gather_cap: int          # G — staged leaf rows per tile (DMA mode)
    k: int

    @property
    def n_tiles(self) -> int:
        return self.tt_tiles.shape[0]

    @property
    def n_levels(self) -> int:
        return int(self.level_of_tile.max()) + 1 if self.n_tiles else 0

    def tiles_of_level(self, level: int) -> np.ndarray:
        """Tile indices belonging to one netlist level, in walk order."""
        return np.nonzero(self.level_of_tile == level)[0]


def compile_tile_plan(plan: _Plan, n_pis: int, k: int,
                      tile_rows: int = _DEFAULT_TILE_ROWS) -> TilePlan:
    """Tile the levelized plan for ``lut_eval_streamed_pallas``.

    Each level's slots are cut into tiles of ``tile_rows``; the level's
    output band is padded to a whole number of tiles so band stores
    stay contiguous. Levelization makes tile order a topological order,
    which is what lets the kernel stream tiles back-to-back with only
    plan-tensor DMAs in flight.
    """
    T = max(1, int(tile_rows))
    n_luts = sum(la.out_wires.shape[0] for la in plan.levels)
    n_wires = 1 + n_pis + n_luts
    row_of_wire = np.zeros((n_wires,), np.int32)
    row_of_wire[: n_pis + 1] = np.arange(n_pis + 1, dtype=np.int32)
    base = 1 + n_pis
    bands = []                       # (first_row, n_real_slots, n_tiles)
    for la in plan.levels:
        n_real = la.out_wires.shape[0]
        nt = -(-n_real // T)
        row_of_wire[la.out_wires] = base + np.arange(n_real,
                                                     dtype=np.int32)
        bands.append((base, n_real, nt))
        base += nt * T
    n_rows = base
    n_tiles = sum(b[2] for b in bands)
    tt_tiles = np.zeros((n_tiles, T, 1 << k), np.uint32)
    leaf_tiles = np.zeros((n_tiles, T, k), np.int32)
    leaf_loc = np.zeros((n_tiles, T, k), np.int32)
    out_base = np.zeros((n_tiles,), np.int32)
    level_of_tile = np.zeros((n_tiles,), np.int32)
    uniq: List[np.ndarray] = []
    ti = 0
    for lvl, ((b, n_real, nt), la) in enumerate(zip(bands, plan.levels)):
        for t in range(nt):
            lo, hi = t * T, min((t + 1) * T, n_real)
            n = hi - lo
            tt_tiles[ti, :n] = la.tt_bits[lo:hi]
            leaf_tiles[ti, :n] = row_of_wire[la.leaf_idx[lo:hi]]
            # pad slots keep row-0 leaves + zero INIT (write 0)
            rows, inv = np.unique(leaf_tiles[ti].reshape(-1),
                                  return_inverse=True)
            leaf_loc[ti] = inv.reshape(T, k).astype(np.int32)
            uniq.append(rows.astype(np.int32))
            out_base[ti] = b + lo
            level_of_tile[ti] = lvl
            ti += 1
    gather_cap = max((r.shape[0] for r in uniq), default=1)
    gather_rows = np.zeros((n_tiles, gather_cap), np.int32)
    for ti, rows in enumerate(uniq):
        gather_rows[ti, :rows.shape[0]] = rows   # pad: re-stage row 0
    out_idx = row_of_wire[plan.out_idx].astype(np.int32)
    return TilePlan(tt_tiles, leaf_tiles, leaf_loc, gather_rows, out_base,
                    level_of_tile, out_idx, plan.out_neg.copy(),
                    row_of_wire, n_pis, n_rows, T, gather_cap, k)


def staged_rows(tp: TilePlan) -> int:
    """Unique leaf rows the streamed kernel stages per call, summed over
    tiles (``gather_rows`` without its padding)."""
    if tp.n_tiles == 0:
        return 0
    s = np.sort(tp.leaf_tiles.reshape(tp.n_tiles, -1), axis=1)
    return int(tp.n_tiles + (np.diff(s, axis=1) != 0).sum())


# ---------------------------------------------------------------------------
# Device plan: level-stacked, width-padded tensors (what repro.check reads)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DevicePlan:
    """The mapped netlist as dense plan tensors for on-device execution.

    Levels are padded with no-op slots to the widest level so the
    tensors stack rectangularly: a padded slot reads the constant-0
    wire (all leaves 0, INIT masks 0) and writes the dump row
    ``n_wires`` — one past the last real wire — so the kernel's slot
    walk needs no per-slot validity branch.

    ``tiles`` is the same netlist as the tile schedule the device
    engine runs (``TilePlan``); it is derived data and deliberately
    excluded from ``repro.check.plan_check.plan_fingerprint``.
    """

    leaf_idx: np.ndarray     # (n_levels, Lw, k) int32 wire indices
    tt_bits: np.ndarray      # (n_levels, Lw, 2^k) uint32 INIT masks
    out_wires: np.ndarray    # (n_levels, Lw) int32 wire written
    out_idx: np.ndarray      # (n_outputs,) int32 wire index per output
    out_neg: np.ndarray      # (n_outputs,) bool complement flags
    n_pis: int
    n_wires: int             # 1 + n_pis + n_luts (dump row index)
    k: int
    tiles: TilePlan

    @property
    def n_levels(self) -> int:
        return self.leaf_idx.shape[0]

    @property
    def level_width(self) -> int:
        return self.leaf_idx.shape[1]


def compile_device_plan(mapped: MappedNetwork,
                        plan: Optional[_Plan] = None,
                        verify: bool = False,
                        tile_rows: int = _DEFAULT_TILE_ROWS) -> DevicePlan:
    """Stack the per-level arrays of ``_compile_plan`` into uniform-width
    tensors, and attach the tile schedule at ``tile_rows`` slots a tile
    (``DevicePlan.tiles``). ``verify=True`` runs ``repro.check``'s plan
    validator plus a mapped<->plan miter on the result and raises
    ``CheckFailure`` with the first counterexample on any
    disagreement."""
    if plan is None:
        plan = _compile_plan(mapped)
    k = mapped.k
    n_wires = 1 + mapped.n_pis + mapped.n_luts
    n_levels = len(plan.levels)
    lw = max((la.out_wires.shape[0] for la in plan.levels), default=0)
    leaf_idx = np.full((n_levels, lw, k), _CONST_WIRE, np.int32)
    tt_bits = np.zeros((n_levels, lw, 1 << k), np.uint32)
    out_wires = np.full((n_levels, lw), n_wires, np.int32)   # dump row
    for i, la in enumerate(plan.levels):
        n = la.out_wires.shape[0]
        leaf_idx[i, :n] = la.leaf_idx
        tt_bits[i, :n] = la.tt_bits
        out_wires[i, :n] = la.out_wires
    dplan = DevicePlan(leaf_idx, tt_bits, out_wires,
                       plan.out_idx.copy(), plan.out_neg.copy(),
                       mapped.n_pis, n_wires, k,
                       compile_tile_plan(plan, mapped.n_pis, k, tile_rows))
    if verify:
        from repro.check.pipeline import verify_plan
        verify_plan(mapped, dplan, formal=(verify == "formal"))
    return dplan


def execute_packed_streamed(mapped: MappedNetwork, pi_words: np.ndarray,
                            tplan: Optional[TilePlan] = None,
                            tile_rows: int = _DEFAULT_TILE_ROWS,
                            gather: Optional[str] = None,
                            interpret: Optional[bool] = None) -> np.ndarray:
    """``execute_packed`` through the streamed/tiled kernel: pi_words
    (n_pis, W) uint32 -> output words (n_outputs, W) uint32."""
    from repro.kernels.lut_eval import lut_eval_streamed

    pi_words = np.asarray(pi_words, np.uint32)
    assert pi_words.shape[0] == mapped.n_pis
    if tplan is None:
        tplan = compile_tile_plan(_compile_plan(mapped), mapped.n_pis,
                                  mapped.k, tile_rows)
    plane = lut_eval_streamed(pi_words, tplan, gather=gather,
                              interpret=interpret)
    out = plane[tplan.out_idx]
    out[tplan.out_neg] = ~out[tplan.out_neg]
    return out


# ---------------------------------------------------------------------------
# Executors (the engines behind ``BitplaneNetwork(engine=...)``)
# ---------------------------------------------------------------------------

class _NumpyExecutor:
    """Host-fold engine: ``execute_packed`` level by level, then the
    bitplane decode — no jax anywhere on the path."""

    def __init__(self, bitnet: "BitplaneNetwork",
                 interpret: Optional[bool] = None, spec=None):
        self._b = bitnet

    def apply_codes(self, codes: np.ndarray) -> np.ndarray:
        b = self._b
        codes = np.asarray(codes, np.int64)
        batch = codes.shape[0]
        # codes -> input bitplanes (wire i*in_bits+j = bit j of code i)
        planes = np.empty((codes.shape[1] * b.in_bits, batch), np.uint8)
        for j in range(b.in_bits):
            planes[j::b.in_bits] = ((codes >> j) & 1).T
        out_words = execute_packed(b.mapped, pack_bits(planes),
                                   plan=b._plan)
        return self._decode(out_words, batch)

    def _decode(self, out_words: np.ndarray, batch: int) -> np.ndarray:
        b = self._b
        out_bits = unpack_bits(out_words, batch)       # (n_out_wires, B)
        n_out = out_bits.shape[0] // b.out_bits
        out_codes = np.zeros((batch, n_out), np.int64)
        for j in range(b.out_bits):
            out_codes |= out_bits[j::b.out_bits].T.astype(np.int64) << j
        return out_codes

    def classify_codes(self, codes: np.ndarray,
                       n_classes: int) -> np.ndarray:
        vals = self._b.out_levels[self.apply_codes(codes)]
        return np.argmax(vals[..., :n_classes], axis=-1).astype(np.int32)

    def classify_packed(self, pi_words: np.ndarray, n_rows: int,
                        n_classes: int) -> np.ndarray:
        b = self._b
        out_words = execute_packed(b.mapped, pi_words, plan=b._plan)
        vals = b.out_levels[self._decode(out_words, n_rows)]
        return np.argmax(vals[..., :n_classes], axis=-1).astype(np.int32)


class _DeviceExecutor:
    """The device engine (``"pallas-streamed"``) over a ``TilePlan``.

    Every public entry point is one jit: bitplane pack (32 samples per
    int32 lane), the streamed ``lut_eval`` kernel (double-buffered
    per-tile records, whole-tile folds, the wire plane in VMEM where it
    fits the core's budget, else in HBM: ``gather`` is ``"vmem"`` or
    ``"dma"``, from the plan's size, see
    ``repro.check.plan_check.gather_mode``), the output complement,
    code decode, and — for the classify paths — the ``out_levels``
    gather and per-request argmax. Distinct batch shapes retrace;
    serving callers pin the shape (``pad_rows``) so the hot path
    compiles once.

    Plan tensors and every input are committed to the network's
    ``device`` when it has one, so each jit runs there (one replica per
    chip); with ``device=None`` they land on JAX's default device.
    """

    def __init__(self, bitnet: "BitplaneNetwork",
                 interpret: Optional[bool] = None, spec=None):
        import jax
        import jax.numpy as jnp
        from repro.kernels.lut_eval.lut_eval import (default_gather,
                                                     pack_tile_meta)
        from repro.kernels.spec import DEFAULT_SPEC
        from repro.obs.trace import NULL_TRACER

        self.tracer = NULL_TRACER       # set through BitplaneNetwork.tracer
        self._jnp = jnp
        self.spec = DEFAULT_SPEC if spec is None else spec
        self.interpret = self.spec.resolve_interpret(interpret)
        self.device = bitnet.device
        self.in_bits = bitnet.in_bits
        self.out_bits = bitnet.out_bits
        self._levels = self._put(np.asarray(bitnet.out_levels, np.float32))
        self._apply = jax.jit(self._apply_codes)
        self._argmax_codes = jax.jit(self._argmax_from_codes,
                                     static_argnames=("n_classes",))
        self._argmax_words = jax.jit(self._argmax_from_words,
                                     static_argnames=("n_classes",))
        tp = compile_tile_plan(bitnet._plan, bitnet.mapped.n_pis,
                               bitnet.mapped.k, self.spec.tile.tile_rows)
        self.tp = tp
        self.gather = default_gather(tp, self.interpret,
                                     self.spec.tile.block_w)
        self._meta = self._put(pack_tile_meta(tp, self.gather))
        self._out_idx = self._put(tp.out_idx.astype(np.int32))
        self._neg = self._put(np.where(tp.out_neg, -1, 0).astype(np.int32))
        # plan counts, one dict for every fetch span of this engine
        self.fetch_args = {"luts": bitnet.mapped.n_luts,
                           "tiles": tp.n_tiles,
                           "gather": self.gather,
                           "staged_rows": (staged_rows(tp)
                                           if self.gather == "dma" else 0)}

    def _put(self, a: np.ndarray):
        """Host array -> a jax array on this executor's device."""
        import jax
        return jax.device_put(np.asarray(a), self.device)

    # ---- jit-traced building blocks -------------------------------------

    def _eval_words(self, words):
        """(n_pis, W) int32 -> complemented output words (n_outputs, W)."""
        from repro.kernels.lut_eval.lut_eval import lut_eval_streamed_pallas
        jnp = self._jnp
        tp = self.tp
        w = words.shape[1]
        if tp.n_tiles == 0 or tp.n_pis == 0:     # constant network
            plane = jnp.zeros((tp.n_rows, w), jnp.int32)
            plane = plane.at[1: tp.n_pis + 1].set(words)
        else:
            plane = lut_eval_streamed_pallas(
                words, self._meta, n_pis=tp.n_pis, n_tiles=tp.n_tiles,
                tile_rows=tp.tile_rows, gather_cap=tp.gather_cap,
                n_rows=tp.n_rows, k=tp.k,
                block_w=self.spec.tile.clamp_block_w(w),
                gather=self.gather, interpret=self.interpret)
        return plane[self._out_idx] ^ self._neg[:, None]

    def _pack(self, codes):
        """(B, n_inputs) int32 codes -> (n_pi_wires, ceil(B/32)) int32
        packed bitplanes (wire i*in_bits+b = bit b of code i)."""
        jnp = self._jnp
        b, n_in = codes.shape
        shifts = jnp.arange(self.in_bits, dtype=jnp.int32)
        bits = (codes[:, :, None].astype(jnp.int32) >> shifts) & 1
        planes = bits.reshape(b, n_in * self.in_bits).T
        pad = (-b) % WORD_BITS
        if pad:
            planes = jnp.pad(planes, ((0, 0), (0, pad)))
        lanes = planes.reshape(planes.shape[0], -1, WORD_BITS)
        # disjoint bit positions: int32 wraparound sum == bitwise OR
        return (lanes << jnp.arange(WORD_BITS, dtype=jnp.int32)).sum(
            axis=2, dtype=self._jnp.int32)

    def _decode(self, out_words, b):
        """(n_out_wires, W) int32 words -> (b, n_out) int32 codes."""
        jnp = self._jnp
        shifts = jnp.arange(WORD_BITS, dtype=jnp.int32)
        bits = ((out_words[:, :, None] >> shifts) & 1)
        bits = bits.reshape(out_words.shape[0], -1)[:, :b]
        n_out = out_words.shape[0] // self.out_bits
        grouped = bits.reshape(n_out, self.out_bits, b)
        weights = jnp.arange(self.out_bits, dtype=jnp.int32)[None, :, None]
        return (grouped << weights).sum(axis=1, dtype=jnp.int32).T

    def _apply_codes(self, codes):
        words = self._pack(codes)
        return self._decode(self._eval_words(words), codes.shape[0])

    def _argmax_from_codes(self, codes, n_classes: int):
        jnp = self._jnp
        vals = self._levels[self._apply_codes(codes)]
        return jnp.argmax(vals[..., :n_classes], axis=-1).astype(jnp.int32)

    def _argmax_from_words(self, words, n_classes: int):
        jnp = self._jnp
        out = self._eval_words(words)
        codes = self._decode(out, words.shape[1] * WORD_BITS)
        vals = self._levels[codes]
        return jnp.argmax(vals[..., :n_classes], axis=-1).astype(jnp.int32)

    # ---- host-facing API -------------------------------------------------

    def apply_codes(self, codes: np.ndarray) -> np.ndarray:
        out = self._apply(self._put(np.asarray(codes, np.int32)))
        return np.asarray(out).astype(np.int64)

    def classify_codes(self, codes, n_classes: int) -> np.ndarray:
        return np.asarray(self._argmax_codes(
            self._put(np.asarray(codes, np.int32)), n_classes=n_classes))

    def _put_words(self, pi_words: np.ndarray):
        """Packed PI words -> int32 words on the device (the ``h2d``
        span: ``device_put`` returns once the host has handed the
        buffer over, so the rest of the copy lands in ``fetch``)."""
        tr = self.tracer
        with tr.span("h2d", cat="exec",
                     args={"batch": tr.batch} if tr.enabled else None):
            return self._put(
                np.ascontiguousarray(pi_words, np.uint32).view(np.int32))

    def device_labels(self, pi_words: np.ndarray, n_classes: int):
        """Packed PI words -> per-lane argmax labels, left on the
        device (a jax array of ``W * 32`` labels, not yet awaited)."""
        return self._argmax_words(self._put_words(pi_words),
                                  n_classes=n_classes)

    def classify_packed(self, pi_words: np.ndarray, n_rows: int,
                        n_classes: int) -> "PendingLabels":
        """Packed PI words straight to the device; returns as soon as
        the program is launched (the serve aggregation hot path). Only
        the per-request argmax labels come back, when the caller asks
        for them (``PendingLabels``)."""
        return PendingLabels(self.device_labels(pi_words, n_classes),
                             n_rows, self.tracer, self.fetch_args)


class PendingLabels:
    """Per-request labels of a launched device program, not yet on the
    host. The copy back starts as soon as the program ends;
    ``np.asarray`` waits for it, on whichever thread asks, as the
    ``fetch`` span (the engine's ``fetch_args``, plus the calling
    thread's ``tracer.batch`` when it has one), slices the first
    ``n_rows`` labels and keeps them: a second ``np.asarray`` returns
    the same host array without waiting again."""

    __slots__ = ("_dev", "_n_rows", "_tracer", "_args", "_host")

    def __init__(self, dev, n_rows: int, tracer, fetch_args: dict):
        dev.copy_to_host_async()
        self._dev = dev
        self._n_rows = n_rows
        self._tracer = tracer
        self._args = fetch_args
        self._host: Optional[np.ndarray] = None

    def __array__(self, dtype=None, copy=None) -> np.ndarray:
        if self._host is None:
            tr = self._tracer
            args = self._args
            if tr.enabled and tr.batch is not None:
                args = {**args, "batch": tr.batch}
            with tr.span("fetch", cat="exec", args=args):
                self._host = np.asarray(self._dev)[: self._n_rows]
            self._dev = None
        out = self._host if dtype is None else self._host.astype(dtype)
        return out.copy() if copy and out is self._host else out


_EXECUTORS = {"numpy": _NumpyExecutor, "pallas-streamed": _DeviceExecutor}


class UnknownEngineError(KeyError):
    """An ``engine=`` name that is not one of ``ENGINES``."""

    def __init__(self, name: str):
        self.name = name
        super().__init__(f"unknown bitplane engine {name!r} (engines: "
                         f"{', '.join(ENGINES)})")

    def __str__(self) -> str:   # KeyError str() would quote the message
        return self.args[0]


def _executor_class(engine: str):
    """The executor class of an engine name; ``UnknownEngineError`` for
    any name not in ``ENGINES``."""
    try:
        return _EXECUTORS[engine]
    except KeyError:
        raise UnknownEngineError(engine) from None


# ---------------------------------------------------------------------------
# Whole-network bitplane inference (LogicNetwork-compatible front end)
# ---------------------------------------------------------------------------

class BitplaneNetwork:
    """A compiled ``LogicNetwork`` executed through the mapped netlist.

    ``from_logic_network`` runs the full synthesis pipeline
    (SOP -> AIG -> balance/rewrite -> k-LUT map); ``from_store`` loads
    the netlist synthesized ahead of time; ``__call__`` matches
    ``LogicNetwork.__call__`` bit-exactly on every reachable input.

    ``engine`` is one of ``ENGINES``: ``"numpy"`` (the host fold) or
    ``"pallas-streamed"`` (the device engine); see the module
    docstring. Any other name raises ``UnknownEngineError``. Both
    engines are bit-identical on every reachable input.

    ``device`` (a ``jax.Device``) pins the device engine's plan tensors
    and jitted calls to that device; ``None`` leaves them on JAX's
    default device. Input quantization runs on the host.
    """

    def __init__(self, net, mapped: MappedNetwork, engine: str = "numpy",
                 interpret: Optional[bool] = None, spec=None, device=None):
        self._factory = _executor_class(engine)   # typed error on bad name
        self.net = net
        self.mapped = mapped
        self.engine = engine
        self.interpret = interpret
        self.spec = spec
        self.device = device
        # lazy import: this module loads during repro.serve/__init__
        # (via aggregate), while repro.obs pulls repro.serve.metrics —
        # a module-level import here would close an import cycle
        from repro.obs.trace import NULL_TRACER
        self._exec = None
        self.tracer = NULL_TRACER
        self._plan = _compile_plan(mapped)
        self.in_bits = net.in_spec.code_bits
        last = net.layers[-1]
        self.out_bits = last.out_spec.code_bits
        self.out_levels = np.asarray(last.out_spec.levels(last.out_alpha))

    @classmethod
    def from_logic_network(cls, net, effort: int = 1, k: int = 6,
                           engine: str = "numpy",
                           interpret: Optional[bool] = None,
                           verify: bool = False,
                           device=None) -> "BitplaneNetwork":
        """Synthesize ``net`` and wrap the mapped netlist."""
        from . import synthesize        # lazy: package init imports us
        from .from_sop import network_to_aig
        _executor_class(engine)         # a bad name fails before synthesis
        bn = cls(net, synthesize(network_to_aig(net), effort=effort, k=k,
                                 verify=verify),
                 engine=engine, interpret=interpret, device=device)
        if verify:
            from repro.check.pipeline import preflight
            from repro.check.report import require_ok
            require_ok(preflight(bn))
        return bn

    @classmethod
    def from_store(cls, net, effort: int = 1, k: int = 6,
                   engine: str = "numpy",
                   interpret: Optional[bool] = None,
                   device=None) -> "BitplaneNetwork":
        """Wrap the netlist of ``net`` synthesized ahead of time
        (``repro.synth.store``); never synthesizes: a store miss raises
        ``NetlistNotFound``."""
        from . import store
        _executor_class(engine)         # a bad name fails before the load
        return cls(net, store.load(net, effort, k), engine=engine,
                   interpret=interpret, device=device)

    @property
    def tracer(self):
        """The span tracer, handed down to the engine (now, or when it
        is built): engines with a ``tracer`` attribute record their
        spans on it."""
        return self._tracer

    @tracer.setter
    def tracer(self, tracer) -> None:
        self._tracer = tracer
        if self._exec is not None and hasattr(self._exec, "tracer"):
            self._exec.tracer = tracer

    @property
    def executor(self):
        """This network's engine instance (built lazily on first use)."""
        if self._exec is None:
            self._exec = self._factory(self, interpret=self.interpret,
                                       spec=self.spec)
            if hasattr(self._exec, "tracer"):
                self._exec.tracer = self._tracer
        return self._exec

    def quantize_codes(self, x) -> np.ndarray:
        """Real inputs -> (B, n_inputs) int32 input codes, quantized on
        the host (float32 numpy, bit-identical to
        ``LogicNetwork.quantize_inputs``)."""
        return encode_inputs_host(self.net.in_spec, x, self.net.in_alpha)

    def apply_codes(self, codes: np.ndarray) -> np.ndarray:
        """(B, n_inputs) input codes -> (B, n_out_neurons) output codes."""
        return self.executor.apply_codes(np.asarray(codes, np.int64))

    def __call__(self, x) -> np.ndarray:
        """Real inputs -> decoded real outputs (LogicNetwork contract)."""
        return self.out_levels[self.apply_codes(self.quantize_codes(x))]

    def classify(self, x, n_classes: int) -> np.ndarray:
        return self.executor.classify_codes(self.quantize_codes(x),
                                            n_classes)

    def classify_packed(self, pi_words: np.ndarray, n_rows: int,
                        n_classes: int) -> np.ndarray:
        """Packed PI bitplanes -> per-lane argmax labels, (n_rows,) int32.

        The serve-aggregation entry point: on the device engine the
        words go straight to the kernel and the call returns once the
        program is launched, with ``PendingLabels`` that ``np.asarray``
        waits for; on numpy it is the host fold + decode, an ndarray."""
        return self.executor.classify_packed(pi_words, n_rows, n_classes)


# ---------------------------------------------------------------------------
# Verilog emission of the mapped netlist
# ---------------------------------------------------------------------------

def emit_verilog(mapped: MappedNetwork, name: str = "mapped_logic") -> str:
    """Structural Verilog: one INIT-vector-indexed assign per LUT (the
    textual form of a LUT6 instance, synthesizable and simulable)."""
    k = mapped.k
    lut_pos = {l.root: i for i, l in enumerate(mapped.luts)}

    def wname(node: int) -> str:
        w = _wire_of(mapped, node, lut_pos)
        if w == _CONST_WIRE:
            return "1'b0"
        if w <= mapped.n_pis:
            return f"x[{w - 1}]"
        return f"n{w}"

    lines = [
        f"// {name}: {mapped.n_luts} LUT{k}s, depth {mapped.depth}",
        f"// generated by repro.synth (AIG -> rewrite -> {k}-LUT map)",
        f"module {name} (",
        f"  input  wire [{mapped.n_pis - 1}:0] x,",
        f"  output wire [{len(mapped.outputs) - 1}:0] y",
        ");",
    ]
    for i, l in enumerate(mapped.luts):
        m = len(l.leaves)
        tt = tt_expand(l.tt, m, k)
        init = f"{1 << k}'h{tt:0{(1 << k) // 4}x}"
        ins = [wname(x) for x in l.leaves]
        ins += ["1'b0"] * (k - m)            # pad unused select inputs
        sel = ", ".join(reversed(ins))       # MSB first in concatenation
        w = mapped.n_pis + 1 + i
        lines.append(f"  wire n{w};")
        lines.append(f"  wire [{(1 << k) - 1}:0] n{w}_init = {init};  // LUT{k}")
        lines.append(f"  assign n{w} = n{w}_init[{{{sel}}}];")
    for i, o in enumerate(mapped.outputs):
        inv = "~" if lit_compl(o) else ""
        src = wname(lit_var(o))
        if src == "1'b0" and inv:
            lines.append(f"  assign y[{i}] = 1'b1;")
        else:
            lines.append(f"  assign y[{i}] = {inv}{src};")
    lines.append("endmodule")
    return "\n".join(lines)
