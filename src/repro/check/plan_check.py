"""Pass 3 — static validation of ``DevicePlan`` tensors before launch.

``compile_device_plan`` output is what the device engine trusts
blindly: wire indices become unchecked VMEM loads/stores, INIT masks
become the Shannon fold, and the dump-row convention turns padded
slots into silent no-ops. A malformed plan therefore fails *on device*
(or worse, silently corrupts the wire plane), so every contract is
checked here on the host first:

  * shape/dtype contracts of all six tensors;
  * leaf indices in [0, n_wires) — a leaf must never read the dump row;
  * every real wire written exactly once, only by its own level, and
    read only by strictly later levels (levelization);
  * no-op (padded) slots fully inert: const-wire leaves, all-zero INIT,
    dump-row output;
  * INIT masks restricted to the {0, 0xFFFFFFFF} bitplane encoding;
  * output gather indices/complements in range;
  * the attached tile schedule: its staged-gather remap and tile order;
  * estimated VMEM working set of one tile step at the kernel's word
    tile against a configurable budget.

Results are cached by a content hash of the plan so the serving hot
path (which validates on every ``--check`` preflight) pays the cost
once per distinct netlist version.
"""
from __future__ import annotations

import hashlib
from typing import Dict, Optional

import numpy as np

from repro.synth.executor import DevicePlan

from .report import CheckReport

PASS = "plan"

# mirrors kernels/lut_eval DEFAULT_BW without importing jax here
_DEFAULT_BLOCK_W = 128
# a v5e core's default scoped VMEM limit, against which one tile step's
# working set is checked
DEFAULT_VMEM_BUDGET = 16 << 20
# a TPU v5e core's VMEM (``pltpu.get_tpu_info().vmem_capacity_bytes``)
V5E_VMEM_BYTES = 128 << 20
# the streamed kernel keeps its plane in VMEM while the plane takes at
# most this share of the core's VMEM, leaving the rest to the compiler
_RESIDENT_SHARE = 0.5

_FULL = np.uint32(0xFFFFFFFF)

_CACHE: Dict[str, CheckReport] = {}


def plan_fingerprint(dplan: DevicePlan) -> str:
    """Content hash over every tensor and scalar the kernel consumes."""
    h = hashlib.sha1()
    for arr in (dplan.leaf_idx, dplan.tt_bits, dplan.out_wires,
                dplan.out_idx, dplan.out_neg):
        a = np.ascontiguousarray(arr)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    h.update(f"{dplan.n_pis},{dplan.n_wires},{dplan.k}".encode())
    return h.hexdigest()


def estimate_tile_vmem_bytes(tplan, block_w: int = _DEFAULT_BLOCK_W) -> int:
    """Working-set estimate for one tile step with the wire plane in
    HBM (``"dma"``; a resident plane is sized by ``gather_mode``
    instead). On-chip the kernel holds the PI block, the
    double-buffered plan tensors for two tiles, the staged leaf rows
    (DMA-gather mode), the gathered-input/fold state of one tile, and
    the output band — so the budget scales with (tile_rows, gather_cap,
    block_w), never with netlist size."""
    t, k, g = tplan.tile_rows, tplan.k, tplan.gather_cap
    n_tt = 1 << k
    pis = tplan.n_pis * block_w * 4
    bufs = 2 * t * n_tt * 4 + 2 * t * k * 4        # double-buffered plans
    stage = 2 * g * block_w * 4                    # staged leaf rows (dma)
    fold = t * n_tt * block_w * 4 + t * k * block_w * 4   # state + gathers
    band = t * block_w * 4                         # contiguous out band
    return pis + bufs + stage + fold + band


def resident_plane_bytes(tplan, block_w: int = _DEFAULT_BLOCK_W) -> int:
    """VMEM the streamed kernel's resident plane takes: the plan's rows
    padded to a multiple of 8 by ``block_w`` padded to 128 lanes, int32."""
    rows = -(-tplan.n_rows // 8) * 8
    lanes = -(-max(block_w, 1) // 128) * 128
    return rows * lanes * 4


def gather_mode(tplan, vmem_capacity_bytes: int,
                block_w: int = _DEFAULT_BLOCK_W) -> str:
    """The streamed kernel's leaf-gather mode for a plan: ``"vmem"``
    (plane resident in VMEM, leaves read by vector loads) when the
    padded plane fits half the core's VMEM, else ``"dma"`` (plane in
    HBM, leaf rows staged by DMA)."""
    budget = int(vmem_capacity_bytes * _RESIDENT_SHARE)
    return ("vmem" if resident_plane_bytes(tplan, block_w) <= budget
            else "dma")


def validate_device_plan(dplan: DevicePlan,
                         vmem_budget_bytes: Optional[int]
                         = DEFAULT_VMEM_BUDGET,
                         block_w: int = _DEFAULT_BLOCK_W,
                         use_cache: bool = True,
                         name: str = "device-plan") -> CheckReport:
    """Static checks on a compiled ``DevicePlan``; cached by plan hash."""
    key = None
    if use_cache:
        tp = dplan.tiles
        key = (plan_fingerprint(dplan), vmem_budget_bytes, block_w,
               (tp.tile_rows, tp.gather_cap))
        hit = _CACHE.get(key)
        if hit is not None:
            return hit
    rep = _validate(dplan, vmem_budget_bytes, block_w, name)
    if use_cache:
        _CACHE[key] = rep
    return rep


def _validate(dplan: DevicePlan, vmem_budget_bytes: Optional[int],
              block_w: int, name: str) -> CheckReport:
    rep = CheckReport(name)
    li, tt, ow = dplan.leaf_idx, dplan.tt_bits, dplan.out_wires
    oi, on = dplan.out_idx, dplan.out_neg
    nw, k, n_pis = dplan.n_wires, dplan.k, dplan.n_pis

    # ---- dtype / shape contracts ----
    rep.checked += 1
    for aname, arr, dt in (("leaf_idx", li, np.int32),
                           ("tt_bits", tt, np.uint32),
                           ("out_wires", ow, np.int32),
                           ("out_idx", oi, np.int32)):
        if arr.dtype != dt:
            rep.error(PASS, "dtype",
                      f"{aname} dtype {arr.dtype} != {np.dtype(dt)}",
                      where=aname)
    if on.dtype != np.bool_:
        rep.error(PASS, "dtype", f"out_neg dtype {on.dtype} != bool",
                  where="out_neg")
    if li.ndim != 3:
        rep.error(PASS, "shape", f"leaf_idx rank {li.ndim} != 3",
                  where="leaf_idx")
        return rep
    n_levels, lw, kk = li.shape
    rep.checked += 1
    if kk != k:
        rep.error(PASS, "shape",
                  f"leaf_idx last dim {kk} != k={k}", where="leaf_idx")
    if tt.shape != (n_levels, lw, 1 << k):
        rep.error(PASS, "shape",
                  f"tt_bits shape {tt.shape} != "
                  f"{(n_levels, lw, 1 << k)} (INIT width 2^k)",
                  where="tt_bits")
        return rep
    if ow.shape != (n_levels, lw):
        rep.error(PASS, "shape",
                  f"out_wires shape {ow.shape} != {(n_levels, lw)}",
                  where="out_wires")
        return rep
    if oi.shape != on.shape or oi.ndim != 1:
        rep.error(PASS, "shape",
                  f"out_idx {oi.shape} / out_neg {on.shape} must be "
                  f"equal rank-1 shapes")
        return rep
    n_luts = nw - 1 - n_pis
    rep.checked += 1
    if n_luts < 0:
        rep.error(PASS, "wire-count",
                  f"n_wires {nw} < 1 + n_pis {n_pis}")
        return rep

    # ---- INIT masks: bitplane {0, ~0} encoding only ----
    rep.checked += 1
    bad_tt = (tt != 0) & (tt != _FULL)
    if bad_tt.any():
        lvl, s, r = (int(x[0]) for x in np.nonzero(bad_tt))
        rep.error(PASS, "tt-encoding",
                  f"tt_bits[{lvl},{s},{r}] = {tt[lvl, s, r]:#x} is "
                  f"neither 0 nor 0xFFFFFFFF (bitplane mask encoding)",
                  where=f"level {lvl} slot {s}")

    # ---- leaf reads: in range, never the dump row, only earlier levels
    rep.checked += 1
    if li.size and (li.min() < 0 or li.max() >= nw):
        lvl, s, j = (int(x[0]) for x in
                     np.nonzero((li < 0) | (li >= nw)))
        rep.error(PASS, "leaf-range",
                  f"leaf_idx[{lvl},{s},{j}] = {li[lvl, s, j]} outside "
                  f"[0, {nw}) — reading the dump row or beyond",
                  where=f"level {lvl} slot {s}")

    # ---- output wires: pad slots use the dump row; real slots cover
    # every LUT wire exactly once at a consistent level ----
    pad = ow == nw
    rep.checked += 1
    if ow.size and ((ow < n_pis + 1) | (ow > nw)).any():
        lvl, s = (int(x[0]) for x in
                  np.nonzero((ow < n_pis + 1) | (ow > nw)))
        rep.error(PASS, "out-range",
                  f"out_wires[{lvl},{s}] = {ow[lvl, s]} outside the LUT "
                  f"wire range [{n_pis + 1}, {nw}]",
                  where=f"level {lvl} slot {s}")
        return rep
    real = ow[~pad]
    rep.checked += 1
    if real.size != n_luts or (real.size and
                               not np.array_equal(
                                   np.sort(real),
                                   np.arange(n_pis + 1, nw))):
        counts = np.bincount(real - (n_pis + 1), minlength=max(n_luts, 0)) \
            if real.size else np.zeros(max(n_luts, 0), np.int64)
        dup = np.nonzero(counts > 1)[0]
        missing = np.nonzero(counts == 0)[0]
        detail = []
        if dup.size:
            detail.append(f"wire {dup[0] + n_pis + 1} written "
                          f"{counts[dup[0]]}x")
        if missing.size:
            detail.append(f"wire {missing[0] + n_pis + 1} never written")
        rep.error(PASS, "wire-cover",
                  f"real slots write {real.size} wires but the plan "
                  f"declares {n_luts} LUTs"
                  + (f" ({'; '.join(detail)})" if detail else ""))

    # level of each wire (PIs/const = level 0; LUT wires = writing level+1)
    wire_level = np.zeros(nw + 1, np.int64)
    for lvl in range(n_levels):
        w = ow[lvl][~pad[lvl]]
        wire_level[w] = lvl + 1
    rep.checked += 1
    for lvl in range(n_levels):
        leaves = li[lvl][~pad[lvl]]          # (slots, k)
        if leaves.size and (wire_level[leaves] > lvl).any():
            s, j = (int(x[0]) for x in
                    np.nonzero(wire_level[leaves] > lvl))
            rep.error(PASS, "level-order",
                      f"level {lvl} reads wire {leaves[s, j]} which is "
                      f"written at level {wire_level[leaves[s, j]] - 1} "
                      f"(same level or later)",
                      where=f"level {lvl}")
            break

    # ---- no-op slot consistency ----
    rep.checked += 1
    for lvl in range(n_levels):
        p = pad[lvl]
        if not p.any():
            continue
        if li[lvl][p].any():
            s = int(np.nonzero(p)[0][np.nonzero(li[lvl][p].any(axis=1))
                                     [0][0]])
            rep.error(PASS, "pad-slot",
                      f"padded slot ({lvl},{s}) reads wire "
                      f"{int(li[lvl, s].max())} instead of the constant "
                      f"wire", where=f"level {lvl} slot {s}")
            break
        if tt[lvl][p].any():
            s = int(np.nonzero(p)[0][np.nonzero(tt[lvl][p].any(axis=1))
                                     [0][0]])
            rep.error(PASS, "pad-slot",
                      f"padded slot ({lvl},{s}) has nonzero INIT masks "
                      f"— it would write garbage to the dump row",
                      where=f"level {lvl} slot {s}")
            break

    # ---- output gather ----
    rep.checked += 1
    if oi.size and ((oi < 0) | (oi >= nw)).any():
        i = int(np.nonzero((oi < 0) | (oi >= nw))[0][0])
        rep.error(PASS, "out-idx",
                  f"out_idx[{i}] = {oi[i]} outside [0, {nw})",
                  where=f"output {i}")

    # ---- tile schedule consistency ----
    tp = dplan.tiles
    rep.checked += 1
    staged = tp.gather_rows[
        np.arange(tp.n_tiles)[:, None, None], tp.leaf_loc]
    if not np.array_equal(staged, tp.leaf_tiles):
        t, s, j = (int(x[0]) for x in
                   np.nonzero(staged != tp.leaf_tiles))
        rep.error(PASS, "tile-gather",
                  f"gather_rows[{t}][leaf_loc[{t},{s},{j}]] = "
                  f"{staged[t, s, j]} != leaf_tiles[{t},{s},{j}] = "
                  f"{tp.leaf_tiles[t, s, j]} — the staged-DMA remap "
                  f"disagrees with the direct leaf rows",
                  where=f"tile {t} slot {s}")
    rep.checked += 1
    bad = tp.leaf_tiles >= tp.out_base[:, None, None]
    if bad.any():
        t, s, j = (int(x[0]) for x in np.nonzero(bad))
        rep.error(PASS, "tile-order",
                  f"tile {t} (band starts at row {tp.out_base[t]}) "
                  f"reads row {tp.leaf_tiles[t, s, j]} from its own "
                  f"or a later band — streamed tile order would "
                  f"read unwritten rows", where=f"tile {t} slot {s}")

    # ---- VMEM working set of one tile step ----
    rep.info["n_levels"] = n_levels
    rep.info["level_width"] = lw
    rep.checked += 1
    est = estimate_tile_vmem_bytes(tp, block_w)
    rep.info["vmem_bytes"] = est
    rep.info["tile_rows"] = tp.tile_rows
    rep.info["n_tiles"] = tp.n_tiles
    if vmem_budget_bytes is not None and est > vmem_budget_bytes:
        rep.error(PASS, "vmem-budget",
                  f"estimated per-tile VMEM working set "
                  f"{est / 2**20:.1f} MiB (tile_rows "
                  f"{tp.tile_rows} x {block_w} words, gather_cap "
                  f"{tp.gather_cap}) exceeds the "
                  f"{vmem_budget_bytes / 2**20:.1f} MiB budget — "
                  f"shrink tile_rows or block_w")
    return rep
