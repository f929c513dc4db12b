"""Per-level ``lut_eval`` profiling -> a measured device-latency table.

The mapper optimizes structural LUT count/depth, and the scheduler's
flush margin + ``least_slack`` dispatch run on a cold-start EWMA of
whole-batch execution time. Neither knows what a netlist *level*
actually costs on the device. This module measures it two ways:

  * ``measure_level_grid`` — synthetic single-level plans swept over
    ``(level_width, fanin)`` at fixed ``k``: random leaves into a
    wire plane sized like a real netlist's, timed through the same
    jitted ``lut_eval_pallas`` entry the serving path uses. The grid is
    netlist-independent, so it can be measured once per device and
    reused (the nnabla-nas layer-wise offline-estimation shape).
  * ``profile_plan`` — the real ``DevicePlan``'s levels, timed by
    running level prefixes 1..n and differencing: level i's row is the
    *incremental* device cost of adding it, which captures gather
    locality the synthetic grid cannot.
  * ``profile_tile_plan`` — the streamed kernel's walk over a
    ``TilePlan``, timed by tile prefixes the same way: each row is one
    tile's incremental cost, which is the granularity the streamed
    engine actually schedules (and what tile-size autotuning trades).

``build_latency_table`` fits both into a ``LatencyTable`` whose
``estimate_level_us``/``estimate_plan_us`` interpolate (linear in
width, nearest in fanin) and whose ``save`` artifact is what
``least_slack`` dispatch (``ReplicaSet(exec_seed_us=...)``), the
scheduler's flush margin (``SchedConfig.exec_estimate_us``) and future
hardware-aware mapping search consume.

Interpret-mode timings on CPU are **not** TPU microseconds — the
artifact records backend + interpret flags so consumers can refuse to
mix calibrations from different devices.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Dict, List, Optional, Sequence

import numpy as np

DEFAULT_WIDTHS = (4, 16, 64)
DEFAULT_FANINS = (2, 4, 6)


class LatencyTableError(ValueError):
    """Typed error for latency-table estimation failures."""


class EmptyLatencyTable(LatencyTableError):
    """Estimation was asked of a table holding no measurements."""


def _time_us(fn, *args, iters: int = 3) -> float:
    """Wall µs per call, first (compile) call excluded."""
    import jax

    from repro.serve.clock import SystemClock
    clk = SystemClock()
    jax.block_until_ready(fn(*args))
    t0 = clk.now_us()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (clk.now_us() - t0) / iters


def time_single_level(width: int, fanin: int, k: int = 6,
                      w_words: int = 128, iters: int = 3,
                      interpret: Optional[bool] = None,
                      seed: int = 0) -> float:
    """Device µs for one synthetic level of ``width`` LUTs with
    ``fanin`` live leaves each, through the jitted kernel."""
    import jax.numpy as jnp

    from repro.kernels.lut_eval import default_interpret
    from repro.kernels.lut_eval.lut_eval import lut_eval_pallas

    if interpret is None:
        interpret = default_interpret()
    rng = np.random.default_rng(seed)
    # wire plane shaped like a real netlist's: as many PI rows as LUTs
    n_pis = max(int(width), fanin, 1)
    leaf = np.zeros((width, k), np.int32)
    leaf[:, :fanin] = rng.integers(1, n_pis + 1, (width, fanin))
    tt = (rng.integers(0, 2, (width, 1 << k)).astype(np.uint32)
          * np.uint32(0xFFFFFFFF))
    ow = (np.arange(width, dtype=np.int32) + n_pis + 1)
    n_wires = 1 + n_pis + width
    words = rng.integers(0, 1 << 31, (n_pis, w_words), dtype=np.int64)
    args = (jnp.asarray(words.astype(np.int32)), jnp.asarray(leaf),
            jnp.asarray(tt.view(np.int32)), jnp.asarray(ow))

    def fn(w, l, t, o):
        return lut_eval_pallas(w, l, t, o, n_pis=n_pis, n_slots=width,
                               n_wires=n_wires, k=k,
                               block_w=min(128, w_words),
                               interpret=interpret)

    return _time_us(fn, *args, iters=iters)


def measure_level_grid(widths: Sequence[int] = DEFAULT_WIDTHS,
                       fanins: Sequence[int] = DEFAULT_FANINS,
                       k: int = 6, w_words: int = 128, iters: int = 3,
                       interpret: Optional[bool] = None,
                       seed: int = 0) -> List[Dict]:
    """Synthetic ``(level_width, fanin)`` sweep -> measurement rows."""
    rows = []
    for width in widths:
        for fanin in fanins:
            if fanin > k:
                continue
            us = time_single_level(width, fanin, k=k, w_words=w_words,
                                   iters=iters, interpret=interpret,
                                   seed=seed)
            rows.append({"source": "grid", "level_width": int(width),
                         "k": int(k), "fanin": int(fanin),
                         "device_us": float(us), "w_words": int(w_words)})
    return rows


def plan_level_fanins(dplan) -> List[float]:
    """Mean live (non-const-leaf) fanin per level of a ``DevicePlan``.

    Padded no-op slots (all leaves const, INIT masks all-zero) are
    excluded from the mean; a level that is pure padding reports 0.
    """
    out = []
    for lvl in range(dplan.n_levels):
        live = dplan.tt_bits[lvl].any(axis=1)        # real (non-pad) slots
        if not live.any():
            out.append(0.0)
            continue
        fan = (dplan.leaf_idx[lvl][live] != 0).sum(axis=1)
        out.append(float(fan.mean()))
    return out


def profile_plan(dplan, w_words: int = 128, iters: int = 3,
                 interpret: Optional[bool] = None,
                 seed: int = 0) -> List[Dict]:
    """Measured incremental device µs per level of a real plan.

    Times the kernel on level prefixes 1..n_levels and differences
    consecutive timings; clamps at >= 0 (timer noise can invert
    neighbouring prefixes on near-empty levels).
    """
    import jax.numpy as jnp

    from repro.kernels.lut_eval import default_interpret
    from repro.kernels.lut_eval.lut_eval import lut_eval_pallas

    if interpret is None:
        interpret = default_interpret()
    rng = np.random.default_rng(seed)
    lw, k = dplan.level_width, dplan.k
    words = rng.integers(0, 1 << 31, (max(dplan.n_pis, 1), w_words),
                         dtype=np.int64)
    jwords = jnp.asarray(words.astype(np.int32))
    leaf = jnp.asarray(dplan.leaf_idx.reshape(-1, k).astype(np.int32))
    tt = jnp.asarray(np.ascontiguousarray(
        dplan.tt_bits.reshape(-1, 1 << k)).view(np.int32))
    ow = jnp.asarray(dplan.out_wires.reshape(-1).astype(np.int32))
    fanins = plan_level_fanins(dplan)

    prefix_us = []
    for lvl in range(dplan.n_levels):
        n_slots = (lvl + 1) * lw

        def fn(w, l, t, o, n_slots=n_slots):
            return lut_eval_pallas(w, l[:n_slots], t[:n_slots],
                                   o[:n_slots], n_pis=dplan.n_pis,
                                   n_slots=n_slots, n_wires=dplan.n_wires,
                                   k=k, block_w=min(128, w_words),
                                   interpret=interpret)

        prefix_us.append(_time_us(fn, jwords, leaf, tt, ow, iters=iters))
    rows = []
    for lvl, us in enumerate(prefix_us):
        inc = us - (prefix_us[lvl - 1] if lvl else 0.0)
        rows.append({"source": "plan", "level": lvl,
                     "level_width": int(lw), "k": int(k),
                     "fanin": round(fanins[lvl], 2),
                     "device_us": float(max(inc, 0.0)),
                     "prefix_us": float(us), "w_words": int(w_words)})
    return rows


def tile_plan_fanins(tplan) -> List[float]:
    """Mean live (non-const-leaf) fanin per tile of a ``TilePlan``;
    pad slots (all-zero INIT) are excluded, pure-pad tiles report 0."""
    out = []
    for t in range(tplan.n_tiles):
        live = tplan.tt_tiles[t].any(axis=1)
        if not live.any():
            out.append(0.0)
            continue
        fan = (tplan.leaf_tiles[t][live] != 0).sum(axis=1)
        out.append(float(fan.mean()))
    return out


def profile_tile_plan(tplan, w_words: int = 128, iters: int = 3,
                      interpret: Optional[bool] = None,
                      gather: Optional[str] = None,
                      seed: int = 0) -> List[Dict]:
    """Measured incremental device µs per *tile* of a streamed plan.

    Times the streamed kernel on tile prefixes 1..n_tiles and
    differences consecutive timings (clamped >= 0), so each row is what
    one double-buffered tile step costs end to end — DMA overlap
    included, which per-level timing through the monolithic kernel
    cannot see.
    """
    import jax.numpy as jnp

    from repro.kernels.lut_eval.lut_eval import (default_gather,
                                                 lut_eval_streamed_pallas,
                                                 pack_tile_meta)
    from repro.kernels.spec import default_interpret

    if interpret is None:
        interpret = default_interpret()
    if gather is None:
        gather = default_gather(tplan, interpret, min(128, w_words))
    rng = np.random.default_rng(seed)
    words = rng.integers(0, 1 << 31, (max(tplan.n_pis, 1), w_words),
                         dtype=np.int64)
    jwords = jnp.asarray(words.astype(np.int32))
    meta = jnp.asarray(pack_tile_meta(tplan, gather))
    fanins = tile_plan_fanins(tplan)

    prefix_us = []
    for n in range(1, tplan.n_tiles + 1):
        def fn(w, n=n):
            return lut_eval_streamed_pallas(
                w, meta[:n], n_pis=tplan.n_pis, n_tiles=n,
                tile_rows=tplan.tile_rows, gather_cap=tplan.gather_cap,
                n_rows=tplan.n_rows, k=tplan.k, block_w=min(128, w_words),
                gather=gather, interpret=interpret)

        prefix_us.append(_time_us(fn, jwords, iters=iters))
    rows = []
    for t, us in enumerate(prefix_us):
        inc = us - (prefix_us[t - 1] if t else 0.0)
        rows.append({"source": "tile", "level": int(tplan.level_of_tile[t]),
                     "tile": t, "level_width": int(tplan.tile_rows),
                     "k": int(tplan.k), "fanin": round(fanins[t], 2),
                     "device_us": float(max(inc, 0.0)),
                     "prefix_us": float(us), "w_words": int(w_words)})
    return rows


@dataclasses.dataclass
class LatencyTable:
    """Measured ``(level_width, k, fanin) -> device µs`` lookup.

    Estimation is nearest-fanin (which clamps out-of-sweep fanins to
    the nearest calibrated one), then linear interpolation in
    ``level_width``. Queries **outside the calibrated width sweep are
    clamped, never slope-extrapolated**: below the grid the smallest
    measurement applies (``np.interp``'s edge clamp); above it the
    largest measurement scales proportionally per LUT
    (``us[-1] * width / ws[-1]``) — per-level work is linear in width
    for a fixed word tile, and a two-point slope can go negative or
    explode on a noisy sweep, which once fed the flush margin a
    nonsense estimate.

    ``scale`` is a correction factor: calibration happens on an idle
    device, serving happens on a busy one, and ``blend_scale`` folds a
    measured/predicted ratio into it so estimates can track the
    machine as it actually is.
    """

    rows: List[Dict]
    meta: Dict = dataclasses.field(default_factory=dict)
    scale: float = 1.0              # online measured/predicted blend

    SCALE_MIN = 0.1
    SCALE_MAX = 10.0

    def _grid_rows(self, k: int) -> List[Dict]:
        if not self.rows:
            raise EmptyLatencyTable(
                "latency table holds no measurements — run "
                "build_latency_table (or load a saved artifact) before "
                "estimating")
        rows = [r for r in self.rows
                if r["k"] == k and r["source"] == "grid"]
        return rows or [r for r in self.rows if r["k"] == k]

    def estimate_level_us(self, level_width: int, fanin: float,
                          k: int = 6) -> float:
        rows = self._grid_rows(k)
        if not rows:
            raise LatencyTableError(
                f"no measurements for k={k} "
                f"(calibrated: {sorted({r['k'] for r in self.rows})})")
        if not np.isfinite(level_width) or not np.isfinite(fanin):
            raise LatencyTableError(
                f"non-finite query (level_width={level_width}, "
                f"fanin={fanin})")
        level_width = max(float(level_width), 0.0)
        fans = sorted({r["fanin"] for r in rows})
        near_fan = min(fans, key=lambda f: abs(f - fanin))
        pts = sorted((r["level_width"], r["device_us"]) for r in rows
                     if r["fanin"] == near_fan)
        ws = [p[0] for p in pts]
        us = [p[1] for p in pts]
        if level_width > ws[-1]:        # past grid: per-LUT scaling of
            est = us[-1] * level_width / max(ws[-1], 1)     # the last point
        elif len(pts) == 1:
            est = us[0] * level_width / max(ws[0], 1)
        else:                           # in-grid interp; below-grid clamps
            est = float(np.interp(level_width, ws, us))     # to us[0]
        return max(est, 0.0) * self.scale

    def estimate_plan_us(self, dplan) -> float:
        """Calibrated whole-netlist estimate: sum of per-level
        estimates at each level's width and mean live fanin."""
        total = 0.0
        for fanin in plan_level_fanins(dplan):
            total += self.estimate_level_us(dplan.level_width, fanin,
                                            k=dplan.k)
        return total

    def blend_scale(self, factor: float, alpha: float = 0.2) -> float:
        """EWMA-blend a live measured/predicted ratio into ``scale``.

        ``factor`` outside ``[SCALE_MIN, SCALE_MAX]`` is clamped before
        blending (one absurd sample — a GC pause mid-measurement — must
        not poison every later estimate); non-finite factors are
        ignored. Returns the updated scale."""
        if not np.isfinite(factor) or factor <= 0:
            return self.scale
        factor = min(max(float(factor), self.SCALE_MIN), self.SCALE_MAX)
        self.scale = min(max((1.0 - alpha) * self.scale + alpha * factor,
                             self.SCALE_MIN), self.SCALE_MAX)
        return self.scale

    # -- artifact ----------------------------------------------------------
    def to_dict(self) -> Dict:
        return {"kind": "lut_level_latency_table", "meta": self.meta,
                "scale": self.scale, "rows": self.rows}

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_dict(), f, indent=1)
        return path

    @classmethod
    def load(cls, path: str) -> "LatencyTable":
        with open(path) as f:
            doc = json.load(f)
        if doc.get("kind") != "lut_level_latency_table":
            raise ValueError(f"{path} is not a lut-level latency table")
        return cls(rows=doc["rows"], meta=doc.get("meta", {}),
                   scale=float(doc.get("scale", 1.0)))


def build_latency_table(dplan=None, widths: Sequence[int] = DEFAULT_WIDTHS,
                        fanins: Sequence[int] = DEFAULT_FANINS, k: int = 6,
                        w_words: int = 128, iters: int = 3,
                        interpret: Optional[bool] = None,
                        seed: int = 0) -> LatencyTable:
    """Grid sweep (+ real-plan per-level rows when ``dplan`` given) ->
    a saveable ``LatencyTable`` stamped with the measurement context."""
    import jax

    from repro.kernels.lut_eval import default_interpret

    if interpret is None:
        interpret = default_interpret()
    if dplan is not None:
        k = dplan.k
    rows = measure_level_grid(widths, fanins, k=k, w_words=w_words,
                              iters=iters, interpret=interpret, seed=seed)
    if dplan is not None:
        rows += profile_plan(dplan, w_words=w_words, iters=iters,
                             interpret=interpret, seed=seed)
        if getattr(dplan, "tiles", None) is not None:
            rows += profile_tile_plan(dplan.tiles, w_words=w_words,
                                      iters=iters, interpret=interpret,
                                      seed=seed)
    meta = {"backend": jax.default_backend(), "interpret": bool(interpret),
            "device": str(jax.devices()[0]), "w_words": int(w_words),
            "iters": int(iters), "k": int(k)}
    return LatencyTable(rows=rows, meta=meta)
