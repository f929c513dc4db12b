"""Program spans on the device trace's clock.

``repro.obs`` spans are stamped in ``time.perf_counter`` µs; the events
of a ``jax.profiler`` trace are ns from the profiler session's own
origin. The two are put on one clock by pairing, not by a wall-clock
anchor: each ``bench.pack`` annotation in the device trace wraps the
``pack_requests`` call that the program's ``aggregate_pack`` span
encloses, so the pairs' start differences give the offset.

1. The coarse offset maps the trace's ``bench.window`` start to the
   capture start on the host (``ctx.host_window[1]``). It is late by
   the time ``jax.profiler.start_trace`` took, which can be many packs.
2. Every start difference within ``reach`` of it is a candidate; the
   densest ``BIN_US`` of candidates, the one most packs share, refines
   the coarse offset.
3. At that offset each ``bench.pack`` is paired with the nearest
   ``aggregate_pack`` that contains it, and the median start difference
   of the pairs is the offset. Fewer than ``MIN_PAIRS`` pairs give
   None. The residual is the median distance of a pack's start from
   its aligned ``aggregate_pack`` start.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import numpy as np

from . import profile

PACK = "bench.pack"
SPAN = "aggregate_pack"
MIN_PAIRS = 8
BIN_US = 10.0
SEARCH_US = 1e6         # least reach of the coarse offset's error


@dataclasses.dataclass
class Alignment:
    offset_us: float        # host µs = trace ns * 1e-3 + offset_us
    residual_us: float
    n_pairs: int


def pack_alignment(spans, trace, host_window) -> Optional[Alignment]:
    """The offset from the device trace's clock to the spans' clock, or
    None where fewer than ``MIN_PAIRS`` packs pair up."""
    if trace is None or not spans:
        return None
    packs = np.array(sorted((s, e) for n, s, e in trace.host if n == PACK),
                     float).reshape(-1, 2) * 1e-3
    aggs = np.array(sorted((e.ts_us, e.ts_us + e.dur_us) for e in spans
                           if e.ph == "X" and e.name == SPAN),
                    float).reshape(-1, 2)
    if len(packs) < MIN_PAIRS or len(aggs) < MIN_PAIRS:
        return None
    coarse = host_window[1] - trace.window[0] * 1e-3
    reach = max(SEARCH_US, trace.window_ns * 1e-3)
    lo, hi = np.searchsorted(aggs[:, 0], [packs[0, 0] + coarse - reach,
                                          packs[-1, 0] + coarse + reach])
    aggs = aggs[lo:hi]
    if not len(aggs):
        return None
    d = (aggs[None, :, 0] - packs[:, None, 0]).ravel()
    d = np.sort(d[np.abs(d - coarse) <= reach])
    if not d.size:
        return None
    n_in = np.searchsorted(d, d + BIN_US, side="right") - np.arange(d.size)
    i = int(np.argmax(n_in))
    guess = float(np.median(d[i: i + n_in[i]]))
    diffs = []
    for s, e in packs:
        # the last aggregate_pack to start before this pack does (within
        # the bin) is the nearest one that can contain it
        j = int(np.searchsorted(aggs[:, 0], s + guess + BIN_US,
                                side="right")) - 1
        if j >= 0 and aggs[j, 1] >= e + guess - BIN_US:
            diffs.append(aggs[j, 0] - s)
    if len(diffs) < MIN_PAIRS:
        return None
    diffs = np.array(diffs)
    off = float(np.median(diffs))
    return Alignment(off, float(np.median(np.abs(diffs - off))), len(diffs))


def _overlap_ns(a: List[Tuple[int, int]], b: List[Tuple[int, int]]) -> int:
    """Length of the intersection of two sorted disjoint interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def unspanned_idle_share(spans, trace, host_window,
                         log=None) -> Optional[float]:
    """% of the device's idle time in the traced window that no program
    thread span (any thread) covers, mean over the traced devices."""
    if trace is None or not trace.devices or trace.window_ns <= 0:
        return None
    al = pack_alignment(spans, trace, host_window)
    if al is None:
        return None
    if log is not None:
        log(f"span alignment: offset {al.offset_us:.3f} us, residual "
            f"{al.residual_us:.3f} us over {al.n_pairs} packs")
    w0, w1 = trace.window
    cover = profile.union([
        (max(w0, int((e.ts_us - al.offset_us) * 1e3)),
         min(w1, int((e.ts_us + e.dur_us - al.offset_us) * 1e3)))
        for e in spans if e.ph == "X"])
    cover = [(s, e) for s, e in cover if e > s]
    shares = []
    for dev in trace.devices:
        edges = [w0] + [t for iv in dev.busy for t in iv] + [w1]
        idle = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        total = sum(b - a for a, b in idle)
        if total > 0:
            shares.append((total - _overlap_ns(idle, cover)) / total)
    return 100.0 * float(np.mean(shares)) if shares else None
