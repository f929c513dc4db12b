"""Device trace: capture a few seconds with ``jax.profiler`` and reduce
the ``.xplane.pb`` it writes to device busy time, per-op time and the
host spans that idle gaps fall in.

Device planes are the ``/device:TPU:<n>`` planes; their ``XLA Ops``
line holds one event per operation run on the chip, named by its HLO
instruction (``%lut_eval_streamed_pallas.1 = s32[...] custom-call(...)``;
the reduction keeps the part before `` = ``). Host planes hold the
``jax.profiler.TraceAnnotation`` spans the harness writes (named
``bench.*``); the annotation ``bench.window`` marks the traced window.
The Python function tracer stays off: it would trace every call of the
served path and slow it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import shutil
import threading
import time
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"


@dataclasses.dataclass
class DeviceTrace:
    name: str
    ops: List[Tuple[str, int, int]]     # (name, start_ns, dur_ns) in window
    busy: List[Tuple[int, int]]         # union of op intervals, in window


@dataclasses.dataclass
class Reduced:
    window: Tuple[int, int]             # (start_ns, end_ns)
    devices: List[DeviceTrace]
    host: List[Tuple[str, int, int]]    # bench.* annotations (name, s, e)

    @property
    def window_ns(self) -> int:
        return self.window[1] - self.window[0]

    def busy_ns(self, dev: DeviceTrace) -> int:
        return sum(e - s for s, e in dev.busy)

    def mean_busy_s(self) -> float:
        if not self.devices:
            return 0.0
        return sum(self.busy_ns(d) for d in self.devices) / (
            len(self.devices) * 1e9)

    def op_seconds(self) -> Dict[str, float]:
        """Device seconds per op name, summed over devices."""
        out: Dict[str, float] = {}
        for d in self.devices:
            for name, _, dur in d.ops:
                out[name] = out.get(name, 0.0) + dur * 1e-9
        return out

    def idle_gaps(self) -> Dict[str, float]:
        """Idle device seconds (mean over devices) by the innermost
        ``bench.*`` host span that covers each gap's midpoint."""
        out: Dict[str, float] = {}
        host = sorted(self.host, key=lambda h: h[2] - h[1])
        for d in self.devices:
            edges = [self.window[0]]
            for s, e in d.busy:
                edges += [s, e]
            edges.append(self.window[1])
            for a, b in zip(edges[::2], edges[1::2]):
                if b <= a:
                    continue
                mid = (a + b) // 2
                label = next((n for n, s, e in host if s <= mid < e),
                             "no bench span")
                out[label] = out.get(label, 0.0) + (b - a) * 1e-9
        n = max(1, len(self.devices))
        return {k: v / n for k, v in out.items()}


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def reduce_xplane(path: str, devices: Optional[int] = None) -> Reduced:
    """Reduce one ``.xplane.pb`` file. ``devices`` keeps the first n
    device planes (the chips the cell uses)."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    dev_planes = []
    host: List[Tuple[str, int, int]] = []
    window = None
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev_planes.append(plane)
            continue
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("bench."):
                    s = int(ev.start_ns)
                    e = s + int(ev.duration_ns)
                    if ev.name == WINDOW:
                        window = (s, e)
                    else:
                        host.append((ev.name, s, e))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW!r} annotation")
    dev_planes.sort(key=lambda p: int(p.name[len(DEVICE_PREFIX):]))
    if devices is not None:
        dev_planes = dev_planes[:devices]
    w0, w1 = window
    devs = []
    for plane in dev_planes:
        ops = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s = max(int(ev.start_ns), w0)
                e = min(int(ev.start_ns) + int(ev.duration_ns), w1)
                if e > s:
                    ops.append((op_name(ev.name), s, e - s))
        devs.append(DeviceTrace(plane.name, ops,
                                union([(s, s + d) for _, s, d in ops])))
    return Reduced(window, devs, host)


def op_name(hlo: str) -> str:
    """``%name.3 = f32[...] op(...)`` -> ``%name.3``."""
    return hlo.split(" = ", 1)[0]


def newest_xplane(root: str) -> str:
    files = glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return max(files, key=os.path.getmtime)


class Capture:
    """Trace ``seconds`` of the window, starting at ``start_us``
    (``time.perf_counter`` µs), from a thread of its own so the drivers
    keep their pace."""

    def __init__(self, root: str, start_us: float, seconds: float):
        self.root = root
        self.t_start_us = start_us      # when tracing began, once it has
        self.error: Optional[BaseException] = None
        shutil.rmtree(root, ignore_errors=True)
        self._t = threading.Thread(target=self._run, daemon=True,
                                   args=(start_us, seconds),
                                   name="bench-profiler")
        self._t.start()

    def _run(self, start_us: float, seconds: float) -> None:
        import jax
        try:
            while time.perf_counter() * 1e6 < start_us:
                time.sleep(0.01)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            self.t_start_us = time.perf_counter() * 1e6
            jax.profiler.start_trace(self.root, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation(WINDOW):
                    time.sleep(seconds)
            finally:
                jax.profiler.stop_trace()
        except BaseException as e:          # re-raised by result()
            self.error = e

    def result(self, devices: int) -> Reduced:
        self._t.join(timeout=300)
        if self._t.is_alive():
            raise RuntimeError("profiler thread did not finish")
        if self.error is not None:
            raise self.error
        red = reduce_xplane(newest_xplane(self.root), devices)
        shutil.rmtree(self.root, ignore_errors=True)
        return red
