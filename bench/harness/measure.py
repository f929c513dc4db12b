"""End-to-end quantities of a window, shared by the metric readers.

``window`` arguments are ``(t0_us, t1_us)`` on the host clock
(``time.perf_counter`` µs, the clock of the scheduler's spans): the
readers of a traced run pass ``ctx.host_window``, the part of the
window before the device trace began."""
from __future__ import annotations

import numpy as np


def latencies_us(served) -> np.ndarray:
    """Done time minus due time of every answered request."""
    ok = served.answered
    return served.done_us[ok] - served.due_us[ok]


def rows_per_s(served, seconds: float) -> float:
    """Rows labelled inside the window, per second of window."""
    ok = served.answered & (served.done_us <= served.t1_us)
    return float(served.rows[ok].sum()) / seconds


def rows_per_s_in(served, window) -> float:
    """Rows whose labels came back inside ``window``, per second."""
    t0, t1 = window
    ok = served.answered & (served.done_us >= t0) & (served.done_us < t1)
    return float(served.rows[ok].sum()) / ((t1 - t0) * 1e-6)


def _inside(ts_us: float, window) -> bool:
    return window is None or window[0] <= ts_us < window[1]


def spans(events, name: str, window=None):
    """Durations (µs) of the thread spans called ``name`` that began
    inside ``window``."""
    return np.array([e.dur_us for e in events or []
                     if e.ph == "X" and e.name == name
                     and _inside(e.ts_us, window)], float)


def async_spans(events, name: str, window=None):
    """Durations (µs) of the async spans called ``name`` that began
    inside ``window`` and ended in the trace."""
    begin = {}
    out = []
    for e in events or []:
        if e.name != name:
            continue
        if e.ph == "b":
            if _inside(e.ts_us, window):
                begin[e.scope_id] = e.ts_us
        elif e.ph == "e" and e.scope_id in begin:
            out.append(e.ts_us - begin.pop(e.scope_id))
    return np.array(out, float)
