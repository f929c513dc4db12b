"""Traffic: seeded jet features, arrival schedules and the two drivers.

A traffic file (``bench/traffic/<name>.json``) holds only parameters:

* ``loop``: ``"open"`` (requests sent on a schedule; ``arrivals``
  ``"poisson"`` at ``rate_per_s``) or ``"closed"`` (``clients``
  threads, each sending its next request when the last one is
  answered);
* ``rows_per_request``: feature rows in one request;
* ``pool_rows``: distinct feature rows drawn from the seed; requests
  take their rows from this pool.

Every seed gets the same work: an open loop always sends the same
multiset of inter-arrival gaps (exponential quantiles) in a seeded
order, and every request has the same row count. The seed changes the
rows, the order of the gaps and which pool rows each request carries.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import List

import numpy as np

N_FEATURES = 16
N_CLASSES = 5


def make_jsc(n: int, seed: int = 0, spread: float = 0.5,
             label_noise: float = 0.1):
    """Synthetic jet-substructure features: (x (n, 16) float32, y (n,)).

    Five Gaussian class clusters in R^16 with anisotropic covariance,
    standardised with fixed population statistics; the same generator
    the repository trains on, kept here so the benchmark's inputs do not
    change when the program's data module does."""
    rng = np.random.default_rng(seed)
    geo = np.random.default_rng(1234)        # fixed class geometry
    means = geo.normal(size=(N_CLASSES, N_FEATURES)) * spread
    covs = []
    for _ in range(N_CLASSES):
        q, _ = np.linalg.qr(geo.normal(size=(N_FEATURES, N_FEATURES)))
        scales = geo.uniform(0.5, 2.0, N_FEATURES)
        covs.append((q * scales) @ q.T)
    y = rng.integers(0, N_CLASSES, n)
    x = np.empty((n, N_FEATURES), np.float64)
    for c in range(N_CLASSES):
        idx = np.nonzero(y == c)[0]
        z = rng.normal(size=(len(idx), N_FEATURES))
        chol = np.linalg.cholesky(covs[c] + 1e-6 * np.eye(N_FEATURES))
        x[idx] = means[c] + z @ chol.T
    x = (x - means.mean(0)) / x.std(0)
    if label_noise > 0:
        flip = rng.random(n) < label_noise
        y = np.where(flip, rng.integers(0, N_CLASSES, n), y)
    return x.astype(np.float32), y.astype(np.int32)


def make_pool(traffic: dict, seed: int) -> np.ndarray:
    """The run's distinct feature rows, (pool_rows, 16) float32."""
    x, _ = make_jsc(int(traffic["pool_rows"]), seed=seed)
    return x


def poisson_arrivals_us(n: int, rate_per_s: float,
                        seed: int) -> np.ndarray:
    """Open-loop arrival offsets (µs from the window's start) of ``n``
    requests at ``rate_per_s``: the n quantiles of the exponential gap
    distribution, shuffled by the seed, so every seed offers the same
    gaps in another order."""
    mean_us = 1e6 / rate_per_s
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) * mean_us
    np.random.default_rng([seed, 1]).shuffle(gaps)
    return np.cumsum(gaps)


def pace_until(target_us: float) -> None:
    """Sleep until ``time.perf_counter()`` reaches ``target_us`` µs.
    Sleep-only: a spin would hold the interpreter lock against the
    scheduler thread."""
    while True:
        rem = target_us - time.perf_counter() * 1e6
        if rem <= 0:
            return
        time.sleep(rem * 1e-6)


@dataclasses.dataclass
class Served:
    """What the window sent and got back, one entry per request; the
    labels of all requests' rows lie end to end in ``labels``."""

    start: np.ndarray           # (n,) first pool row of each request
    rows: np.ndarray            # (n,) rows in each request
    due_us: np.ndarray          # (n,) when it was due (open loop) or sent
    submit_us: np.ndarray       # (n,) when submit() was called
    done_us: np.ndarray         # (n,) scheduler's completion stamp; nan if none
    answered: np.ndarray        # (n,) bool: a label came back
    labels: np.ndarray          # (sum(rows),) int32; -1 where none came
    errors: List[str]           # one line per request that got no answer
    t0_us: float                # window start
    t1_us: float                # window close

    @property
    def n(self) -> int:
        return len(self.start)

    def pool_rows(self) -> np.ndarray:
        """Pool row of every entry of ``labels``."""
        first = np.cumsum(self.rows) - self.rows
        within = np.arange(int(self.rows.sum())) - np.repeat(first, self.rows)
        return np.repeat(self.start, self.rows) + within


def _label_row(lab, rows: int) -> np.ndarray:
    """A future's result as ``rows`` labels; a wrong-sized answer reads
    as -2 everywhere (every row wrong)."""
    a = np.asarray(lab).reshape(-1)
    if a.shape != (rows,):
        return np.full(rows, -2, np.int32)
    return a.astype(np.int32)


def open_loop(sched, pool: np.ndarray, traffic: dict, seed: int,
              seconds: float) -> Served:
    """Send requests on the Poisson schedule for ``seconds``, then wait
    (at most a minute past the close) for every answer. Answers are
    collected while the generator waits for the next due time, so only
    requests in flight hold a future."""
    if traffic["arrivals"] != "poisson":
        raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
    rate = float(traffic["rate_per_s"])
    r = int(traffic["rows_per_request"])
    n = int(round(rate * seconds))
    offs = poisson_arrivals_us(n, rate, seed)
    rng = np.random.default_rng([seed, 2])
    start = rng.integers(0, pool.shape[0] - r + 1, n)
    submit_us = np.empty(n)
    done = np.full(n, np.nan)
    labels = np.full((n, r), -1, np.int32)
    errors: List[str] = []
    pending: collections.deque = collections.deque()

    def collect(i, fut, timeout=None):
        try:
            lab = fut.result(timeout=timeout)
        except Exception as e:          # a failed request: counted
            errors.append(f"request {i}: {type(e).__name__}: {e}")
            return
        labels[i] = _label_row(lab, r)
        done[i] = fut.t_done_us

    t0 = time.perf_counter() * 1e6 + 1000.0
    due = t0 + offs
    for i in range(n):
        while pending and pending[0][1].done():
            collect(*pending.popleft())
        pace_until(due[i])
        submit_us[i] = time.perf_counter() * 1e6
        try:
            x = pool[start[i]] if r == 1 else pool[start[i]: start[i] + r]
            pending.append((i, sched.submit(x)))
        except Exception as e:          # a typed reject: counted
            errors.append(f"request {i}: {type(e).__name__}: {e}")
    t1 = t0 + seconds * 1e6
    limit_s = t1 * 1e-6 + 60.0
    while pending:
        collect(*pending.popleft(),
                timeout=max(0.0, limit_s - time.perf_counter()))
    return Served(start, np.full(n, r, np.int64), due, submit_us, done,
                  ~np.isnan(done), labels.reshape(-1), errors, t0, t1)


def closed_loop(sched, pool: np.ndarray, traffic: dict, seed: int,
                seconds: float) -> Served:
    """``clients`` threads each send ``rows_per_request``-row requests
    back to back until the window closes."""
    n_clients = int(traffic["clients"])
    r = int(traffic["rows_per_request"])
    span = pool.shape[0] - r + 1
    t0 = time.perf_counter() * 1e6 + 1000.0
    t1 = t0 + seconds * 1e6
    logs = [[] for _ in range(n_clients)]

    def client(c: int) -> None:
        rng = np.random.default_rng([seed, 3, c])
        log = logs[c]
        pace_until(t0)
        while True:
            now = time.perf_counter() * 1e6
            if now >= t1:
                return
            s = int(rng.integers(0, span))
            try:
                fut = sched.submit(pool[s: s + r])
                lab = _label_row(fut.result(timeout=60.0), r)
                log.append((s, now, fut.t_done_us, lab, None))
            except Exception as e:      # reject, executor error, timeout
                log.append((s, now, np.nan, np.full(r, -1, np.int32),
                            f"{type(e).__name__}: {e}"))

    threads = [threading.Thread(target=client, args=(c,), daemon=True,
                                name=f"bench-client-{c}")
               for c in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=seconds + 120.0)
    if any(t.is_alive() for t in threads):
        raise RuntimeError("a closed-loop client did not finish")
    entries = sorted((e for log in logs for e in log), key=lambda e: e[1])
    n = len(entries)
    errors = [f"request {i}: {e[4]}" for i, e in enumerate(entries)
              if e[4] is not None]
    sub = np.array([e[1] for e in entries], float)
    return Served(np.array([e[0] for e in entries], np.int64),
                  np.full(n, r, np.int64), sub, sub.copy(),
                  np.array([e[2] for e in entries], float),
                  np.array([e[4] is None for e in entries], bool),
                  np.concatenate([e[3] for e in entries]) if n
                  else np.zeros(0, np.int32),
                  errors, t0, t1)


DRIVERS = {"open": open_loop, "closed": closed_loop}


def drive(sched, pool, traffic: dict, seed: int, seconds: float) -> Served:
    """Run the traffic's loop against ``sched`` for ``seconds``."""
    loop = traffic["loop"]
    if loop not in DRIVERS:
        raise ValueError(f"unknown loop {loop!r} (expected one of "
                         f"{sorted(DRIVERS)})")
    return DRIVERS[loop](sched, pool, traffic, seed, seconds)
