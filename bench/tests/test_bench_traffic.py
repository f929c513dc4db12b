"""Traffic determinism: the seed fixes the rows and the schedule, and
every seed offers the same work."""
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

from harness import traffic  # noqa: E402

SEED = 2 ** 31 + 12345          # larger than a signed 32-bit integer


def test_same_seed_same_rows_and_arrivals():
    tr = {"pool_rows": 4096}
    a, b = traffic.make_pool(tr, SEED), traffic.make_pool(tr, SEED)
    assert a.shape == (4096, 16) and a.dtype == np.float32
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(traffic.poisson_arrivals_us(5000, 1e4, SEED),
                                  traffic.poisson_arrivals_us(5000, 1e4, SEED))


def test_other_seed_same_gaps_in_another_order():
    a = traffic.poisson_arrivals_us(5000, 1e4, SEED)
    b = traffic.poisson_arrivals_us(5000, 1e4, SEED + 1)
    assert not np.array_equal(a, b)
    ga = np.sort(np.diff(a, prepend=0.0))
    gb = np.sort(np.diff(b, prepend=0.0))
    # every seed offers the same multiset of gaps, in another order
    np.testing.assert_allclose(ga, gb, rtol=0, atol=1e-6)
    assert ga.size == 5000 and ga[0] > 0
    # mean gap matches the rate; the window spans ~n / rate seconds
    assert abs(a[-1] / 1e6 - 0.5) < 0.02
    assert not np.array_equal(traffic.make_pool({"pool_rows": 64}, SEED),
                              traffic.make_pool({"pool_rows": 64}, SEED + 1))


class _Sched:
    """Answers every request at once with label 0 per row."""

    def submit(self, x):
        from repro.serve.sched import ServeFuture
        f = ServeFuture()
        import time
        f.t_enqueue_us = f.t_done_us = time.perf_counter() * 1e6
        f.set_result(np.zeros(x.shape[0], np.int32) if x.ndim == 2 else 0)
        return f


def test_drivers_record_every_request():
    pool = traffic.make_pool({"pool_rows": 1024}, SEED)
    s = traffic.drive(_Sched(), pool, {"loop": "open", "arrivals": "poisson",
                                      "rate_per_s": 2000.0,
                                      "rows_per_request": 1},
                      SEED, 0.2)
    assert s.n == 400 and s.answered.all() and not s.errors
    assert np.all(s.submit_us >= s.due_us)
    c = traffic.drive(_Sched(), pool, {"loop": "closed", "clients": 2,
                                       "rows_per_request": 256}, SEED, 0.1)
    assert c.n > 0 and c.answered.all()
    assert np.all(c.rows == 256) and np.all(c.start <= 1024 - 256)
