"""The load generator: seeded schedules, latency from the due time, and
a closed loop that keeps exactly N requests outstanding."""

import itertools
import threading
import time

import numpy as np
import pytest

import _paths  # noqa: F401
from chipbench import traffic



class _Result:
    def __init__(self, latency_s):
        self.status, self.ok, self.queue_s = "ok", True, 0.0
        self.latency_s, self.logits = latency_s, np.zeros(2)


class _Future:
    def __init__(self, server, t_sub):
        self.server, self.t_sub = server, t_sub

    def result(self, timeout=None):
        return self.server.serve(self)


class FakeServer:
    """Answers each request ``service_s`` after it was submitted, in
    order, and records how many were outstanding at each submit."""

    def __init__(self, service_s=0.002, stall_at=None, stall_s=0.0):
        self.service_s = service_s
        self.outstanding = 0
        self.max_outstanding = 0
        self.at_submit = []
        self.lock = threading.Lock()

    def submit(self, image):
        with self.lock:
            self.outstanding += 1
            self.max_outstanding = max(self.max_outstanding,
                                       self.outstanding)
            self.at_submit.append(self.outstanding)
        return _Future(self, time.perf_counter())

    def serve(self, fut):
        wait = fut.t_sub + self.service_s - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        with self.lock:
            self.outstanding -= 1
        return _Result(time.perf_counter() - fut.t_sub)


IMAGES = np.zeros((16, 2, 2, 3), np.float32)


@pytest.mark.parametrize("seed", [0, 2**31 + 11])
def test_open_schedule_is_seeded_and_same_gaps(seed):
    a = traffic.open_gaps(500.0, 2.0, seed)
    b = traffic.open_gaps(500.0, 2.0, seed)
    c = traffic.open_gaps(500.0, 2.0, seed + 1)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    # every seed offers the same set of gaps, in another order
    np.testing.assert_allclose(np.sort(a), np.sort(c))
    assert len(a) == 1000 and a.sum() == pytest.approx(2.0)
    assert np.mean(a) == pytest.approx(1 / 500.0)


def test_image_order_is_seeded_permutations():
    take = lambda: list(itertools.islice(traffic.image_order(7, 16), 40))
    a = take()
    assert a == take()
    assert sorted(a[:16]) == list(range(16))
    assert sorted(a[16:32]) == list(range(16))


def test_open_loop_latency_runs_from_due_time():
    """A generator that falls behind shows it as latency: the first
    request stalls the submit path, and the ones due meanwhile are timed
    from when they were due, not from when they were sent."""
    server = FakeServer(service_s=0.001)
    calls = []

    def submit(image):
        calls.append(time.perf_counter())
        if len(calls) == 1:
            time.sleep(0.05)            # the generator stalls once
        return server.submit(image)

    t0 = time.perf_counter() + 0.01
    recs = traffic.run_open(submit, IMAGES, 400.0, t0, t0 + 0.1, 3)
    assert len(recs) == 40
    assert all(r.status == "ok" for r in recs)
    late = [r for r in recs[1:] if r.due < recs[0].sub + 0.04]
    assert late, "some requests fell due during the stall"
    for r in late:
        assert r.sub - r.due > 0.005
        assert r.done - r.due >= r.sub - r.due + 0.001 - 1e-4


def test_closed_loop_keeps_n_outstanding():
    server = FakeServer(service_s=0.002)
    t0 = time.perf_counter()
    recs = traffic.run_closed(server.submit, IMAGES, 8, t0, t0 + 0.2, 1)
    assert server.max_outstanding == 8
    # after the first fill, every submit is a replacement: it finds the
    # other 7 still outstanding
    assert set(server.at_submit[8:]) == {8}
    assert server.outstanding == 0
    assert len(recs) > 8 * 10
    assert all(r.due <= r.sub for r in recs)
