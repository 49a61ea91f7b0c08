"""Client-side timing: every request is timed from when it was due."""

import queue
import threading
import time

import numpy as np

import drive
import generate
import harness


class _Handle:
    def __init__(self):
        self.q = queue.Queue(maxsize=1)

    def get(self, block=True, timeout=None):
        return self.q.get(block, timeout)


class SerialServer:
    """Answers one request at a time, in order, each after ``service_s``;
    the first request stalls for ``stall_s`` more."""

    def __init__(self, service_s, stall_s):
        self.service_s, self.stall_s = service_s, stall_s
        self.q = queue.Queue()
        self.batcher = self
        threading.Thread(target=self._loop, daemon=True).start()

    def submit(self, request):
        h = _Handle()
        self.q.put(h)
        return h

    def _loop(self):
        first = True
        while True:
            h = self.q.get()
            time.sleep(self.service_s + (self.stall_s if first else 0.0))
            first = False
            h.q.put([(0, 1.0)])


def test_latency_counts_the_wait_behind_a_stall():
    n = 20
    sched = generate.Schedule(np.linspace(0.0, 0.19, n), np.zeros(n, int),
                              [None] * n, [[1]])
    server = SerialServer(0.001, 0.3)
    t0 = time.perf_counter() + 0.01
    reads, late = drive.open_loop(lambda q: server.submit("q"), sched, t0)
    lat = 1e3 * (reads.done - reads.due)
    assert np.all(~np.isnan(reads.done))
    # every request queued behind the 300 ms stall: timed from its due
    # time, the last one waited about 300 - 190 = 110 ms and more
    assert lat[0] >= 300
    assert lat[-1] >= 100
    # the percentile is over all requests, none left out
    assert harness.pct(lat, 50) >= 150
    assert late["late_max_ms"] < 50


def test_unanswered_requests_stay_nan():
    class Silent:
        batcher = None

        def submit(self, request):
            return _Handle()
    s = Silent()
    s.batcher = s
    sched = generate.Schedule(np.array([0.0, 0.01]), np.zeros(2, int),
                              [None, None], [[1]])
    old = drive.GRACE_S
    drive.GRACE_S = 0.2
    try:
        reads, _ = drive.open_loop(lambda q: s.submit("q"), sched,
                                   time.perf_counter())
    finally:
        drive.GRACE_S = old
    assert np.isnan(reads.done).all()


def test_closed_loop_keeps_every_client_busy():
    server = SerialServer(0.002, 0.0)
    plan = generate.ClosedPlan(4, np.arange(5), [[1]] * 5)
    reads, _ = drive.closed_loop(lambda q: server.submit("q"), plan,
                                 time.perf_counter(), 0.3)
    done = reads.done[~np.isnan(reads.done)]
    assert len(done) > 50
    assert np.all(reads.due == reads.sent)
