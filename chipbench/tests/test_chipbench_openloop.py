"""The open loop times each request from its due time, so a stall in
the server delays the measured latency of every request behind it, and
a request refused at the door is marked, not waited for."""

import queue
import threading
import time
from concurrent.futures import Future

import pytest

from chipbench.openloop import Request, drive


class Refused(Exception):
    pass


class SerialServer:
    """Serves requests one at a time, each taking `service_s`; request
    number `stall_at` takes `stall_s` more."""

    def __init__(self, service_s, stall_at=None, stall_s=0.0):
        self.q = queue.Queue()
        self.service_s, self.stall_at, self.stall_s = \
            service_s, stall_at, stall_s
        self.n = 0
        self.thread = threading.Thread(target=self._loop, daemon=True)
        self.thread.start()

    def submit(self, text, top_k):
        if text == "refuse":
            raise Refused(text)
        fut = Future()
        self.q.put(fut)
        return fut

    def _loop(self):
        while True:
            fut = self.q.get()
            if fut is None:
                return
            time.sleep(self.service_s
                       + (self.stall_s if self.n == self.stall_at else 0.0))
            self.n += 1
            fut.set_result(self.n)

    def stop(self):
        self.q.put(None)
        self.thread.join(timeout=5)


def _requests(n, gap):
    return [Request(i * gap, ("term", "w"), f"q{i}", None) for i in range(n)]


def _latencies(outcomes):
    return [o.done - o.due for o in outcomes]


def test_latency_runs_from_due_time():
    srv = SerialServer(0.01)
    try:
        outs, t0 = drive(srv.submit, _requests(5, 0.05), drain_s=5.0,
                         window_s=0.25, refused=(Refused,))
    finally:
        srv.stop()
    assert all(o.answered for o in outs)
    for i, o in enumerate(outs):
        assert o.due == pytest.approx(t0 + i * 0.05)
        assert o.sent >= o.due
        assert 0.01 <= o.done - o.due < 0.05


def test_a_stall_delays_every_later_request():
    srv = SerialServer(0.005, stall_at=1, stall_s=0.5)
    try:
        outs, _ = drive(srv.submit, _requests(8, 0.02), drain_s=5.0,
                        window_s=0.16, refused=(Refused,))
    finally:
        srv.stop()
    lat = _latencies(outs)
    assert lat[0] < 0.1
    # every request due during the stall waits out the rest of it
    for i in range(1, 8):
        assert lat[i] >= 0.5 - i * 0.02, (i, lat)


def test_refused_and_unanswered_requests():
    srv = SerialServer(0.3)
    reqs = _requests(3, 0.0)
    reqs[1] = Request(0.0, ("term", "w"), "refuse", None)
    try:
        outs, _ = drive(srv.submit, reqs, drain_s=0.1, window_s=0.0,
                        refused=(Refused,))
    finally:
        srv.stop()
    assert outs[1].refused and not outs[1].answered
    assert not outs[2].answered and outs[2].done is None
