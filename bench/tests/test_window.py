"""The open-loop driver and the window's arithmetic, against a fake
server that answers through the program's own ``Ticket``."""
import queue
import threading
import time

import numpy as np
import pytest

from bench import window
from repro.serve.admission import Ticket


class _Result:
    def __init__(self, doc_ids):
        self.doc_ids = doc_ids
        self.algorithm = "fake"


class FakeServer:
    """One worker answers submissions in order, ``service_s`` each; the
    ``stall_at``-th answer first sleeps ``stall_s``."""

    def __init__(self, service_s=0.0005, stall_at=None, stall_s=0.0):
        self.q = queue.Queue()
        self.service_s, self.stall_at, self.stall_s = (service_s, stall_at,
                                                       stall_s)
        self.worker = threading.Thread(target=self._work, daemon=True)
        self.worker.start()

    def submit(self, terms, arrival_at=None):
        t = Ticket(submitted_at=arrival_at, deadline_us=0.0)
        self.q.put((t, terms))
        return t

    def _work(self):
        n = 0
        while True:
            item = self.q.get()
            if item is None:
                return
            t, terms = item
            if n == self.stall_at:
                time.sleep(self.stall_s)
            time.sleep(self.service_s)
            t.resolve(_Result(np.asarray(terms, np.uint32)))
            n += 1

    def close(self):
        self.q.put(None)
        self.worker.join(5)


def _replay(server, n=400, seconds=1.0):
    times = np.linspace(0, seconds, n, endpoint=False)
    queries = [(i,) for i in range(n)]
    with window.Stamps(Ticket):
        rp = window.Replay(server.submit, queries, times,
                           time.perf_counter() + 0.02,
                           read=lambda v: (v.doc_ids, v.algorithm)).start()
        rp.join(10)
        rp.wait_answers(time.perf_counter() + 10)
    server.close()
    resolved, answers, routes = rp.outcomes()
    ok = np.array([a is not None for a in answers])
    return window.window_metrics(times, resolved, ok, seconds)


def test_nearest_rank():
    v = list(range(1, 101))
    assert window.nearest_rank(v, 50) == 50
    assert window.nearest_rank(v, 99) == 99
    assert window.nearest_rank([5.0], 99) == 5.0
    assert window.nearest_rank([1, 2, np.inf], 50) == 2


def test_a_stall_raises_the_tail_and_lowers_the_rate():
    calm = _replay(FakeServer())
    stalled = _replay(FakeServer(stall_at=200, stall_s=0.6))
    assert calm["due"] == stalled["due"] == 400
    assert stalled["p99_ms"] > calm["p99_ms"] + 300
    assert stalled["p50_ms"] > calm["p50_ms"]
    assert stalled["qps"] < calm["qps"]
    assert calm["qps"] == pytest.approx(400, rel=0.05)


def test_failed_and_unanswered_requests_count_as_infinitely_late():
    due = np.array([0.0, 0.1, 0.2, 0.3])
    resolved = np.array([0.01, 0.12, np.nan, 0.35])
    ok = np.array([True, True, False, False])
    m = window.window_metrics(due, resolved, ok, 1.0)
    assert m["p99_ms"] == np.inf and m["p50_ms"] == pytest.approx(20.0)
    assert m["qps"] == 2.0 and m["answered_in_window"] == 2


def test_answers_after_the_close_are_not_in_the_rate():
    due = np.array([0.0, 0.5, 0.9])
    resolved = np.array([0.1, 0.6, 1.2])
    m = window.window_metrics(due, resolved, np.ones(3, bool), 1.0)
    assert m["qps"] == 2.0 and m["p99_ms"] == pytest.approx(300.0)


def test_backlog_counts_due_but_unanswered():
    due = np.array([0.0, 0.1, 0.2, 0.3])
    resolved = np.array([0.05, 0.4, np.nan, 0.35])
    assert window.backlog(due, resolved, [0.0, 0.25, 0.5]) == [1, 2, 1]


def test_stamps_are_removed_after_the_window():
    before = (Ticket.resolve, Ticket.resolve_error)
    with window.Stamps(Ticket):
        t = Ticket(submitted_at=0.0, deadline_us=0.0)
        t.resolve(1)
        assert hasattr(t, window.Stamps.ATTR)
    assert (Ticket.resolve, Ticket.resolve_error) == before
