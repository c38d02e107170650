"""The open-loop driver and the window's arithmetic.

The driver replays a log in real time against an engine whose background
flusher runs, as ``repro.serve.loadgen.run_wallclock`` does (copied, so
that the yardstick does not move with the program): a few submitter
threads sleep until each request is due and submit it with
``arrival_at`` stamped to that time, so a submitter that wakes late
charges its lateness to the request (no coordinated omission).

Every number comes from all the requests due in the window, never from
medians of chunks:

- a request's latency runs from when it was due to when its answer
  resolved; one that failed or never resolved counts as infinitely late;
- ``p50_ms`` and ``p99_ms`` are nearest-rank percentiles of those
  latencies;
- ``qps`` counts the requests answered correctly by the window's close,
  over the window's seconds.
"""
from __future__ import annotations

import math
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np


def nearest_rank(values: Sequence[float], q: float) -> float:
    """The smallest value with at least ``q`` percent of ``values`` at or
    below it."""
    ordered = np.sort(np.asarray(values, dtype=np.float64))
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return float(ordered[rank - 1])


def window_metrics(due_s: np.ndarray, resolved_s: np.ndarray,
                   ok: np.ndarray, seconds: float) -> Dict[str, float]:
    """End-to-end numbers of one window.

    ``due_s`` and ``resolved_s`` are seconds from the window's start
    (``resolved_s`` is NaN for a request that never resolved); ``ok``
    marks the requests answered correctly.
    """
    late = np.where(ok & np.isfinite(resolved_s), resolved_s - due_s, np.inf)
    in_time = ok & (resolved_s <= seconds)
    return {
        "p50_ms": nearest_rank(late, 50) * 1e3,
        "p90_ms": nearest_rank(late, 90) * 1e3,
        "p95_ms": nearest_rank(late, 95) * 1e3,
        "p99_ms": nearest_rank(late, 99) * 1e3,
        "qps": float(in_time.sum()) / seconds,
        "answered_in_window": int(in_time.sum()),
        "due": int(len(due_s)),
    }


def backlog(due_s: np.ndarray, resolved_s: np.ndarray,
            at: Sequence[float]) -> List[int]:
    """Requests due but not yet answered at each time in ``at``."""
    done = np.where(np.isfinite(resolved_s), resolved_s, np.inf)
    return [int((due_s <= t).sum() - (done <= t).sum()) for t in at]


class Stamps:
    """When each answer resolved, stamped as the program resolves it.

    The program's ``Ticket`` records how long a request queued, not when
    its answer became visible.  While installed, this wraps
    ``Ticket.resolve`` and ``Ticket.resolve_error`` to write
    ``time.perf_counter()`` onto the ticket first, before its ``done``
    event is set, so a caller that sees ``done`` sees the stamp.
    """

    ATTR = "bench_resolved_at"

    def __init__(self, ticket_cls):
        self.cls = ticket_cls
        self.saved = (ticket_cls.resolve, ticket_cls.resolve_error)

    def __enter__(self) -> "Stamps":
        resolve, resolve_error = self.saved
        attr = self.ATTR

        def stamped_resolve(ticket, *a, **kw):
            setattr(ticket, attr, time.perf_counter())
            return resolve(ticket, *a, **kw)

        def stamped_error(ticket, *a, **kw):
            setattr(ticket, attr, time.perf_counter())
            return resolve_error(ticket, *a, **kw)

        self.cls.resolve = stamped_resolve
        self.cls.resolve_error = stamped_error
        return self

    def __exit__(self, *exc) -> None:
        self.cls.resolve, self.cls.resolve_error = self.saved


class Replay:
    """One open-loop replay of a log; fills ``tickets`` and ``late_s``.

    ``submit(request, arrival_at=...)`` admits one request and returns its
    ticket; ``read(value)`` turns a resolved ticket's value into the
    answer and the route that answered it.  ``start`` launches the
    submitter threads; ``join`` waits for them to have submitted
    everything.
    """

    def __init__(self, submit: Callable, queries: Sequence, times: np.ndarray,
                 t0: float, read: Callable, submitters: int = 2):
        self.submit = submit
        self.read = read
        self.queries = queries
        self.times = times
        self.t0 = t0
        self.tickets: List[Optional[object]] = [None] * len(times)
        self.late_s = np.zeros(len(times))
        self.errors: List[BaseException] = []
        self.threads = [threading.Thread(target=self._slice, args=(k,
                        submitters), name=f"bench-submit-{k}", daemon=True)
                        for k in range(submitters)]

    def _slice(self, offset: int, stride: int) -> None:
        try:
            for j in range(offset, len(self.times), stride):
                due = self.t0 + float(self.times[j])
                delay = due - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                self.late_s[j] = time.perf_counter() - due
                self.tickets[j] = self.submit(self.queries[j],
                                              arrival_at=due)
        except BaseException as exc:  # reported by join, never lost
            self.errors.append(exc)

    def start(self) -> "Replay":
        for th in self.threads:
            th.start()
        return self

    def join(self, timeout: float) -> None:
        end = time.perf_counter() + timeout
        for th in self.threads:
            th.join(max(0.0, end - time.perf_counter()))
        if any(th.is_alive() for th in self.threads):
            raise RuntimeError("submitters still running past the timeout")
        if self.errors:
            raise RuntimeError("a submit raised") from self.errors[0]

    def wait_answers(self, deadline: float) -> None:
        """Wait, until the absolute ``deadline``, for every ticket."""
        for t in self.tickets:
            left = deadline - time.perf_counter()
            if t is None or left <= 0:
                continue
            t.wait(left)

    def outcomes(self):
        """Per request: resolution time from the window's start (NaN when
        unresolved), the answer (what ``read`` takes from the value, an
        exception, or None) and the route that answered it (None unless
        answered)."""
        resolved = np.full(len(self.times), np.nan)
        answers: List[object] = []
        routes: List[Optional[str]] = []
        for j, t in enumerate(self.tickets):
            if t is None or not t.done:
                answers.append(None)
                routes.append(None)
                continue
            resolved[j] = getattr(t, Stamps.ATTR) - self.t0
            if t.error is not None:
                answers.append(t.error)
                routes.append(None)
            else:
                answer, route = self.read(t.value)
                answers.append(answer)
                routes.append(route)
        return resolved, answers, routes
