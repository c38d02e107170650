"""Async admission & micro-batching front-end primitives.

The batched executor (``repro.exec``) wants signature-coherent ``(B, …)``
buckets; live traffic arrives as single queries from many concurrent
callers.  This module is the adapter between the two: an
:class:`AdmissionQueue` accumulates submissions into per-key micro-batches
(the key is a :class:`~repro.exec.plan.ShapeSig` in the search front-end,
but the queue is generic) and hands a bucket back for execution when

- **tier flush** — the bucket reaches the configured power-of-two
  ``flush_tier`` (a full bucket pads to exactly its own size, zero waste), or
- **deadline flush** — the *oldest* queued submission's deadline budget
  expires (default 2 ms), bounding the tail latency a query can lose to
  waiting for batch-mates,

whichever comes first.  Flush causes are counted in
``EXEC_COUNTERS["tier_flushes"]`` / ``["deadline_flushes"]``.

Each submission returns a :class:`Ticket` — a minimal future: callers poll
``ticket.done`` / read ``ticket.value`` after the owning engine flushes.
Tickets also carry queue-wait telemetry (``wait_us``), which is exactly the
quantity the deadline budget bounds (total latency = wait + bucket
execution).

The queue itself does no execution and holds no device state; an engine
(e.g. ``serve.search.AsyncSearchEngine``) drives it: ``submit`` into it,
``take_due(now)`` out of it, execute, resolve tickets.  All methods are
lock-protected so many caller threads can submit concurrently; the clock is
injectable so tests can fire deadlines deterministically.

Concurrency contract (audited for the background-flusher runtime): the
internal lock is held only for bucket-dict bookkeeping — never across
ticket resolution or execution — so ``submit`` cannot block behind a flush.
Every ``take_*`` method removes whole buckets from the dict *atomically
under the lock*; a (ticket, item) pair therefore leaves the queue exactly
once, no matter how ``take_full`` / ``take_due`` / ``take_all`` interleave
across threads.  That single property is what makes a drain idempotent and
safe to run concurrently with a flusher's pump: the second taker simply
finds the bucket gone.  A submission that lands *after* a take has started
goes into a fresh bucket and is picked up by the next take — never lost,
never double-flushed.  (``Ticket`` resolution being single-shot is the
backstop: a logic bug that double-flushed would raise, not clobber.)

Audit note for the overlapped (dispatch/collect-split) flusher: ticket
resolution now happens at *collect* time, outside the engine's exec lock
and potentially on a different thread than the one that took the bucket.
That is safe against this queue precisely because of the contract above —
once a ``take_*`` pops a bucket, the queue holds no reference to its
tickets, so resolution order/thread is invisible here; and because
``next_deadline_in_us`` reports 0 for full tiers, the flusher's
deadline-sleep wake covers the tier-flush case without polling.  The only
queue-side requirement the overlap adds is that ``take_*`` stay atomic
whole-bucket pops (a half-taken bucket could dispatch twice), which the
single lock already guarantees.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable, Dict, Hashable, List, Optional, Tuple

from ..core.engine import EXEC_COUNTERS

__all__ = ["Ticket", "AdmissionQueue"]


@dataclasses.dataclass
class Ticket:
    """Minimal future for one admitted request.

    ``submitted_at`` / ``deadline_us`` define the flush budget; after
    resolution ``value`` holds the engine's result, ``wait_us`` the time the
    request sat in the queue (0 for requests answered at submit time, e.g.
    result-cache hits), and ``done`` flips True.  Reading ``value`` before
    resolution raises.  A ticket whose bucket failed to execute resolves
    with the error instead: ``done`` is True, ``error`` holds the
    exception, and ``value`` re-raises it — callers polling ``done`` never
    hang on a failed bucket.

    Cross-thread contract: resolution is published through a
    ``threading.Event`` — the payload fields are written *before* the event
    is set, and the Event's internal lock gives the release/acquire pairing
    a bare bool would lack, so a caller thread that observes ``done`` (or
    returns from :meth:`wait`) is guaranteed to see the resolved value.
    Resolution is single-shot: a second ``resolve`` / ``resolve_error``
    raises instead of clobbering a result some caller may already have
    read (the failed-then-retried-bucket hazard).

    ``resolved_at`` is stamped once, on ``clock`` (the engine's), inside
    the single-shot resolution, before ``done`` is set: when the answer
    became visible.  None until then.

    Tracing: the submitting engine may stamp ``span`` (the request's root
    span) and ``admission_span`` (the queue-wait child) plus ``obs``.
    The root span is closed inside :meth:`_record_wait` — i.e. exactly
    once, under the same single-shot guarantee as resolution itself, on
    every path (value, error, cache hit, host plan), at ``resolved_at``
    when the tracer shares the ticket's clock — which is the "every
    submitted ticket yields exactly one closed root span" invariant the
    observability tests gate.
    """

    submitted_at: float
    deadline_us: float
    wait_us: float = 0.0
    error: Optional[BaseException] = None
    resolved_at: Optional[float] = None
    clock: Callable[[], float] = dataclasses.field(
        default=time.perf_counter, repr=False, compare=False)
    span: Any = dataclasses.field(default=None, repr=False, compare=False)
    admission_span: Any = dataclasses.field(
        default=None, repr=False, compare=False)
    obs: Any = dataclasses.field(default=None, repr=False, compare=False)
    _value: Any = None
    _done: threading.Event = dataclasses.field(
        default_factory=threading.Event, repr=False, compare=False)

    @property
    def done(self) -> bool:
        """True once resolved (value or error) — Event-backed, safe to poll
        from any thread."""
        return self._done.is_set()

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Block until resolved (or ``timeout`` seconds); returns ``done``.
        The blocking complement of polling ``done`` for caller threads."""
        return self._done.wait(timeout)

    @property
    def value(self) -> Any:
        if not self._done.is_set():
            raise RuntimeError("ticket not resolved yet — flush/drain first")
        if self.error is not None:
            raise self.error
        return self._value

    def _record_wait(self, wait_us: float) -> None:
        """Per-ticket wait telemetry, stamped exactly once at resolution.

        ``queue_wait_us`` accumulates integer microseconds;
        ``deadline_violations`` counts waits that exceeded this ticket's
        own budget by more than the 0.5 us virtual-clock float epsilon
        (tickets with no budget — e.g. resolved-at-submit paths with
        ``deadline_us == 0`` — can't violate).  This is the raw material
        for the load harness's SLO-burn accounting.

        The three counters are one :meth:`ExecCounters.bump_many` — a
        concurrent ``EXEC_COUNTERS.snapshot()`` sees either none or all
        of this resolution (the tearing fix).  With ``obs`` stamped, the
        wait also lands in the typed ``queue_wait_us`` histogram and the
        request's root span closes here (exactly once per ticket).
        """
        violated = (self.deadline_us > 0
                    and wait_us > self.deadline_us + 0.5)
        EXEC_COUNTERS.bump_many({
            "tickets_resolved": 1,
            "queue_wait_us": int(wait_us),
            "deadline_violations": 1 if violated else 0,
        })
        if self.obs is not None:
            self.obs.queue_wait.observe(wait_us)
        if self.span is not None:
            tracer = getattr(self.span, "tracer", None)
            at_us = (self.resolved_at * 1e6
                     if tracer is not None and tracer.clock is self.clock
                     else None)
            self.span.end(end_us=at_us, wait_us=round(wait_us, 1),
                          deadline_violation=violated,
                          error=(type(self.error).__name__
                                 if self.error is not None else None))

    def resolve(self, value: Any, wait_us: float = 0.0) -> None:
        if self._done.is_set():
            raise RuntimeError("ticket already resolved — single-shot")
        self._value = value
        self.wait_us = wait_us
        self.resolved_at = self.clock()
        self._record_wait(wait_us)
        self._done.set()  # publish AFTER the payload writes

    def resolve_error(self, exc: BaseException, wait_us: float = 0.0) -> None:
        if self._done.is_set():
            raise RuntimeError("ticket already resolved — single-shot")
        self.error = exc
        self.wait_us = wait_us
        self.resolved_at = self.clock()
        self._record_wait(wait_us)
        self._done.set()  # publish AFTER the payload writes

    def deadline_at(self) -> float:
        """Absolute clock time at which this ticket forces a flush."""
        return self.submitted_at + self.deadline_us * 1e-6


class AdmissionQueue:
    """Deadline-aware per-key micro-batch accumulator (execution-free).

    Buckets are keyed by any hashable (the search engine uses ``ShapeSig``);
    each bucket remembers insertion order, and its binding deadline is the
    *earliest* entry deadline — normally the oldest entry's, unless a later
    submission carried a tighter per-query budget.  Thread-safe.
    """

    def __init__(self, flush_tier: int = 64, deadline_us: float = 2000.0,
                 clock: Callable[[], float] = time.perf_counter):
        assert flush_tier >= 1 and (flush_tier & (flush_tier - 1)) == 0, (
            "flush_tier must be a power of two (bucket pads to pow2 tiers)"
        )
        self.flush_tier = flush_tier
        self.deadline_us = float(deadline_us)
        self.clock = clock
        self._lock = threading.Lock()
        self._buckets: Dict[Hashable, List[Tuple[Ticket, Any]]] = {}

    def submit(self, key: Hashable, item: Any,
               deadline_us: Optional[float] = None,
               submitted_at: Optional[float] = None,
               span: Any = None, obs: Any = None) -> Ticket:
        """Queue ``item`` under ``key``; returns its unresolved Ticket.

        The per-submission ``deadline_us`` overrides the queue default.
        ``submitted_at`` (engine-clock seconds) back-stamps the ticket's
        arrival time — an open-loop load generator passes the *scheduled*
        arrival so queue waits (and the deadline budget) are measured from
        when the query should have arrived, not from when the submitter
        thread got scheduled; the coordinated-omission correction.
        Submission never flushes by itself — call :meth:`take_full` /
        :meth:`take_due` afterwards so the engine (which owns execution)
        controls when device work happens.

        ``span`` / ``obs`` stamp the request's root span and telemetry
        bundle onto the ticket *before* it becomes visible to any
        concurrent flush (an "admission" child span opens here and is
        ended by the flusher when the bucket is picked up).
        """
        ticket = Ticket(
            submitted_at=(self.clock() if submitted_at is None
                          else float(submitted_at)),
            deadline_us=self.deadline_us if deadline_us is None else float(deadline_us),
            clock=self.clock,
        )
        if span is not None:
            ticket.span = span
            ticket.admission_span = span.child("admission")
        if obs is not None:
            ticket.obs = obs
        with self._lock:
            self._buckets.setdefault(key, []).append((ticket, item))
        return ticket

    def take_full(self) -> List[Tuple[Hashable, List[Tuple[Ticket, Any]]]]:
        """Remove and return buckets that reached the full flush tier."""
        out = []
        with self._lock:
            for key in [k for k, b in self._buckets.items()
                        if len(b) >= self.flush_tier]:
                out.append((key, self._buckets.pop(key)))
                EXEC_COUNTERS["tier_flushes"] += 1
        return out

    @staticmethod
    def _bucket_deadline(bucket) -> float:
        """Earliest absolute deadline in a bucket.  Usually the oldest
        entry's, but a later submission with a tighter per-query budget
        (``submit(..., deadline_us=...)``) can be the binding one."""
        return min(t.deadline_at() for t, _ in bucket)

    def take_due(self, now: Optional[float] = None
                 ) -> List[Tuple[Hashable, List[Tuple[Ticket, Any]]]]:
        """Remove and return buckets whose earliest deadline has expired.

        Full-tier buckets are also taken (counted as tier flushes) — a
        caller that only ever calls ``take_due`` still flushes correctly.
        """
        now = self.clock() if now is None else now
        out = []
        with self._lock:
            for key in list(self._buckets):
                bucket = self._buckets[key]
                if len(bucket) >= self.flush_tier:
                    out.append((key, self._buckets.pop(key)))
                    EXEC_COUNTERS["tier_flushes"] += 1
                elif bucket and self._bucket_deadline(bucket) <= now:
                    out.append((key, self._buckets.pop(key)))
                    EXEC_COUNTERS["deadline_flushes"] += 1
        return out

    def take_all(self) -> List[Tuple[Hashable, List[Tuple[Ticket, Any]]]]:
        """Remove and return every pending bucket (drain path).

        Counted as deadline flushes for partial buckets and tier flushes
        for full ones — drain is "the deadline is now".
        """
        out = []
        with self._lock:
            for key in list(self._buckets):
                bucket = self._buckets.pop(key)
                cause = ("tier_flushes" if len(bucket) >= self.flush_tier
                         else "deadline_flushes")
                EXEC_COUNTERS[cause] += 1
                out.append((key, bucket))
        return out

    def pending(self) -> int:
        """Number of queued, not-yet-flushed submissions."""
        with self._lock:
            return sum(len(b) for b in self._buckets.values())

    def next_deadline_in_us(self, now: Optional[float] = None) -> Optional[float]:
        """Microseconds until the next flush is due (<= 0 = overdue); None
        when nothing is queued.  Lets a serving loop sleep exactly as long
        as the latency budget allows instead of busy-polling.

        A bucket that already reached ``flush_tier`` is ready NOW — the
        hint is 0 regardless of any deadline, so a sleep-based pump loop
        never idles on a full, flushable bucket (deadlines alone would let
        it sleep a whole budget with work queued).
        """
        now = self.clock() if now is None else now
        with self._lock:
            if not self._buckets:
                return None
            if any(len(b) >= self.flush_tier for b in self._buckets.values()):
                return 0.0
            soonest = min(self._bucket_deadline(b)
                          for b in self._buckets.values() if b)
            return (soonest - now) * 1e6
