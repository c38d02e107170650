"""Bucketed batch executor: group QueryPlans by shape signature, stack their
DeviceSet rows into (B, …) arrays, and run each bucket in ONE jit execution.

The contract with the planner: every plan in a bucket shares
``ShapeSig(k, ts, gmaxes, capacity_tier, shards, replicas)``, so the
stacked arrays are shape-uniform and the whole bucket hits a single
compiled executable (``core.engine._intersect_k_batch``, its z-sharded
twin ``_intersect_k_sharded_batch`` when ``sig.shards > 1`` on a 1-D
mesh, or the 2-D ``_intersect_k_mesh2d_batch`` when a topology is
attached and the signature is mesh-routed).  Queries whose survivor count
exceeds the capacity tier raise per-query overflow flags; the engine
re-runs just the overflowing subset once at full capacity — a second
(rare) jit execution, not a recompile of the bucket.

With a 2-D topology, single-device buckets additionally get *placed*: the
executor asks the topology's :class:`~repro.exec.topology.ReplicaBalancer`
for the least-loaded replica row and resolves the bucket's sets against
that row's plain mirrors, so small-query traffic spreads across the
data-parallel axis instead of serializing on device 0.  Placement is not
part of the signature — the same bucket may run on any replica.

Per-query timing is amortized: each result's stats carry ``batch_us`` (the
bucket wall time divided by bucket size), which is the honest per-query
cost under heavy traffic.

Asynchronous dispatch: :func:`dispatch_bucket` is the non-blocking half of
:func:`execute_bucket` — it issues the bucket's first jit pass (routing,
balancer placement, lazy-mirror resolution) and returns an
:class:`InFlightBucket` whose :meth:`~InFlightBucket.collect` blocks for
the transfer, runs overflow re-runs, releases the balancer, and feeds the
capacity model.  JAX's async dispatch means the device computes while the
handle is held, so a caller that dispatches several buckets before
collecting overlaps them — across replica rows, and host post-processing
against device compute.  The module tracks the overlap in
``EXEC_COUNTERS``: ``inflight_dispatches`` per dispatched bucket,
``inflight_collects`` per one-shot teardown (collect completion or
failure — after a drain the two match, the no-lost-bucket invariant),
``overlap_high_water`` (max simultaneous in-flight buckets), and
``collect_us`` (cumulative blocking-collect time).
"""
from __future__ import annotations

import threading
import time
from collections import defaultdict
from typing import (
    Callable, Dict, Iterable, List, Mapping, Optional, Sequence, Tuple,
)

import numpy as np

from ..core.engine import (
    EXEC_COUNTERS, SHARD_AXIS, DeviceSet, PendingBatch,
    default_capacity_per_shard, default_expr_capacity_per_shard,
    dispatch_count_batch, dispatch_count_mesh2d_batch,
    dispatch_count_sharded_batch, dispatch_device_batch, dispatch_expr_batch,
    dispatch_expr_mesh2d_batch, dispatch_expr_sharded_batch,
    dispatch_mesh2d_batch, dispatch_sharded_batch, expr_total_width,
)
from ..obs.profile import sig_label
from .expr import subexpr_keys
from .plan import QueryPlan, ShapeSig, plan_query

__all__ = [
    "bucket_plans",
    "InFlightBucket",
    "dispatch_bucket",
    "execute_bucket",
    "execute_plan_buckets",
    "execute_name_queries",
]

# process-global in-flight gauge behind overlap_high_water: dispatch_bucket
# increments, InFlightBucket.collect decrements, and the high-water mark
# lands in EXEC_COUNTERS (counters themselves stay unlocked/approximate;
# the gauge gets a lock because overlap accounting is the one telemetry
# tests assert exactly across threads)
_inflight_lock = threading.Lock()
_inflight_now = 0


def _inflight_enter() -> None:
    global _inflight_now
    with _inflight_lock:
        _inflight_now += 1
        if _inflight_now > EXEC_COUNTERS["overlap_high_water"]:
            EXEC_COUNTERS["overlap_high_water"] = _inflight_now


def _inflight_exit() -> None:
    global _inflight_now
    with _inflight_lock:
        _inflight_now = max(0, _inflight_now - 1)


def bucket_plans(
    indexed_plans: Iterable[Tuple[int, QueryPlan]],
) -> Dict[ShapeSig, List[Tuple[int, QueryPlan]]]:
    """Group (query_index, plan) pairs by shape signature (insertion order).

    Accepts device plans only (asserts); pure bookkeeping, no counters.
    Each returned bucket is shape-uniform: stacking its rows yields
    ``(B, 2^t_i, …)`` arrays ready for one jit execution.
    """
    buckets: Dict[ShapeSig, List[Tuple[int, QueryPlan]]] = defaultdict(list)
    for qi, plan in indexed_plans:
        assert plan.algorithm == "device" and plan.sig is not None, (
            "only device plans can be bucketed"
        )
        buckets[plan.sig].append((qi, plan))
    return dict(buckets)


class InFlightBucket:
    """Handle for one dispatched-but-not-collected bucket.

    Created by :func:`dispatch_bucket`; holds the pipeline's
    :class:`~repro.core.engine.PendingBatch`, the bucket bookkeeping
    (items, signature, balancer placement), and finishes the job in
    :meth:`collect`.  The split is what lets a serving loop keep several
    buckets on the device at once: dispatch is cheap host work (routing +
    jit call issue), collect is where the blocking transfer lives.

    Balancer accounting: a balancer-placed bucket holds its replica's
    in-flight weight from dispatch until :meth:`collect` — so
    ``ReplicaBalancer.load_snapshot()["in_flight"]`` reflects work that is
    *actually on the device*, and least-loaded routing of the next
    dispatch sees it.  Release happens exactly once, even when collect
    raises.

    :meth:`collect` is idempotent (memoized) and thread-safe against
    double-release, but is meant to be called by one owner; ``is_ready()``
    is safe to poll from anywhere.
    """

    def __init__(self, sig: ShapeSig, items: Sequence[Tuple[int, QueryPlan]],
                 pending: PendingBatch, dispatched_at: float,
                 capacity_model=None, topology=None,
                 replica: Optional[int] = None, weight: float = 0.0,
                 obs=None):
        self.sig = sig
        self.items = list(items)
        self.pending = pending
        self.dispatched_at = dispatched_at
        self.dispatch_end_at = time.perf_counter()
        self.capacity_model = capacity_model
        self.topology = topology
        self.replica = replica
        self.weight = weight
        self.obs = obs
        self.span = None
        # set by an owner that scatters the results itself (see end_spans)
        self.hold_spans = False
        self._collect_span = None
        self._scatter_span = None
        self._out: Optional[Dict[int, Tuple[np.ndarray, Dict]]] = None
        self._finished = False
        if obs is not None:
            obs.inflight.inc()
            obs.inflight_high_water.set(obs.inflight.value)
            if obs.tracer.enabled:
                # bucket root span, backdated to dispatch start; the
                # dispatch stage is already over, recorded retroactively
                self.span = obs.tracer.start(
                    "bucket", start_us=dispatched_at * 1e6,
                    sig=sig_label(sig), batch=len(self.items),
                    replica=replica)
                obs.tracer.span_at(
                    "dispatch", dispatched_at * 1e6,
                    self.dispatch_end_at * 1e6, parent=self.span)

    def is_ready(self) -> bool:
        """Non-blocking readiness peek: True when the first pass's device
        buffers have materialized (collect would only pay host work and
        any rare overflow re-run)."""
        return self.pending.is_ready()

    def _finish(self, failed: bool = False) -> None:
        """One-shot teardown: return the balancer weight and leave the
        in-flight gauge.  Runs on first collect completion OR failure.
        ``failed=True`` (dispatch/collect raised) additionally leaves the
        failure trace: balancer row ``failures``, the
        ``dispatch_failures`` counter in both telemetry worlds, and an
        ``error``-flagged bucket span."""
        if self._finished:
            return
        self._finished = True
        if self.replica is not None and self.topology is not None:
            self.topology.balancer.release(self.replica, self.weight,
                                           failed=failed)
        EXEC_COUNTERS["inflight_collects"] += 1
        if failed:
            EXEC_COUNTERS.bump("dispatch_failures")
        _inflight_exit()
        if self.obs is not None:
            self.obs.inflight.dec()
            if failed:
                self.obs.dispatch_failures.inc()
                if self.span is not None:
                    self.span.end(error=True)

    def _trace_collect(self, c0: float) -> None:
        """Open the ``collect`` span at ``c0`` with a ``fetch`` child per
        blocking transfer and a ``rerun`` child per overflow re-run (its
        issue to the end of its fetch), read from the pass record; then
        open ``scatter`` where the last transfer ended."""
        tracer = self.obs.tracer
        passes = self.pending.passes
        span = tracer.start("collect", parent=self.span, start_us=c0 * 1e6)
        rerun_rows = 0
        for p in passes:
            if p.pass_no:
                rerun_rows += p.rows
                tracer.span_at("rerun", p.t_issue * 1e6, p.t_fetched * 1e6,
                               parent=span, rows=p.rows,
                               capacity=p.capacity)
            tracer.span_at("fetch", p.t_fetch * 1e6, p.t_fetched * 1e6,
                           parent=span, **{"pass": p.pass_no})
        self.span.set(passes=max(1, len(passes)), rerun_rows=rerun_rows)
        self._collect_span = span
        self._scatter_span = tracer.start(
            "scatter", parent=span, rows=len(self.items),
            start_us=passes[-1].t_fetched * 1e6 if passes else None)

    def end_spans(self) -> None:
        """Close the ``scatter``, ``collect`` and ``bucket`` spans.  With
        ``hold_spans`` set, :meth:`collect` leaves them open for its
        owner, whose result scatter (cache store, ticket resolution) they
        then cover until it calls this; idempotent, and a no-op with
        tracing off."""
        if self._collect_span is not None:
            self._scatter_span.end()
            self._collect_span.end()
            self._collect_span = None
            self.span.end()

    def collect(self) -> Dict[int, Tuple[np.ndarray, Dict]]:
        """Block for the bucket's results; returns {query_index: (values,
        stats)} exactly as :func:`execute_bucket` does.

        Performs the deferred ``jax.device_get``, the overflow re-run
        passes, balancer release, ``batch_us`` stamping (dispatch-to-
        collect wall over bucket size), and the capacity-model feedback.
        Needs no executor lock: re-runs resolve against the DeviceSet rows
        captured at dispatch (no lazy-mirror mutation), the balancer and
        the capacity model are internally locked.  Adds the blocking time
        to ``EXEC_COUNTERS["collect_us"]``.

        With ``obs`` attached: observes the dispatch→collect latency,
        batch-size, and per-row survivor histograms, feeds the per-
        signature :class:`~repro.obs.profile.ProfileStore`, and (tracing
        on) records the ``collect`` span with its ``fetch``, ``rerun`` and
        ``scatter`` children, and ``passes`` / ``rerun_rows`` on the bucket
        span.  The spans close on return, or, with ``hold_spans`` set, at
        the owner's :meth:`end_spans`.
        """
        if self._out is not None:
            return self._out
        c0 = time.perf_counter()
        try:
            results = self.pending.collect()
        except BaseException:
            self._finish(failed=True)
            raise
        else:
            self._finish()
        c1 = time.perf_counter()
        if self.span is not None:
            self._trace_collect(c0)
        EXEC_COUNTERS["collect_us"] += int((c1 - c0) * 1e6)
        us = (c1 - self.dispatched_at) * 1e6
        out: Dict[int, Tuple[np.ndarray, Dict]] = {}
        for (qi, _), (values, stats) in zip(self.items, results):
            stats["batch_us"] = us / len(self.items)
            if self.replica is not None:
                stats["replica"] = self.replica
            out[qi] = (values, stats)
        if self.capacity_model is not None:
            self.capacity_model.observe_bucket(
                self.sig, [stats for _, stats in out.values()])
        if self.obs is not None:
            self.obs.collect_latency.observe(us)
            self.obs.batch_size.observe(len(self.items))
            for _, stats in out.values():
                if "r" in stats:
                    self.obs.survivors.observe(stats["r"])
            self.obs.profile.observe(self.sig, len(self.items), us)
        self._out = out
        if not self.hold_spans:
            self.end_spans()
        return out


def dispatch_bucket(
    get_set: Callable[[object], DeviceSet],
    sig: ShapeSig,
    items: Sequence[Tuple[int, QueryPlan]],
    use_pallas="auto",
    mesh=None,
    shard_axis: str = SHARD_AXIS,
    get_sharded_set: Optional[Callable[[object], DeviceSet]] = None,
    capacity_model=None,
    topology=None,
    get_replica_set: Optional[Callable[[int, object], DeviceSet]] = None,
    obs=None,
) -> InFlightBucket:
    """Dispatch ONE same-signature bucket without blocking; returns an
    :class:`InFlightBucket` whose :meth:`~InFlightBucket.collect` yields
    {query_index: (values, stats)}.

    Routing is identical to :func:`execute_bucket` (which is now just
    ``dispatch_bucket(...).collect()``): 2-D topology-routed signatures go
    through ``dispatch_mesh2d_batch``, ``shards > 1`` through
    ``dispatch_sharded_batch`` on ``mesh``, and single-device buckets on a
    multi-replica topology are placed on the least-loaded replica row by
    the balancer — whose weight is now held until collect, so overlapping
    dispatches see each other's in-flight load.

    Caller contract: dispatch resolves terms through ``get_set`` /
    ``get_sharded_set`` / ``get_replica_set``, which on the engines build
    lazy per-row mirrors — serialize *dispatches* (the serving layer holds
    its exec lock here) but collect freely outside any lock.

    Counters: ``inflight_dispatches`` per bucket; ``overlap_high_water``
    tracks the max simultaneously dispatched-not-collected buckets;
    ``replica_dispatches`` per balancer placement; the per-pass pipeline
    counters are unchanged.  A dispatch that raises (any branch) bumps
    ``dispatch_failures`` once — balancer branches additionally mark the
    row's failure via ``release(..., failed=True)``.

    ``obs``: an optional :class:`repro.obs.Obs`.  When given, the bucket
    reports through it — in-flight gauge + high-water, dispatch→collect
    latency / batch-size / survivor histograms, the per-signature profile
    store, and (tracer enabled) a ``bucket`` span with a retroactive
    ``dispatch`` child and, at collect, a ``collect`` child holding
    ``fetch`` / ``rerun`` / ``scatter``.  ``None`` keeps the
    executor layer decoupled: only ``EXEC_COUNTERS`` is touched.
    """
    try:
        return _dispatch_bucket(
            get_set, sig, items, use_pallas=use_pallas, mesh=mesh,
            shard_axis=shard_axis, get_sharded_set=get_sharded_set,
            capacity_model=capacity_model, topology=topology,
            get_replica_set=get_replica_set, obs=obs,
        )
    except BaseException:
        EXEC_COUNTERS.bump("dispatch_failures")
        if obs is not None:
            obs.dispatch_failures.inc()
        raise


def _dispatch_bucket(
    get_set: Callable[[object], DeviceSet],
    sig: ShapeSig,
    items: Sequence[Tuple[int, QueryPlan]],
    use_pallas="auto",
    mesh=None,
    shard_axis: str = SHARD_AXIS,
    get_sharded_set: Optional[Callable[[object], DeviceSet]] = None,
    capacity_model=None,
    topology=None,
    get_replica_set: Optional[Callable[[int, object], DeviceSet]] = None,
    obs=None,
) -> InFlightBucket:
    shards = getattr(sig, "shards", 1)
    replicas = getattr(sig, "replicas", 1)
    t0 = time.perf_counter()
    replica: Optional[int] = None
    weight = 0.0
    eshape = getattr(sig, "eshape", None)
    if eshape is not None:
        # expression DAG bucket: same routing tree, expression executables.
        # Rows resolve in the plan's canonical traversal order (plan.terms
        # IS that order — never re-sorted), and each query ships its
        # canonical subexpression keys so collect can hand intermediate
        # node results to the subexpression cache.
        sub_keys = {qi: subexpr_keys(plan.expr) for qi, plan in items}
        queries = [[t for t in plan.terms] for _, plan in items]
        if topology is not None and (shards > 1 or replicas > 1):
            assert get_sharded_set is not None, (
                "2-D expression buckets resolve through the engine's "
                "ReplicatedDeviceSet mirrors (get_sharded_set)"
            )
            rows = [[get_sharded_set(t) for t in q] for q in queries]
            pending = dispatch_expr_mesh2d_batch(
                rows, eshape, topology,
                capacity_per_shard=default_expr_capacity_per_shard(
                    sig.ts, sig.gmaxes, shards, capacity=sig.capacity_tier),
                sub_keys=[sub_keys[qi] for qi, _ in items],
            )
        elif shards > 1:
            assert mesh is not None, "sharded bucket needs the engine's mesh"
            resolve = get_sharded_set or get_set
            rows = [[resolve(t) for t in q] for q in queries]
            pending = dispatch_expr_sharded_batch(
                rows, eshape, mesh, axis=shard_axis,
                capacity_per_shard=default_expr_capacity_per_shard(
                    sig.ts, sig.gmaxes, shards, capacity=sig.capacity_tier),
                sub_keys=[sub_keys[qi] for qi, _ in items],
            )
        elif (topology is not None and topology.replicas > 1
              and get_replica_set is not None):
            # balancer cost: the DAG's dense row width per query (the
            # analogue of the flat bucket's B * G phase-1 rows)
            weight = float(len(items) * expr_total_width(sig.ts, sig.gmaxes))
            replica = topology.balancer.acquire(weight)
            try:
                rows = [[get_replica_set(replica, t) for t in q]
                        for q in queries]
                pending = dispatch_expr_batch(
                    rows, eshape, capacity=sig.capacity_tier,
                    sub_keys=[sub_keys[qi] for qi, _ in items],
                )
            except BaseException:
                topology.balancer.release(replica, weight, failed=True)
                raise
            EXEC_COUNTERS["replica_dispatches"] += 1
        else:
            rows = [[get_set(t) for t in q] for q in queries]
            pending = dispatch_expr_batch(
                rows, eshape, capacity=sig.capacity_tier,
                sub_keys=[sub_keys[qi] for qi, _ in items],
            )
        EXEC_COUNTERS["inflight_dispatches"] += 1
        _inflight_enter()
        return InFlightBucket(
            sig, items, pending, t0, capacity_model=capacity_model,
            topology=topology, replica=replica, weight=weight, obs=obs,
        )
    cands = getattr(sig, "cands", 0)
    if cands > 0:
        # count-only (suggest) bucket: plan.terms is (probe, *candidates)
        # in tie-break order (candidates ascending), sig.capacity_tier is
        # the top-K selection tier.  Same routing tree as the point path,
        # but the dispatches are single-pass — no overflow re-run exists.
        k = sig.capacity_tier
        if topology is not None and (shards > 1 or replicas > 1):
            assert get_sharded_set is not None, (
                "2-D count buckets resolve through the engine's "
                "ReplicatedDeviceSet mirrors (get_sharded_set)"
            )
            rows = [(get_sharded_set(plan.terms[0]),
                     [get_sharded_set(t) for t in plan.terms[1:]])
                    for _, plan in items]
            pending = dispatch_count_mesh2d_batch(
                rows, k, topology, use_pallas=use_pallas)
        elif shards > 1:
            assert mesh is not None, "sharded bucket needs the engine's mesh"
            resolve = get_sharded_set or get_set
            rows = [(resolve(plan.terms[0]),
                     [resolve(t) for t in plan.terms[1:]])
                    for _, plan in items]
            pending = dispatch_count_sharded_batch(
                rows, k, mesh, axis=shard_axis, use_pallas=use_pallas)
        elif (topology is not None and topology.replicas > 1
              and get_replica_set is not None):
            # balancer cost: B * C * G count-matrix cells (the count path's
            # analogue of the flat bucket's B * G phase-1 rows)
            weight = float(len(items) * cands * (1 << max(sig.ts)))
            replica = topology.balancer.acquire(weight)
            try:
                rows = [(get_replica_set(replica, plan.terms[0]),
                         [get_replica_set(replica, t)
                          for t in plan.terms[1:]])
                        for _, plan in items]
                pending = dispatch_count_batch(
                    rows, k, use_pallas=use_pallas)
            except BaseException:
                topology.balancer.release(replica, weight, failed=True)
                raise
            EXEC_COUNTERS["replica_dispatches"] += 1
        else:
            rows = [(get_set(plan.terms[0]),
                     [get_set(t) for t in plan.terms[1:]])
                    for _, plan in items]
            pending = dispatch_count_batch(rows, k, use_pallas=use_pallas)
        EXEC_COUNTERS["inflight_dispatches"] += 1
        _inflight_enter()
        return InFlightBucket(
            sig, items, pending, t0, capacity_model=capacity_model,
            topology=topology, replica=replica, weight=weight, obs=obs,
        )
    if topology is not None and (shards > 1 or replicas > 1):
        assert get_sharded_set is not None, (
            "2-D buckets resolve through the engine's ReplicatedDeviceSet "
            "mirrors (get_sharded_set)"
        )
        resolve = get_sharded_set
        rows = [[resolve(t) for t in plan.terms] for _, plan in items]
        pending = dispatch_mesh2d_batch(
            rows, topology,
            capacity_per_shard=default_capacity_per_shard(
                sig.ts, shards, capacity=sig.capacity_tier),
            use_pallas=use_pallas,
        )
    elif shards > 1:
        assert mesh is not None, "sharded bucket needs the engine's mesh"
        resolve = get_sharded_set or get_set
        rows = [[resolve(t) for t in plan.terms] for _, plan in items]
        pending = dispatch_sharded_batch(
            rows, mesh, axis=shard_axis,
            capacity_per_shard=default_capacity_per_shard(
                sig.ts, shards, capacity=sig.capacity_tier),
            use_pallas=use_pallas,
        )
    elif (topology is not None and topology.replicas > 1
          and get_replica_set is not None):
        weight = float(len(items) * (1 << sig.ts[-1]))  # B * G rows
        replica = topology.balancer.acquire(weight)
        try:
            rows = [[get_replica_set(replica, t) for t in plan.terms]
                    for _, plan in items]
            pending = dispatch_device_batch(
                rows, capacity=sig.capacity_tier, use_pallas=use_pallas
            )
        except BaseException:
            # dispatch itself failed — there is no collect to release at
            topology.balancer.release(replica, weight, failed=True)
            raise
        EXEC_COUNTERS["replica_dispatches"] += 1
    else:
        rows = [[get_set(t) for t in plan.terms] for _, plan in items]
        pending = dispatch_device_batch(
            rows, capacity=sig.capacity_tier, use_pallas=use_pallas
        )
    EXEC_COUNTERS["inflight_dispatches"] += 1
    _inflight_enter()
    return InFlightBucket(
        sig, items, pending, t0, capacity_model=capacity_model,
        topology=topology, replica=replica, weight=weight, obs=obs,
    )


def execute_bucket(
    get_set: Callable[[object], DeviceSet],
    sig: ShapeSig,
    items: Sequence[Tuple[int, QueryPlan]],
    use_pallas="auto",
    mesh=None,
    shard_axis: str = SHARD_AXIS,
    get_sharded_set: Optional[Callable[[object], DeviceSet]] = None,
    capacity_model=None,
    topology=None,
    get_replica_set: Optional[Callable[[int, object], DeviceSet]] = None,
    obs=None,
) -> Dict[int, Tuple[np.ndarray, Dict]]:
    """Execute ONE same-signature bucket; returns {query_index: (values,
    stats)}.

    This is the partial-bucket flush path: the admission queue calls it
    directly with however many queries have accumulated under ``sig`` when
    a flush fires (full power-of-two tier reached, or the oldest query's
    deadline expired) — the executor pads B up to the next power-of-two
    tier, so a partial bucket reuses the same small family of compiled
    executables as a full one.  ``get_set`` resolves a planned term to its
    DeviceSet.

    Buckets whose signature carries ``shards > 1`` run through the
    z-sharded pipeline on ``mesh`` (required then), resolving terms via
    ``get_sharded_set`` (the engine's z-sharded mirrors; falls back to
    ``get_set``, at a per-call reshard cost).  The per-shard capacity is
    derived deterministically from the signature
    (``default_capacity_per_shard``), so ``(sig, B-tier)`` fully keys the
    sharded executable too.

    With a 2-D ``topology`` attached, mesh-routed signatures
    (``shards > 1`` or ``replicas > 1``) run through the 2-D pipeline on
    ``topology.mesh`` (same mirrors, same per-shard capacity derivation),
    and single-device buckets are dispatched to the least-loaded replica
    row: the balancer is asked with the bucket's estimated cost (``B *
    G``, the phase-1 row count), terms resolve via
    ``get_replica_set(replica, term)``, the in-flight load is released
    when the bucket completes, and each result's stats carry the executing
    ``replica``.  One ``EXEC_COUNTERS["replica_dispatches"]`` bump per
    balancer-dispatched bucket.

    Shapes: every plan in ``items`` must carry ``sig`` (the executor
    asserts signature uniformity); the bucket runs as one ``(B, …)`` jit
    execution plus a rare overflow re-run.  Counters: one
    ``EXEC_COUNTERS["batch_calls"]`` (or ``"sharded_calls"``) bump per pass
    (see ``core.engine``); each result's stats carry ``batch_us`` — bucket
    wall time divided by bucket size, the honest amortized per-query cost.

    ``sig.capacity_tier`` sizes the survivor buffer on both paths (the
    sharded per-shard buffer is derived from it via
    ``default_capacity_per_shard``), so a planner consulting a learned
    capacity model changes the executed shapes through the signature alone.
    With a ``capacity_model`` attached, the bucket's per-query survivor
    stats are fed back to it after execution — the telemetry loop the model
    learns from.

    The synchronous composition of :func:`dispatch_bucket` +
    :meth:`InFlightBucket.collect` — callers that can overlap buckets use
    the two halves directly.
    """
    return dispatch_bucket(
        get_set, sig, items, use_pallas=use_pallas, mesh=mesh,
        shard_axis=shard_axis, get_sharded_set=get_sharded_set,
        capacity_model=capacity_model, topology=topology,
        get_replica_set=get_replica_set, obs=obs,
    ).collect()


def execute_plan_buckets(
    get_set: Callable[[object], DeviceSet],
    indexed_plans: Iterable[Tuple[int, QueryPlan]],
    use_pallas="auto",
    mesh=None,
    shard_axis: str = SHARD_AXIS,
    get_sharded_set: Optional[Callable[[object], DeviceSet]] = None,
    capacity_model=None,
    topology=None,
    get_replica_set: Optional[Callable[[int, object], DeviceSet]] = None,
    max_inflight: int = 4,
    obs=None,
) -> Dict[int, Tuple[np.ndarray, Dict]]:
    """Execute device plans bucket-by-bucket; returns {query_index: (values,
    stats)}.

    Synchronous whole-batch entry: groups ``indexed_plans`` by shape
    signature and pipelines the buckets through :func:`dispatch_bucket` /
    :meth:`InFlightBucket.collect` with a bounded in-flight window — one
    jit execution per distinct signature (plus rare overflow re-runs),
    i.e. O(#signatures) device dispatches for the whole batch, with up to
    ``max_inflight`` buckets overlapped on the device (distinct-signature
    buckets are independent; on a multi-replica topology they also land on
    different rows).  All results are collected before returning, so the
    call is externally synchronous.  ``get_set`` resolves a planned term
    to its DeviceSet; sharded-signature buckets resolve via
    ``get_sharded_set`` and run on ``mesh`` (or on ``topology.mesh`` when
    a 2-D topology is attached, which also spreads single-device buckets
    over the replicas via ``get_replica_set``).
    """
    out: Dict[int, Tuple[np.ndarray, Dict]] = {}
    window: List[InFlightBucket] = []
    for sig, items in bucket_plans(indexed_plans).items():
        window.append(dispatch_bucket(
            get_set, sig, items, use_pallas=use_pallas, mesh=mesh,
            shard_axis=shard_axis, get_sharded_set=get_sharded_set,
            capacity_model=capacity_model, topology=topology,
            get_replica_set=get_replica_set, obs=obs,
        ))
        if len(window) >= max(1, max_inflight):
            out.update(window.pop(0).collect())
    for bucket in window:
        out.update(bucket.collect())
    return out


def execute_name_queries(
    sets: Mapping[str, DeviceSet],
    queries: Sequence[Sequence[str]],
    use_pallas="auto",
    mesh=None,
    shard_axis: str = SHARD_AXIS,
    shard_min_g: Optional[int] = None,
    sharded_sets: Optional[Mapping[str, DeviceSet]] = None,
    topology=None,
    get_sharded_set: Optional[Callable[[object], DeviceSet]] = None,
    get_replica_set: Optional[Callable[[int, object], DeviceSet]] = None,
) -> List[Tuple[np.ndarray, Dict]]:
    """BatchedEngine.query_many backend: plan -> bucket -> execute -> scatter.

    ``queries`` are lists of set names; unknown names raise KeyError (same
    contract as single-query ``BatchedEngine.query``).  Duplicate names
    within a query are deduped by the planner.  Results return in request
    order regardless of bucketing.  With a ``mesh``, huge-G plans route
    z-sharded per the planner's ``shard_min_g`` threshold, resolving
    mirrors via ``get_sharded_set`` (or a plain ``sharded_sets`` mapping);
    with a 2-D ``topology`` they route to the 2-D pipeline (the engine's
    lazy ``get_mesh_set`` / ``get_replica_set`` builders — a raw mapping
    won't do there, mirrors materialize on first dispatch) and
    single-device buckets spread over the replicas.  Counters: one
    ``batch_calls`` / ``sharded_calls`` / ``mesh2d_calls`` per distinct
    signature (plus ``*rerun_calls`` on overflow) via
    :func:`execute_bucket`.
    """
    for q in queries:
        for name in q:
            if name not in sets:
                raise KeyError(name)
    if topology is not None:
        mesh_shards, mesh_replicas = topology.shards, topology.replicas
    else:
        mesh_shards = mesh.shape[shard_axis] if mesh is not None else 1
        mesh_replicas = 1
    plan_kw = {} if shard_min_g is None else {"shard_min_g": shard_min_g}
    plans = [
        plan_query(sets, q, hashbin_ratio=float("inf"), device=True,
                   mesh_shards=mesh_shards, mesh_replicas=mesh_replicas,
                   **plan_kw)
        for q in queries
    ]
    # no sharded mirrors supplied -> let execute_bucket fall back to the
    # plain mirrors (correct, at a per-call reshard cost)
    if get_sharded_set is None and sharded_sets:
        get_sharded_set = lambda name: sharded_sets[name]
    by_index = execute_plan_buckets(
        lambda name: sets[name],
        [(i, p) for i, p in enumerate(plans) if p.algorithm == "device"],
        use_pallas=use_pallas,
        mesh=mesh,
        shard_axis=shard_axis,
        get_sharded_set=get_sharded_set,
        topology=topology,
        get_replica_set=get_replica_set,
    )
    # fresh objects per miss: callers annotate stats dicts in place
    return [
        by_index[i] if i in by_index else (np.empty(0, np.uint32),
                                           {"r": 0, "batch_size": 0})
        for i in range(len(queries))
    ]
