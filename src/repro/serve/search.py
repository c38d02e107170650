"""Conjunctive-query search serving — the paper's own application.

Builds the pre-processed index (one PrefixIndex per term posting list) and
serves conjunctive AND-queries through the batched execution subsystem
(``repro.exec``): every request batch is **planned** (terms deduped,
resolved, routed per the paper's §3.4 online policy — HashBin when the size
ratio is extreme, RanGroupScan otherwise), **bucketed** by static shape
signature, **executed** one jit call per bucket on the device engine, and
the results **scattered** back in request order.  Host-path plans (HashBin,
or RanGroupScan without a device) run per query off the same normalized
plans, so all paths agree on term dedup and set ordering.  Single-query
``query`` is just a batch of one.

Two front-ends share that pipeline:

- :class:`SearchEngine` — synchronous: the caller hands over a pre-formed
  batch (``query_batch``) and blocks for all results.
- :class:`AsyncSearchEngine` — online: many concurrent callers ``submit``
  single queries; an admission queue accumulates them into per-signature
  micro-batches and flushes each bucket when it fills a power-of-two tier
  or the oldest query's deadline budget (default 2 ms) expires, so tail
  latency is bounded while jit executions stay O(#signatures).

Both consult an LRU result cache keyed on the normalized plan (repeated
conjunctions answer without touching the device) and can pre-trace the
hot shape signatures of a sample workload at index-build time
(:meth:`SearchEngine.warm`), so first live requests pay no compile.
See ``docs/ARCHITECTURE.md`` for the full dataflow.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.engine import (
    EXEC_COUNTERS, BatchedEngine, pow2_tiers, warm_from_plans,
)
from ..exec.plan import SHARD_MIN_G
from ..core.hashing import default_permutation, random_hash_family
from ..core.intersect import hashbin, rangroupscan
from ..core.partition import preprocess_prefix
from ..exec.adaptive import AdaptiveDeadline, CapacityModel, adaptive_key
from ..exec.batch import InFlightBucket, dispatch_bucket, execute_plan_buckets
from ..exec.cache import ResultCache
from ..exec.candidates import CandidateIndex
from ..exec.expr import (
    And, Diff, Expr, Or, Term, canonicalize, eval_host, expr_key,
)
from ..exec.plan import QueryPlan, ShapeSig, plan_query, plan_suggest
from ..obs import get_obs
from ..obs.profile import sig_label
from .admission import AdmissionQueue, Ticket


@dataclasses.dataclass
class QueryResult:
    """One served query: sorted doc ids + how they were produced.

    ``latency_us`` is per-query wall time for host paths and the amortized
    ``batch_us`` (bucket wall / bucket size) for device buckets;
    ``algorithm`` names the executed path (``"rangroupscan"``,
    ``"rangroupscan/device"``, ``"rangroupscan/sharded"``,
    ``"rangroupscan/mesh2d"``, ``"hashbin"``, ``"empty"``); ``stats`` is
    path-specific (device stats include ``r``, ``tuples_survived``,
    ``capacity``, ``batch_size``; balancer-dispatched buckets carry
    ``replica``; cache hits carry ``{"cached": True}``).
    ``doc_ids`` may be shared with the result cache — treat it as
    immutable.
    """

    doc_ids: np.ndarray
    latency_us: float
    algorithm: str
    stats: Dict


def _device_result_name(stats: Dict) -> str:
    """Executed-path label from a device bucket's stats: the 2-D pipeline
    stamps ``n_replicas`` (even when 1 — the 1-D path never does), the 1-D
    sharded pipeline stamps ``n_shards > 1``; expression-DAG buckets stamp
    ``expr_width`` on every path."""
    base = "expr" if "expr_width" in stats else "rangroupscan"
    if "n_replicas" in stats:
        return base + "/mesh2d"
    if stats.get("n_shards", 1) > 1:
        return base + "/sharded"
    return base + "/device"


class SearchEngine:
    """In-memory conjunctive search over an inverted index.

    ``result_cache`` (entries; 0 disables) adds an LRU cache keyed on the
    normalized plan — hits bump ``EXEC_COUNTERS["result_cache_hits"]`` and
    skip execution entirely.  With ``use_device`` the batched device engine
    mirrors every posting list at build time.  A 1-D ``mesh`` (implies
    ``use_device``) additionally builds z-sharded mirrors and routes
    huge-G queries (largest set with ``2^t >= shard_min_g`` group tuples)
    through the zero-communication sharded pipeline; everything else stays
    single-device.  A 2-D ``topology``
    (``exec.topology.Topology``; exclusive with ``mesh``, implies
    ``use_device``) composes data-parallel replicas with z-sharding:
    huge-G queries run on the full data x shard mesh (batch split over the
    replica rows), and single-device buckets are spread across the
    replicas by the topology's load balancer.  The cache registers itself
    on the device engine's mutation hook, so index changes
    (:meth:`add_postings`, or direct ``device.add``) can never serve stale
    cached results.
    """

    def __init__(self, postings: Dict[int, np.ndarray], w: int = 256,
                 m: int = 2, seed: int = 0, use_device: bool = False,
                 hashbin_ratio: float = 100.0, result_cache: int = 0,
                 mesh=None, shard_min_g: int = SHARD_MIN_G,
                 adaptive_capacity=False, topology=None, obs=None):
        # observability bundle (repro.obs.Obs): typed metrics + profile
        # store always report through it; tracing only if its tracer is
        # enabled.  Defaults to the shared process-global instance.
        self.obs = obs if obs is not None else get_obs()
        self.family = random_hash_family(m, w, seed=seed)
        self.perm = default_permutation(seed)
        self.w, self.m = w, m
        self.hashbin_ratio = hashbin_ratio
        self.use_device = (use_device or mesh is not None
                           or topology is not None)
        t0 = time.perf_counter()
        self.index = {
            t: preprocess_prefix(p, w=w, m=m, family=self.family,
                                 perm=self.perm)
            for t, p in postings.items() if len(p)
        }
        self.build_s = time.perf_counter() - t0
        self.device = (BatchedEngine(use_pallas="auto", mesh=mesh,
                                     shard_min_g=shard_min_g,
                                     topology=topology)
                       if self.use_device else None)
        if self.device:
            for t, idx in self.index.items():
                self.device.add(str(t), idx)
        self.cache = ResultCache(result_cache)
        if self.device:
            # build-time adds are done; from here on every index mutation
            # stales the result cache
            self.device.on_mutate(self.cache.bump_generation)
        # adaptive capacity: pass True (default model) or a CapacityModel to
        # size survivor buffers from observed survivor counts instead of the
        # static G/4 rule; the planner consults it, the executor feeds it,
        # and tier promotions invalidate the result cache + re-warm (below)
        if isinstance(adaptive_capacity, CapacityModel):
            self.capacity_model: Optional[CapacityModel] = adaptive_capacity
        else:
            self.capacity_model = CapacityModel() if adaptive_capacity else None
        if self.capacity_model is not None:
            self.capacity_model.on_promotion(self._on_tier_promotion)
        self.warmed_sigs: List[ShapeSig] = []
        # adaptive-key -> (representative query spec — a term list or an
        # Expr — and warmed b_tiers): what a promotion must re-warm so the
        # new tier's executable is traced deliberately instead of at first
        # live flush
        self._warm_reps: Dict[Tuple, Tuple] = {}

    def plan(self, terms) -> QueryPlan:
        """Normalize + route one query (dedup, §3.4 policy, shape sig,
        mesh routing when a mesh or 2-D topology is attached, learned
        capacity tier when an adaptive model is attached).  ``terms`` is a
        term sequence (flat conjunction) or an ``exec.expr.Expr`` boolean
        expression over ∩/∪/∖."""
        return plan_query(self.index, terms,
                          hashbin_ratio=self.hashbin_ratio,
                          device=self.device is not None,
                          mesh_shards=(self.device.n_shards
                                       if self.device else 1),
                          mesh_replicas=(self.device.n_replicas
                                         if self.device else 1),
                          shard_min_g=(self.device.shard_min_g
                                       if self.device else SHARD_MIN_G),
                          capacity_model=self.capacity_model)

    def _on_tier_promotion(self, key, old_tier: int, new_tier: int) -> None:
        """Capacity-tier promotion hook (fired by the CapacityModel).

        A promoted tier re-keys the signature's executable, so this is the
        deliberate invalidation/retrace point: the result cache is
        invalidated (cached entries' stats/capacity describe the old tier,
        and in-flight results captured against the old generation must not
        re-enter).  Whole-cache invalidation is a deliberate tradeoff:
        cached doc ids are capacity-independent (the overflow re-run keeps
        results exact), but the cache cannot map its ``(algorithm, terms)``
        keys back to signatures for a selective drop, and promotions are
        rare — once per hot signature after ``min_observations`` samples —
        so the hit-rate dip is transient.  When the signature was
        compile-warmed, its
        representative is re-traced at the same batch tiers so the promoted
        executable is compiled here, at promotion time, not at the next
        live flush.
        """
        self.cache.invalidate()
        rep = self._warm_reps.get(key)
        if rep is None or self.device is None:
            return
        spec, b_tiers = rep
        plan = self.plan(spec)  # re-plans with the promoted tier
        if plan.algorithm != "device":
            return
        warm_from_plans(
            [plan], lambda t: self.device.sets[str(t)], top_k=1,
            b_tiers=b_tiers, use_pallas=self.device.use_pallas,
            mesh=self.device.mesh, axis=self.device.shard_axis,
            get_sharded_set=lambda t: self.device.get_mesh_set(str(t)),
            topology=self.device.topology,
            get_replica_set=lambda r, t: self.device.get_replica_set(
                r, str(t)))
        if plan.sig not in self.warmed_sigs:
            self.warmed_sigs.append(plan.sig)

    def add_postings(self, term: int, postings: np.ndarray) -> None:
        """Add or replace one term's posting list after build.

        Re-runs preprocessing for the term, refreshes the device mirrors
        (plain + sharded), and — via the engine's mutation hook — bumps the
        result-cache generation so every previously cached conjunction
        involving any term is stale.  Without a device the cache generation
        is bumped directly.
        """
        idx = preprocess_prefix(np.asarray(postings, dtype=np.uint32),
                                w=self.w, m=self.m, family=self.family,
                                perm=self.perm)
        self.index[term] = idx
        if self.device:
            self.device.add(str(term), idx)  # fires the cache hook
        else:
            self.cache.bump_generation()

    def invalidate_cache(self) -> None:
        """Explicit result-cache invalidation hook (e.g. after mutating
        postings through some path the engine can't observe)."""
        self.cache.invalidate()

    def warm(self, sample_queries: Sequence[Sequence[int]], top_k: int = 8,
             b_tiers: Sequence[int] = (1,)) -> List[ShapeSig]:
        """Pre-trace the hot shape signatures of a sample workload.

        Index-build-time compile warming: plans ``sample_queries`` with the
        engine's own routing, counts device-routed signatures, and traces
        the ``top_k`` most frequent ones at every batch tier in ``b_tiers``
        (see ``core.engine.warm_executables`` — tier ``b`` covers live
        flushes of size in ``(b/2, b]``), so first live requests on a
        warmed signature hit a compiled executable instead of eating
        trace+compile latency.  Bumps ``EXEC_COUNTERS["warm_executions"]``
        per traced (signature, tier).  Returns the warmed signatures, most
        frequent first, and records them on ``self.warmed_sigs``.
        """
        assert self.device is not None, "warming is a device-path concept"
        plans = [self.plan(q) for q in sample_queries]
        self.warmed_sigs = warm_from_plans(
            plans, lambda t: self.device.sets[str(t)], top_k=top_k,
            b_tiers=b_tiers, use_pallas=self.device.use_pallas,
            mesh=self.device.mesh, axis=self.device.shard_axis,
            get_sharded_set=lambda t: self.device.get_mesh_set(str(t)),
            topology=self.device.topology,
            get_replica_set=lambda r, t: self.device.get_replica_set(
                r, str(t)))
        # remember one representative per warmed signature so an adaptive
        # capacity-tier promotion can re-warm the new executable (the
        # warming key follows the learned tier: plans above already carry
        # the model's current tiers via self.plan)
        warmed_keys = {adaptive_key(sig) for sig in self.warmed_sigs}
        for p in plans:
            if p.algorithm != "device":
                continue
            key = adaptive_key(p.sig)
            if key in warmed_keys and key not in self._warm_reps:
                self._warm_reps[key] = (p.query_spec(), tuple(b_tiers))
        return self.warmed_sigs

    def _cached_result(self, plan: QueryPlan) -> Optional[QueryResult]:
        """Result-cache lookup; ``"empty"`` plans bypass the cache (no work
        to save, and their misses would skew hit-rate telemetry).

        Expression plans get a second chance on a root miss: if any
        composite subtree of the canonical DAG is cached (``get_sub``),
        the remainder is merged on the host from cached subtree values and
        raw leaf postings — no device work, one
        ``subexpr_host_merges`` counter bump — and the root is stored so
        the next identical query is a plain root hit."""
        if plan.algorithm == "empty":
            return None
        hit = self.cache.get(plan)
        if hit is not None:
            doc_ids, algorithm = hit
            return QueryResult(doc_ids, 0.0, algorithm,
                               {"cached": True, "r": len(doc_ids)})
        if plan.expr is not None:
            doc_ids = self._resolve_expr_from_subcache(plan.expr)
            if doc_ids is not None:
                EXEC_COUNTERS["subexpr_host_merges"] += 1
                result = QueryResult(
                    doc_ids, 0.0, "expr/subcache",
                    {"cached": True, "r": len(doc_ids),
                     "subexpr_merge": True})
                self._store(plan, result)
                return result
        return None

    def _resolve_expr_from_subcache(self, e: Expr) -> Optional[np.ndarray]:
        """Try to answer a canonical expression from cached subexpression
        values + raw leaf postings, without touching the device.

        Probes every composite node once (memoized; probes count
        ``subexpr_cache_hits`` / ``_misses``).  If NO composite subtree is
        cached the query goes to the device untouched — recomputing the
        whole DAG in numpy here would just bypass the engine.  With at
        least one cached subtree, uncached nodes merge on the host
        (intersect1d/union1d/setdiff1d — the exact oracle semantics, so
        the merged result is bit-identical to a device execution)."""
        probes: Dict[Tuple, Optional[np.ndarray]] = {}

        def probe(node: Expr) -> Optional[np.ndarray]:
            key = expr_key(node)
            if key not in probes:
                probes[key] = self.cache.get_sub(key)
            return probes[key]

        def any_cached(node: Expr) -> bool:
            if isinstance(node, Term):
                return False
            if probe(node) is not None:
                return True
            if isinstance(node, Diff):
                return any_cached(node.left) or any_cached(node.right)
            return any(any_cached(c) for c in node.children)

        if not any_cached(e):
            return None
        memo: Dict[Tuple, np.ndarray] = {}

        def merge(node: Expr) -> np.ndarray:
            key = expr_key(node)
            if key in memo:
                return memo[key]
            if isinstance(node, Term):
                out = np.unique(
                    np.asarray(self.index[node.term].values, np.uint32))
            else:
                cached = probe(node)
                if cached is not None:
                    out = cached
                elif isinstance(node, And):
                    out = merge(node.children[0])
                    for c in node.children[1:]:
                        out = np.intersect1d(out, merge(c))
                elif isinstance(node, Or):
                    out = merge(node.children[0])
                    for c in node.children[1:]:
                        out = np.union1d(out, merge(c))
                else:
                    out = np.setdiff1d(merge(node.left), merge(node.right))
            out = out.astype(np.uint32)
            memo[key] = out
            return out

        return merge(e)

    def _execute_host_plan(self, plan: QueryPlan) -> QueryResult:
        """Run one non-device plan (``empty`` / ``hashbin`` / ``host``) to a
        QueryResult.  Per-query wall time lands in ``latency_us``; no
        EXEC_COUNTERS are touched (those count device work)."""
        if plan.algorithm == "empty":
            return QueryResult(np.empty(0, np.uint32), 0.0, "empty", {})
        if plan.expr is not None:
            t0 = time.perf_counter()
            res = eval_host(plan.expr, lambda t: self.index[t].values)
            dt = (time.perf_counter() - t0) * 1e6
            return QueryResult(res, dt, "expr/host", {"r": len(res)})
        idxs = [self.index[t] for t in plan.terms]
        t0 = time.perf_counter()
        if plan.algorithm == "hashbin":
            res, stats = hashbin(idxs[0], idxs[1])
            name = "hashbin"
        else:
            res, stats = rangroupscan(idxs)
            name = "rangroupscan"
        dt = (time.perf_counter() - t0) * 1e6
        return QueryResult(res, dt, name, stats.__dict__)

    def query(self, terms: Sequence[int]) -> QueryResult:
        """Serve one query — a batch of one through :meth:`query_batch`."""
        return self.query_batch([terms])[0]

    def query_batch(self, queries: Sequence[Sequence[int]]) -> List[QueryResult]:
        """Plan -> bucket -> execute -> scatter (request order preserved).

        Device-routed plans are grouped by shape signature and each bucket
        runs as ONE jit execution (plus rare overflow re-runs) — the number
        of device dispatches is O(#distinct signatures), not O(#queries);
        each bumps ``EXEC_COUNTERS["batch_calls"]``.  Host-routed plans
        (HashBin / no device) run per query.  When the result cache is
        enabled, hits (any path) are answered in place and misses are
        inserted after execution.
        """
        gen = self.cache.generation  # results compute against THIS index
        plans = [self.plan(q) for q in queries]
        results: List[Optional[QueryResult]] = [None] * len(queries)
        device_plans: List[Tuple[int, QueryPlan]] = []
        for i, plan in enumerate(plans):
            cached = self._cached_result(plan)
            if cached is not None:
                results[i] = cached
            elif plan.algorithm == "device":
                device_plans.append((i, plan))
            else:
                results[i] = self._execute_host_plan(plan)
                self._store(plan, results[i], generation=gen)
        if device_plans:
            by_index = execute_plan_buckets(
                lambda term: self.device.sets[str(term)],
                device_plans,
                use_pallas=self.device.use_pallas,
                mesh=self.device.mesh,
                shard_axis=self.device.shard_axis,
                get_sharded_set=lambda term: self.device.get_mesh_set(str(term)),
                capacity_model=self.capacity_model,
                topology=self.device.topology,
                get_replica_set=lambda r, term: self.device.get_replica_set(
                    r, str(term)),
                obs=self.obs,
            )
            for i, plan in device_plans:
                res, stats = by_index[i]
                results[i] = QueryResult(res, stats.get("batch_us", 0.0),
                                         _device_result_name(stats), stats)
                self._store(plan, results[i], generation=gen)
        return results  # type: ignore[return-value]

    def _store(self, plan: QueryPlan, result: QueryResult,
               generation: Optional[int] = None) -> None:
        """Cache a computed result.  ``generation`` is the cache generation
        captured before execution started — the cache rejects the entry if
        a mutation landed in between (see ``ResultCache.put``).

        Besides the root entry, every result also feeds the
        *subexpression* cache: device expression buckets ship their
        intermediate DAG-node values in ``stats["subexprs"]``; the root
        value itself is stored under its canonical expression key (for a
        flat conjunction, the key of the equivalent canonical ``And``), so
        a finished query — flat or expression — can later resolve as a
        shared subtree of a bigger expression without device work."""
        if plan.algorithm == "empty":
            return
        self.cache.put(plan, (result.doc_ids, result.algorithm),
                       generation=generation)
        if self.cache.capacity <= 0 or result.stats.get("cached"):
            return
        for key, value in result.stats.get("subexprs", ()):
            self.cache.put_sub(key, value, generation=generation)
        if plan.expr is not None:
            root_key = expr_key(plan.expr)
        else:
            root_key = expr_key(canonicalize(
                And(tuple(Term(t) for t in plan.terms)), self.index))
        self.cache.put_sub(root_key, result.doc_ids, generation=generation)


@dataclasses.dataclass
class _Flight:
    """One dispatched-but-uncollected bucket in the serving window.

    Carries everything collection needs once the exec lock is gone: the
    executor's :class:`~repro.exec.batch.InFlightBucket`, the live
    (ticket, plan) entries in bucket-row order, the flush timestamp
    (``wait_us`` is measured submit -> flush start, the quantity the
    deadline budget bounds), and the result-cache generation captured
    before dispatch (so results computed against a mutated index are
    rejected by the cache, exactly as on the synchronous path).
    """

    bucket: InFlightBucket
    entries: List[Tuple[Ticket, QueryPlan]]
    flush_at: float
    generation: int


class AsyncSearchEngine(SearchEngine):
    """Online front-end: single-query admission, deadline-bounded flushing.

    Callers :meth:`submit` one query at a time and get a
    :class:`~repro.serve.admission.Ticket` back immediately.  Device-routed
    plans accumulate in an :class:`~repro.serve.admission.AdmissionQueue`
    keyed by shape signature; a bucket executes when it fills the
    power-of-two ``flush_tier`` or when its oldest query's ``deadline_us``
    budget expires.  Host-routed and cache-hit queries resolve
    synchronously inside ``submit`` — they gain nothing from batching.

    Two flush drivers exist:

    - **Manual** (default): a caller-driven loop calls :meth:`pump` on a
      timer (or sleeps ``admission.next_deadline_in_us()``); full-tier
      buckets additionally flush inline at submit time.
    - **Background flusher** (:meth:`start` / :meth:`stop`): a daemonized
      thread owns the flush cadence — it sleeps exactly until the next
      deadline, is woken early by every device-routed submit, and pumps.
      With the flusher running, ``submit`` never executes device work
      itself (full tiers are flushed by the woken flusher via the
      ``next_deadline_in_us() == 0`` hint), so submission cadence is fully
      decoupled from flush cadence.  Each flusher wake-up bumps
      ``EXEC_COUNTERS["flusher_wakeups"]``.  The flusher sleeps in real
      time, so it assumes the engine ``clock`` is wall time.

    Overlapped dispatch: flushing is split into a *dispatch* phase (the
    bucket's jit call is issued without blocking —
    ``exec.batch.dispatch_bucket``) and a *collect* phase (the blocking
    transfer + overflow re-run + ticket resolution).  Dispatches happen
    back-to-back under the exec lock, so up to ``max_inflight`` buckets
    (default 8) are on the device simultaneously — on a multi-replica
    topology the balancer spreads them across rows, which is what turns
    replica rows into actually-concurrent servers; collection happens
    *outside*
    the lock, in dispatch order, resolving each bucket's tickets as its
    flight completes.  ``EXEC_COUNTERS["overlap_high_water"]`` records the
    achieved overlap.  With flights outstanding the flusher never sleeps
    its idle timer — it blocks on the oldest flight's completion (a
    collection event), re-checking the queue after every one.

    A serving loop looks like::

        eng = AsyncSearchEngine(postings, deadline_us=2000, warm_queries=log)
        with eng:                                     # start()s the flusher
            tickets = [eng.submit(q) for q in incoming]   # any thread(s)
            for t in tickets:
                t.wait()
        # stop() drained in-flight tickets on exit

    The result cache defaults ON here (1024 entries) — repeated
    conjunctions are the common case in live logs — and ``use_device``
    defaults True because micro-batching exists for the device path.

    Thread-safety: many threads may ``submit`` concurrently with the
    flusher (or manual ``pump`` / ``drain`` callers).  ``submit`` holds no
    engine-wide lock — planning is pure, the result cache and the
    admission queue are internally locked — so submitters never block
    behind a bucket execution.  All bucket *dispatch* serializes on one
    execution lock (it touches the engines' lazy mirror dicts); *collect*
    runs outside it.  The queue's atomic bucket pops guarantee each
    ticket is dispatched exactly once, and the flight list's atomic pops
    guarantee each dispatched bucket is collected exactly once — which
    makes ``drain`` idempotent and safe to call while the flusher runs
    (it collects queued flights itself and waits out flights another
    thread holds mid-collect).  The inherited synchronous paths
    (``query`` / ``query_batch`` / ``warm``) are still single-caller:
    don't interleave them with concurrent submits on the same engine
    (except ``_flush``'s own stale-plan fallback, which serializes under
    the execution lock).

    Adaptive serving: ``adaptive_capacity=True`` (inherited) learns
    survivor-sized capacity tiers; ``adaptive_deadline=True`` shrinks
    per-signature flush budgets when the observed arrival rate cannot fill
    a bucket within the default budget (see ``exec/adaptive.py``).  An
    explicit per-query ``deadline_us`` always wins over the adaptive
    budget.
    """

    def __init__(self, postings: Dict[int, np.ndarray],
                 deadline_us: float = 2000.0, flush_tier: int = 64,
                 result_cache: int = 1024,
                 clock: Callable[[], float] = time.perf_counter,
                 warm_queries: Optional[Sequence[Sequence[int]]] = None,
                 warm_top_k: int = 8,
                 warm_b_tiers: Optional[Sequence[int]] = None,
                 adaptive_deadline=False,
                 max_inflight: int = 8,
                 inline_tier_flush: bool = True,
                 snapshot_every_s: float = 1.0,
                 **kw):
        kw.setdefault("use_device", True)
        super().__init__(postings, result_cache=result_cache, **kw)
        self.clock = clock
        # flusher-driven metric snapshots: every ``snapshot_every_s`` of
        # flusher activity, one consistent registry cut lands in
        # ``self.obs.ring`` (post-mortem surface).  0 disables.
        self.snapshot_every_s = float(snapshot_every_s)
        self._last_snapshot_at = 0.0
        # manual mode only: with the flusher stopped, submit flushes full
        # tiers inline (the historical behavior).  A deterministic driver
        # that emulates the flusher itself (serve/loadgen.py's virtual-time
        # mode) sets this False so submit ONLY queues — flush timing then
        # has a single owner and queue waits follow the server model, not
        # the submitter's call stack.
        self.inline_tier_flush = bool(inline_tier_flush)
        self.admission = AdmissionQueue(flush_tier=flush_tier,
                                        deadline_us=deadline_us, clock=clock)
        # one lock serializes all bucket DISPATCH (_flush callers); submit
        # deliberately does not take it, and collection happens outside it
        # — see the class docstring
        self._exec_lock = threading.RLock()
        # dispatched-but-uncollected buckets: the overlap window.  Guarded
        # by _flight_cv (never nested inside _exec_lock acquisition order
        # violations: _exec_lock may be held when taking _flight_cv, never
        # the reverse).  _collecting counts flights popped by some thread
        # whose collect has not finished — drain must wait those out too.
        assert max_inflight >= 1
        self.max_inflight = int(max_inflight)
        self._flight_cv = threading.Condition()
        self._flights: List[_Flight] = []
        self._collecting = 0
        if isinstance(adaptive_deadline, AdaptiveDeadline):
            self.adaptive_deadline: Optional[AdaptiveDeadline] = adaptive_deadline
        else:
            self.adaptive_deadline = (AdaptiveDeadline() if adaptive_deadline
                                      else None)
        self._wake = threading.Event()
        self._stop_flusher = threading.Event()
        self._flusher: Optional[threading.Thread] = None
        self._flusher_lock = threading.Lock()  # start/stop transitions only
        self._flusher_idle_s = 0.05  # re-check cadence when queue is empty
        self._flusher_error: Optional[BaseException] = None
        if warm_queries is not None:
            # default tiers cover every partial-flush size up to flush_tier
            # — otherwise a live micro-batch of 2..flush_tier queries would
            # pad to an unwarmed executable and compile at serve time
            if warm_b_tiers is None:
                warm_b_tiers = pow2_tiers(flush_tier)
            self.warm(warm_queries, top_k=warm_top_k, b_tiers=warm_b_tiers)

    # ------------------------------------------------------------------
    # background flusher lifecycle
    # ------------------------------------------------------------------

    def start(self) -> "AsyncSearchEngine":
        """Start the background flusher thread (idempotent).

        The thread sleeps until the next admission deadline
        (``next_deadline_in_us``), wakes early on every device-routed
        submit, and pumps.  Daemonized, so a forgotten engine never blocks
        interpreter exit — but call :meth:`stop` for a clean shutdown that
        drains in-flight tickets.  Returns ``self`` (context-manager
        friendly).
        """
        with self._flusher_lock:
            if self._flusher is not None and self._flusher.is_alive():
                return self
            self._stop_flusher.clear()
            self._flusher = threading.Thread(
                target=self._flusher_loop, name="repro-flusher", daemon=True)
            self._flusher.start()
        return self

    def stop(self, drain: bool = True) -> None:
        """Stop the background flusher (idempotent) and, by default, drain.

        Joins the thread first, then flushes every still-pending bucket so
        no in-flight ticket is left unresolved — the clean-shutdown
        contract.  ``drain=False`` skips the final flush (tickets stay
        pending for a later ``drain`` or ``start``).  A ``submit`` racing
        this call lands in manual-mode behavior (full tiers flush inline);
        the re-drain below catches its partial bucket in all but a vanishing
        window — callers who keep submitting after ``stop`` own the
        leftover queue, exactly as on a never-pumped manual engine.
        """
        with self._flusher_lock:
            thread = self._flusher
            self._flusher = None
            if thread is not None:
                self._stop_flusher.set()
                self._wake.set()
                thread.join()
                self._wake.clear()
        if drain:
            self.drain()
            if self.pending():
                self.drain()  # a submit raced the join; its bucket is here
        error, self._flusher_error = self._flusher_error, None
        if error is not None:
            raise RuntimeError(
                "background flusher hit a non-bucket error "
                "(tickets were still drained)") from error

    @property
    def running(self) -> bool:
        """True while the background flusher thread is alive."""
        thread = self._flusher
        return thread is not None and thread.is_alive()

    def __enter__(self) -> "AsyncSearchEngine":
        return self.start()

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.stop()
        return False

    def _flusher_loop(self) -> None:
        """Flusher thread body: overlapped dispatch/collect scheduling.

        Each iteration (1) dispatches every due bucket back-to-back under
        the exec lock (window-bounded — the balancer routes them to
        different replica rows since in-flight load is now visible), (2)
        collects already-completed flights without blocking, then (3)
        picks its wait: with flights outstanding it blocks on the *oldest
        flight's collection* — a real completion event, never the flat
        idle sleep (a bucket in flight used to wait up to
        ``_flusher_idle_s`` for its results); with an empty window it
        sleeps exactly until the next admission deadline (or the idle
        re-check when the queue is empty), cut short by ``submit``'s wake
        event.

        Tracing on, every stretch of the loop lies under a span: ``wait``
        (asleep with an empty window), ``take``, ``snapshot``, and the
        ``replan`` / ``bucket`` / ``collect`` spans of the buckets it
        dispatches and collects."""
        tracer = self.obs.tracer
        while True:
            next_us = self.admission.next_deadline_in_us()
            if self._inflight_count() == 0:
                timeout = (self._flusher_idle_s if next_us is None
                           else max(0.0, next_us * 1e-6))
                if timeout > 0:
                    if tracer.enabled:
                        with tracer.start(
                                "wait", queued=self.admission.pending(),
                                timeout_ms=round(timeout * 1e3, 3)):
                            self._wake.wait(timeout)
                    else:
                        self._wake.wait(timeout)
            if self._stop_flusher.is_set():
                # collect whatever is still in flight before exiting so
                # stop()'s drain only deals with the queue, not the window
                while self._collect_one():
                    pass
                return
            self._wake.clear()
            EXEC_COUNTERS["flusher_wakeups"] += 1
            if self.snapshot_every_s > 0:
                now_mono = time.monotonic()
                if now_mono - self._last_snapshot_at >= self.snapshot_every_s:
                    self._last_snapshot_at = now_mono
                    span = tracer.start("snapshot") if tracer.enabled else None
                    self.obs.ring.push(now_mono, self.obs.registry.snapshot())
                    if span is not None:
                        span.end()
            try:
                self._flush(self._take_due())
                # reap everything already finished on the device...
                while self._collect_one(ready_only=True):
                    pass
                # ...then wait on the oldest flight's completion (unless a
                # fresh submit already wants another dispatch pass)
                if not self._wake.is_set():
                    self._collect_one()
            except Exception as exc:  # keep the runtime alive: bucket-level
                # failures already resolve their tickets with the error
                # inside _flush/_collect; anything escaping here is a bug we
                # surface on the next stop() instead of dying silently
                self._flusher_error = exc

    # ------------------------------------------------------------------
    # admission API
    # ------------------------------------------------------------------

    def submit(self, terms: Sequence[int],
               deadline_us: Optional[float] = None,
               arrival_at: Optional[float] = None) -> Ticket:
        """Admit one query; returns a Ticket resolving to a QueryResult.

        Resolution timing by path: ``empty`` / host-routed / result-cache
        hit — already resolved on return (``wait_us`` 0); device-routed —
        resolved when its signature bucket flushes (full tier, deadline,
        or a ``drain``).  With the background flusher running, submit only
        queues and wakes the flusher — all device execution happens on the
        flusher thread.  ``wait_us`` on the ticket is the queue wait the
        deadline budget bounds.

        ``arrival_at`` (engine-clock seconds) back-stamps the ticket with
        the query's *scheduled* arrival time: an open-loop load generator
        passes it so a submitter thread that got scheduled late still
        charges the lateness to the measured wait (and to the deadline
        budget) instead of silently forgiving it — the coordinated-
        omission correction.  Applies to every path, including
        resolved-at-submit ones.

        Tracing (when ``self.obs.tracer`` is enabled): each submit opens
        one ``request`` root span with a ``plan`` child; the root carries
        the resolved ``route`` (``cache`` / ``subcache`` / ``host`` /
        ``device`` + sig label) and is closed exactly once at ticket
        resolution, whichever path resolves it.  With ``arrival_at`` in the
        past, the root starts at the arrival and a ``late`` child covers
        arrival -> entry to ``submit``.
        """
        span = (self._request_span(arrival_at)
                if self.obs.tracer.enabled else None)
        try:
            if span is not None:
                with span.child("plan"):
                    plan = self.plan(terms)
            else:
                plan = self.plan(terms)
            cached = self._cached_result(plan)
            if cached is not None:
                if span is not None:
                    span.set(route=("subcache" if cached.stats.get(
                        "subexpr_merge") else "cache"))
                return self._resolved_now(cached, arrival_at=arrival_at,
                                          span=span)
            if plan.algorithm != "device":
                if span is not None:
                    span.set(route="host", algorithm=plan.algorithm)
                gen = self.cache.generation
                result = self._execute_host_plan(plan)
                self._store(plan, result, generation=gen)
                return self._resolved_now(result, arrival_at=arrival_at,
                                          span=span)
        except BaseException:
            if span is not None:
                span.end(error=True)
            raise
        if span is not None:
            span.set(route="device", sig=sig_label(plan.sig))
        if self.adaptive_deadline is not None:
            key = adaptive_key(plan.sig)
            self.adaptive_deadline.observe(key, self.clock())
            if deadline_us is None:
                deadline_us = self.adaptive_deadline.budget_for(
                    key, self.admission.deadline_us)
        ticket = self.admission.submit(plan.sig, plan, deadline_us,
                                       submitted_at=arrival_at,
                                       span=span, obs=self.obs)
        if self.running:
            # the queue reports 0 for full tiers, so waking the flusher
            # covers both the tier-flush and the recompute-sleep cases
            self._wake.set()
            if self.running:
                return ticket
            # the flusher stopped between the enqueue and the wake: fall
            # through to manual-mode behavior so a full tier still flushes
            # (stop() re-drains to catch the remaining partial-bucket case)
        if self.inline_tier_flush:
            self._flush(self.admission.take_full())
            self._collect_all()
        return ticket

    def _request_span(self, arrival_at: Optional[float]):
        """Open a request's root span (tracing on), backdated to a past
        ``arrival_at`` with a ``late`` child up to now when the engine's
        clock is the tracer's."""
        tracer = self.obs.tracer
        if arrival_at is None or tracer.clock is not self.clock:
            return tracer.start("request")
        now_us = tracer.clock() * 1e6
        at_us = float(arrival_at) * 1e6
        if at_us >= now_us:
            return tracer.start("request")
        span = tracer.start("request", start_us=at_us)
        tracer.span_at("late", at_us, now_us, parent=span)
        return span

    def pump(self) -> int:
        """Flush buckets whose deadline budget has expired (and any that
        filled their tier since the last call).  Returns #buckets flushed.
        Dispatches all due buckets back-to-back (window-bounded), then
        collects every outstanding flight before returning — externally
        synchronous, overlapped inside.  Manual loops call it on a timer —
        the deadline guarantee is only as fine-grained as the pump
        cadence."""
        count = self._flush(self._take_due())
        self._collect_all()
        return count

    def drain(self) -> int:
        """Flush every pending bucket now (shutdown / end-of-batch / test
        path).  Returns #buckets flushed; afterwards every ticket issued
        *before* the call is resolved.  Idempotent and safe to call while
        the background flusher runs: bucket pops are atomic, so a bucket
        the flusher already took is simply not taken again; this call then
        collects every outstanding flight itself and waits out any flight
        another thread is mid-collecting (whose tickets therefore also
        resolve before drain returns)."""
        count = self._flush(self.admission.take_all())
        self._collect_all()
        self._wait_flights()
        return count

    def pending(self) -> int:
        """Queued-but-unflushed submission count (device path only)."""
        return self.admission.pending()

    def _resolved_now(self, result: QueryResult,
                      arrival_at: Optional[float] = None,
                      span=None) -> Ticket:
        """Pre-resolved ticket for paths answered inside ``submit``.

        With an ``arrival_at`` back-stamp the wait is the submitter's
        lateness (scheduled arrival -> now), not zero — a cache hit the
        runtime got to 3 ms late still waited 3 ms from the caller's side.
        The request's root ``span`` (if tracing) is stamped before
        resolution so it closes through the same single-shot
        ``_record_wait`` path as queued tickets.
        """
        now = self.clock()
        arrival = now if arrival_at is None else min(float(arrival_at), now)
        ticket = Ticket(submitted_at=arrival, deadline_us=0.0,
                        clock=self.clock)
        ticket.span = span
        ticket.obs = self.obs
        ticket.resolve(result, wait_us=(now - arrival) * 1e6)
        return ticket

    def _take_due(self):
        """``admission.take_due()``, under a ``take`` span when tracing."""
        tracer = self.obs.tracer
        if not tracer.enabled:
            return self.admission.take_due()
        span = tracer.start("take")
        buckets = self.admission.take_due()
        span.end(buckets=len(buckets),
                 queries=sum(len(entries) for _, entries in buckets))
        return buckets

    def _flush(self, buckets) -> int:
        """Dispatch flushed buckets into the in-flight window; returns
        #buckets processed.  Takes ``_exec_lock`` itself (re-entrant, so
        exec-lock-holding callers compose).

        The overlapped rewrite of the old execute-in-place flush: buckets
        are *dispatched* back-to-back under the exec lock (one non-blocking
        jit issue each — independent signatures land on different replica
        rows because the balancer sees in-flight load) and *collected*
        outside it, by whoever pops the flight (:meth:`_collect_one`).
        When the window is full this thread collects the oldest flight
        itself to free a slot — natural backpressure.  After the last
        dispatch the queue is re-polled for newly-due buckets, so a
        deadline expiring while earlier buckets dispatch is picked up
        without waiting for the next pump.  Tickets of a bucket whose
        dispatch raises resolve with the error (``ticket.value``
        re-raises; nobody hangs on ``done``) and the remaining buckets
        still flush.
        """
        count = 0
        pending = list(buckets)
        while pending:
            with self._exec_lock:
                while pending and self._inflight_count() < self.max_inflight:
                    sig, entries = pending.pop(0)
                    self._dispatch_one(sig, entries)
                    count += 1
                    if not pending:
                        pending.extend(self._take_due())
            if pending and not self._collect_one():
                # window full but no flight to pop: other threads are
                # mid-collect — wait for one to finish and free a slot
                with self._flight_cv:
                    if not self._flights and self._collecting:
                        self._flight_cv.wait(0.01)
        return count

    def _dispatch_one(self, sig, entries) -> None:
        """Dispatch one admission bucket (caller holds ``_exec_lock`` —
        dispatch resolves lazy per-replica mirrors on the engine).

        An index mutation between submit and flush can re-tier a queued
        term, so the entry's frozen sig no longer matches the arrays
        resolved NOW — executing it would trip the bucket's signature-
        uniformity assert and fail every ticket.  Each plan is
        re-validated against the current index; stale entries run through
        the synchronous path (which re-plans) and resolve immediately.
        ``wait_us`` is measured submit -> dispatch, the quantity
        ``deadline_us`` bounds.
        """
        flush_at = self.clock()
        span = (self.obs.tracer.start("replan", queries=len(entries))
                if self.obs.tracer.enabled else None)
        for ticket, _ in entries:
            # queue wait is over the moment the flush picks the bucket up
            if ticket.admission_span is not None:
                ticket.admission_span.end()
        live = []
        for ticket, plan in entries:
            # re-plan via the original spec (flat term list OR canonical
            # expression) — an expression plan's terms tuple alone would
            # re-plan as a flat conjunction and always look stale
            if self.plan(plan.query_spec()).sig == sig:
                live.append((ticket, plan))
                continue
            wait_us = (flush_at - ticket.submitted_at) * 1e6
            try:
                result = self.query(plan.query_spec())
            except Exception as exc:
                ticket.resolve_error(exc, wait_us=wait_us)
            else:
                ticket.resolve(result, wait_us=wait_us)
        if span is not None:
            span.end(stale=len(entries) - len(live))
        if not live:
            return
        items = [(row, plan) for row, (_, plan) in enumerate(live)]
        gen = self.cache.generation  # capture before executing
        try:
            bucket = dispatch_bucket(
                lambda term: self.device.sets[str(term)], sig, items,
                use_pallas=self.device.use_pallas,
                mesh=self.device.mesh,
                shard_axis=self.device.shard_axis,
                get_sharded_set=lambda term: self.device.get_mesh_set(str(term)),
                capacity_model=self.capacity_model,
                topology=self.device.topology,
                get_replica_set=lambda r, term: self.device.get_replica_set(
                    r, str(term)),
                obs=self.obs,
            )
        except Exception as exc:
            for ticket, _ in live:
                ticket.resolve_error(
                    exc, wait_us=(flush_at - ticket.submitted_at) * 1e6)
            return
        if bucket.span is not None:
            # cross-link the bucket span and its member request traces so
            # trace_dump shows which requests shared a flight
            bucket.span.set(traces=[t.span.trace_id for t, _ in live
                                    if t.span is not None])
            for ticket, _ in live:
                if ticket.span is not None:
                    ticket.span.set(bucket_span=bucket.span.span_id,
                                    replica=bucket.replica)
        with self._flight_cv:
            self._flights.append(_Flight(bucket, live, flush_at, gen))
            self._flight_cv.notify_all()

    # ------------------------------------------------------------------
    # collection (outside the exec lock)
    # ------------------------------------------------------------------

    def _inflight_count(self) -> int:
        """Dispatched-but-unresolved buckets: queued flights plus flights
        some thread is currently collecting (both occupy window slots)."""
        with self._flight_cv:
            return len(self._flights) + self._collecting

    def _collect_one(self, ready_only: bool = False) -> bool:
        """Pop and collect the oldest flight; resolve its tickets.

        Returns False when there is nothing to pop (or, with
        ``ready_only``, when the oldest flight's device buffers have not
        materialized yet — the non-blocking reap the flusher uses between
        dispatch passes).  Runs WITHOUT the exec lock: this is the
        collect-outside-the-lock half of the pipeline, so new dispatches
        (and submits) proceed while we block on the transfer.  Pops are
        atomic under the flight condition — a flight is collected exactly
        once no matter how flusher / drain / manual pumps interleave.
        """
        with self._flight_cv:
            if not self._flights:
                return False
            if ready_only and not self._flights[0].bucket.is_ready():
                return False
            flight = self._flights.pop(0)
            self._collecting += 1
        try:
            self._resolve_flight(flight)
        finally:
            with self._flight_cv:
                self._collecting -= 1
                self._flight_cv.notify_all()
        return True

    def _collect_all(self) -> None:
        """Collect every queued flight (blocking each in dispatch order)."""
        while self._collect_one():
            pass

    def _wait_flights(self) -> None:
        """Block until the window is empty — collecting queued flights
        ourselves and waiting out flights other threads are mid-collecting
        (drain's resolution guarantee)."""
        while True:
            if self._collect_one():
                continue
            with self._flight_cv:
                if not self._flights and not self._collecting:
                    return
                # a racing thread holds a flight mid-collect (or just
                # appended one): its finally-notify re-checks us
                self._flight_cv.wait()

    def _resolve_flight(self, flight: _Flight) -> None:
        """Collect one flight's results and resolve its tickets (cache
        store under the dispatch-time generation, error fan-out on a
        failed collect)."""
        flight.bucket.hold_spans = True
        try:
            by_row = flight.bucket.collect()
        except Exception as exc:
            for ticket, _ in flight.entries:
                ticket.resolve_error(
                    exc,
                    wait_us=(flight.flush_at - ticket.submitted_at) * 1e6)
            return
        try:
            for row, (ticket, plan) in enumerate(flight.entries):
                res, stats = by_row[row]
                result = QueryResult(res, stats.get("batch_us", 0.0),
                                     _device_result_name(stats), stats)
                self._store(plan, result, generation=flight.generation)
                wait_us = (flight.flush_at - ticket.submitted_at) * 1e6
                ticket.resolve(result, wait_us=wait_us)
        finally:
            # the bucket's scatter span covers the cache stores and the
            # ticket resolutions above
            flight.bucket.end_spans()


@dataclasses.dataclass
class SuggestResult:
    """One served suggestion query.

    ``suggestions`` is the top-K list of ``(set_id, |probe ∩ candidate|)``
    pairs, best-first under the deterministic ``(-count, smallest id)``
    order; zero-overlap candidates never appear.  ``algorithm`` names the
    executed path (``"suggest/device"``, ``"suggest/sharded"``,
    ``"suggest/mesh2d"``, ``"suggest/host"``); cache hits carry
    ``{"cached": True}`` in ``stats``.
    """

    suggestions: List[Tuple[int, int]]
    latency_us: float
    algorithm: str
    stats: Dict


@dataclasses.dataclass(frozen=True)
class _SuggestCacheKey:
    """Result-cache key shim for a whole suggest request.

    The per-class device plans already key apart via
    ``QueryPlan.cache_key()``'s ``"suggest"`` arm; the *merged* final
    answer is what repeats in live traffic, so the engine caches it under
    the request itself.  Duck-types the one method ``ResultCache`` calls.
    """

    set_id: int
    k: int

    def cache_key(self):
        return ("suggest_result", (self.set_id, self.k))


class SuggestEngine:
    """Top-K set-similarity suggestions over a corpus of sets.

    ``suggest(set_id, k)`` returns the ``k`` corpus sets with the largest
    intersection against the probe set, exact and deterministically
    tie-broken (equal counts prefer the smaller set id).  The serving
    pipeline is the point-query substrate with a count-only execution
    path:

    1. **Pre-filter** (host): the probe's hash-bin occupancy signature is
       ANDed against every corpus signature
       (:class:`~repro.exec.candidates.CandidateIndex`); at the default
       ``min_shared_bins=1`` no true-overlap candidate is ever dropped,
       so the device pass stays exact.
    2. **Plan**: surviving candidates group into ``(t, gmax_tier)`` shape
       classes — one :func:`~repro.exec.plan.plan_suggest` plan per class
       (bucket stacking needs static shapes).  Plans carry
       ``ShapeSig.cands`` (> 0) and route plain / z-sharded / 2-D exactly
       like point queries.
    3. **Execute**: buckets run through
       :func:`~repro.exec.batch.execute_plan_buckets` into the count-only
       jits (``core.engine.intersect_count_batch`` and twins) — no
       survivor buffers, no overflow re-run, device-side ``lax.top_k``.
    4. **Merge** (host): per-class top lists merge by ``(-count, id)``
       and truncate to ``k`` — exact, because every class returns at
       least its own top ``min(k_tier, c_tier) >= min(k, |class|)``.

    The result cache stores *merged* answers per ``(set_id, k)`` and is
    generation-stamped off the device engine's mutation hook, so
    :meth:`add_set` can never serve stale suggestions.  :meth:`warm`
    pre-traces the count executables (signature tiers + batch tiers) so
    warmed serving pays zero traces (``EXEC_COUNTERS["count_traces"]``).
    """

    def __init__(self, corpus: Dict[int, np.ndarray], w: int = 256,
                 m: int = 2, seed: int = 0, use_device: bool = True,
                 result_cache: int = 1024, mesh=None,
                 shard_min_g: int = SHARD_MIN_G, topology=None,
                 min_shared_bins: int = 1,
                 max_candidates: Optional[int] = None, obs=None):
        self.obs = obs if obs is not None else get_obs()
        self.family = random_hash_family(m, w, seed=seed)
        self.perm = default_permutation(seed)
        self.w, self.m = w, m
        self.min_shared_bins = int(min_shared_bins)
        self.max_candidates = max_candidates
        self.use_device = (use_device or mesh is not None
                           or topology is not None)
        self.corpus: Dict[int, np.ndarray] = {}
        self.index: Dict[int, object] = {}
        self.prefilter = CandidateIndex(self.family)
        self.device = (BatchedEngine(use_pallas="auto", mesh=mesh,
                                     shard_min_g=shard_min_g,
                                     topology=topology)
                       if self.use_device else None)
        self.cache = ResultCache(result_cache)
        if self.device:
            self.device.on_mutate(self.cache.bump_generation)
        t0 = time.perf_counter()
        for set_id, values in corpus.items():
            if len(values):
                self.add_set(set_id, values)
        self.build_s = time.perf_counter() - t0
        self.warmed_sigs: List[ShapeSig] = []

    def add_set(self, set_id: int, values: np.ndarray) -> None:
        """Add or replace one corpus set (streaming-ingest entry point).

        Re-runs preprocessing, refreshes the device mirrors and the
        pre-filter signature, and — via the engine's mutation hook — bumps
        the result-cache generation so previously cached suggestions
        (whose candidate pool or counts may have changed) are stale.
        """
        values = np.unique(np.asarray(values, np.uint32))
        idx = preprocess_prefix(values, w=self.w, m=self.m,
                                family=self.family, perm=self.perm)
        self.corpus[set_id] = values
        self.index[set_id] = idx
        self.prefilter.add(set_id, values)
        if self.device:
            self.device.add(str(set_id), idx)  # fires the cache hook
        else:
            self.cache.bump_generation()

    def _classes(self, candidates: Sequence[int]) -> Dict[Tuple, List[int]]:
        """Split prefiltered candidates into ``(t, gmax_tier)`` shape
        classes (deterministic order: sorted class key, ascending ids in
        each class — the tie-break contract feeds off the id order)."""
        from ..core.engine import gmax_tier

        classes: Dict[Tuple, List[int]] = {}
        for c in candidates:
            idx = self.index[c]
            classes.setdefault((idx.t, gmax_tier(idx.gmax)), []).append(c)
        return {key: sorted(classes[key]) for key in sorted(classes)}

    def _plans_for(self, set_id: int, k: int) -> List[QueryPlan]:
        """Pre-filter + per-class planning for one suggest request."""
        cands = self.prefilter.candidates(
            self.corpus[set_id], exclude=set_id,
            min_shared_bins=self.min_shared_bins,
            max_candidates=self.max_candidates)
        return [
            plan_suggest(
                self.index, set_id, class_cands, k,
                device=self.device is not None,
                mesh_shards=self.device.n_shards if self.device else 1,
                mesh_replicas=self.device.n_replicas if self.device else 1,
                shard_min_g=(self.device.shard_min_g if self.device
                             else SHARD_MIN_G),
            )
            for class_cands in self._classes(cands).values()
        ]

    @staticmethod
    def _merge(per_class: List[List[Tuple[int, int]]], k: int
               ) -> List[Tuple[int, int]]:
        """Merge per-class top lists into the global top-k: order by
        ``(-count, id)`` — the same key the device tie-break realizes —
        and truncate."""
        merged = [pair for pairs in per_class for pair in pairs]
        merged.sort(key=lambda pair: (-pair[1], pair[0]))
        return merged[:k]

    def _host_counts(self, set_id: int, plan: QueryPlan
                     ) -> List[Tuple[int, int]]:
        """Host oracle path for one class plan: exact numpy counts."""
        probe = self.corpus[set_id]
        out = []
        for c in plan.terms[1:]:
            n = len(np.intersect1d(probe, self.corpus[c]))
            if n >= 1:
                out.append((c, n))
        return out

    def _execute_flat(self, flat: List[Tuple[int, QueryPlan]]
                      ) -> Dict[int, Tuple[np.ndarray, Dict]]:
        """Run the flattened device-routed class plans for one batch."""
        return execute_plan_buckets(
            lambda sid: self.device.sets[str(sid)],
            flat,
            use_pallas=self.device.use_pallas,
            mesh=self.device.mesh,
            shard_axis=self.device.shard_axis,
            get_sharded_set=lambda sid: self.device.get_mesh_set(
                str(sid)),
            topology=self.device.topology,
            get_replica_set=lambda r, sid: self.device.get_replica_set(
                r, str(sid)),
            obs=self.obs,
        )

    def suggest(self, set_id: int, k: int) -> SuggestResult:
        """Serve one suggestion query — a batch of one."""
        return self.suggest_batch([(set_id, k)])[0]

    def suggest_batch(self, requests: Sequence[Tuple[int, int]]
                      ) -> List[SuggestResult]:
        """Plan -> bucket -> execute -> merge for a request batch.

        Class plans from ALL requests bucket together (same-signature
        classes of different probes share one jit execution), so device
        dispatches stay O(#distinct signatures).  Unknown ``set_id``
        raises KeyError — suggestions are corpus-internal.
        """
        for set_id, _ in requests:
            if set_id not in self.corpus:
                raise KeyError(set_id)
        gen = self.cache.generation
        tracing = self.obs.tracer.enabled
        spans = [self.obs.tracer.start("request", kind="suggest",
                                       set_id=set_id, k=int(k))
                 if tracing else None
                 for set_id, k in requests]
        results: List[Optional[SuggestResult]] = [None] * len(requests)
        req_plans: Dict[int, List[Tuple[int, QueryPlan]]] = {}
        flat: List[Tuple[int, QueryPlan]] = []
        for ri, (set_id, k) in enumerate(requests):
            hit = self.cache.get(_SuggestCacheKey(set_id, int(k)))
            if hit is not None:
                suggestions, algorithm = hit
                results[ri] = SuggestResult(
                    suggestions, 0.0, algorithm,
                    {"cached": True, "k": int(k)})
                if spans[ri] is not None:
                    spans[ri].end(route="cache")
                continue
            plans = []
            if spans[ri] is not None:
                with spans[ri].child("plan"):
                    req_class_plans = self._plans_for(set_id, int(k))
            else:
                req_class_plans = self._plans_for(set_id, int(k))
            for plan in req_class_plans:
                if plan.algorithm == "device":
                    plans.append((len(flat), plan))
                    flat.append((len(flat), plan))
                else:
                    plans.append((-1, plan))
            req_plans[ri] = plans
        by_index: Dict[int, Tuple[np.ndarray, Dict]] = {}
        try:
            by_index = self._execute_flat(flat) if flat else {}
        except BaseException:
            # Close every still-open request span (cache hits already
            # ended; Span.end is idempotent) so a failed device batch
            # can't leak open spans.
            for s in spans:
                if s is not None:
                    s.end(error=True)
            raise
        for ri, (set_id, k) in enumerate(requests):
            if results[ri] is not None:
                continue
            per_class: List[List[Tuple[int, int]]] = []
            algorithm = "suggest/host"
            stats: Dict = {"k": int(k), "classes": len(req_plans[ri])}
            batch_us = 0.0
            for fi, plan in req_plans[ri]:
                if plan.algorithm == "empty":
                    continue
                if fi < 0:
                    per_class.append(self._host_counts(set_id, plan))
                    continue
                pairs, cstats = by_index[fi]
                cands = plan.terms[1:]
                per_class.append([
                    (cands[int(idx)], int(count))
                    for idx, count in pairs if count >= 1
                ])
                algorithm = "suggest" + _device_result_name(
                    cstats).removeprefix("rangroupscan")
                batch_us += cstats.get("batch_us", 0.0)
                stats["n_cands"] = stats.get(
                    "n_cands", 0) + cstats.get("n_cands", 0)
            suggestions = self._merge(per_class, int(k))
            stats["r"] = len(suggestions)
            results[ri] = SuggestResult(
                suggestions, batch_us, algorithm, stats)
            if spans[ri] is not None:
                spans[ri].end(route="device" if any(
                    fi >= 0 for fi, _ in req_plans[ri]) else "host",
                    algorithm=algorithm, r=len(suggestions))
            self.cache.put(_SuggestCacheKey(set_id, int(k)),
                           (suggestions, algorithm), generation=gen)
        return results  # type: ignore[return-value]

    def warm(self, sample_ids: Sequence[int], k: int,
             b_tiers: Sequence[int] = (1,)) -> List[ShapeSig]:
        """Pre-trace the count executables a sample of probes would hit.

        Plans each sample id exactly as :meth:`suggest` will (pre-filter
        included, so the candidate-axis tiers match live traffic) and
        warms every device-routed signature through
        ``core.engine.warm_from_plans`` — plain, z-sharded, 2-D, and
        per-replica-row variants included.  After warming, serving the
        same signatures executes with zero fresh traces
        (``EXEC_COUNTERS["count_traces"]`` stays flat).
        """
        assert self.device is not None, "warming is a device-path concept"
        plans = [p for sid in sample_ids for p in self._plans_for(sid, k)]
        self.warmed_sigs = warm_from_plans(
            plans, lambda sid: self.device.sets[str(sid)],
            top_k=len(plans) or 1, b_tiers=b_tiers,
            use_pallas=self.device.use_pallas,
            mesh=self.device.mesh, axis=self.device.shard_axis,
            get_sharded_set=lambda sid: self.device.get_mesh_set(str(sid)),
            topology=self.device.topology,
            get_replica_set=lambda r, sid: self.device.get_replica_set(
                r, str(sid)))
        return self.warmed_sigs


def zipf_query_log(index_terms: Sequence[int], n_queries: int = 1000,
                   seed: int = 1, kw_dist=((2, 0.68), (3, 0.23), (4, 0.09))
                   ) -> List[List[int]]:
    """Synthetic query log with the paper's keyword-count distribution
    (68% 2-word, 23% 3-word, ...) and Zipf-skewed term popularity."""
    rng = np.random.default_rng(seed)
    terms = np.asarray(sorted(index_terms))
    ks, ps = zip(*kw_dist)
    out = []
    for _ in range(n_queries):
        k = rng.choice(ks, p=np.asarray(ps) / sum(ps))
        # skewed term choice: favor low term-ids (frequent under Zipf corpus)
        idx = np.minimum(len(terms) - 1,
                         (rng.pareto(1.0, size=k) * 10).astype(int))
        out.append(sorted(set(terms[idx].tolist())) or [int(terms[0])])
    return out


def repeated_query_log(index_terms: Sequence[int], n_queries: int = 1000,
                       n_distinct: int = 64, seed: int = 1) -> List[List[int]]:
    """A live-traffic-shaped log: ``n_queries`` drawn Zipf-style from a pool
    of ``n_distinct`` conjunctions, so exact repeats occur (the regime where
    the result cache pays).  The pool itself follows the paper's
    keyword-count mix via :func:`zipf_query_log`."""
    pool = zipf_query_log(index_terms, n_distinct, seed=seed)
    rng = np.random.default_rng(seed + 1)
    ranks = np.arange(1, len(pool) + 1, dtype=np.float64)
    p = (1.0 / ranks) / (1.0 / ranks).sum()
    return [pool[i] for i in rng.choice(len(pool), size=n_queries, p=p)]
