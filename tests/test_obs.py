"""Observability layer tests: typed registry, tracer, exporters, profile
store, and their wiring through the serving stack.

Satellite coverage (ISSUE 10):

1. ``EXEC_COUNTERS`` snapshot tearing — threads hammering ``bump_many``
   while a reader snapshots must never observe a torn multi-key update.
2. Balancer failure telemetry — a mid-collect flight failure returns the
   row's in-flight weight, records a per-row failure, and bumps the
   ``dispatch_failures`` counter (typed and legacy) exactly once.
3. Span lifecycle invariants — exactly one closed ``request`` root span
   per ticket (cache-hit, device, and error paths), genuinely overlapping
   bucket spans under the overlapped window, zero spans in disabled mode.
"""
import threading
import time

import numpy as np
import pytest
import jax

from repro.core.engine import EXEC_COUNTERS, PendingBatch
from repro.exec.plan import ShapeSig
from repro.data.pipeline import inverted_index, zipf_corpus
from repro.exec.adaptive import AdaptiveDeadline, CapacityModel, adaptive_key
from repro.exec.batch import bucket_plans, dispatch_bucket
from repro.exec.topology import ReplicaBalancer, make_topology
from repro.obs import (Obs, get_obs, parse_json, parse_prometheus,
                      set_obs, sig_label, to_json, to_prometheus)
from repro.obs.export import SnapshotRing
from repro.obs.profile import ProfileStore
from repro.obs.registry import (MetricsRegistry, default_latency_buckets,
                                pow2_buckets)
from repro.obs.trace import NULL_SPAN, Tracer, format_trace
from repro.serve.loadgen import CostModel, calibrate_from_profile
from repro.serve.search import AsyncSearchEngine, SearchEngine, zipf_query_log

N_DEVICES = 2
multi_device = pytest.mark.skipif(
    len(jax.devices()) < N_DEVICES,
    reason=f"needs >= {N_DEVICES} devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)",
)


@pytest.fixture(scope="module")
def postings():
    docs = zipf_corpus(3000, vocab=400, mean_len=40, seed=3)
    return inverted_index(docs)


def _sig(cap=256, shards=1, replicas=1):
    return ShapeSig(k=2, ts=(4, 5), gmaxes=(16, 32), capacity_tier=cap,
                    shards=shards, replicas=replicas)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_types_and_snapshot():
    r = MetricsRegistry()
    c = r.counter("reqs", "requests")
    g = r.gauge("depth", "queue depth")
    hw = r.gauge("high", "high water", track_max=True)
    h = r.histogram("lat_us", "latency", buckets=[1.0, 10.0, 100.0])
    c.inc()
    c.inc(2)
    g.set(5)
    g.dec(2)
    hw.set(4)
    hw.set(2)  # track_max keeps 4
    for v in (0.5, 3.0, 50.0, 1e6):
        h.observe(v)
    snap = r.snapshot()
    assert snap["counters"]["reqs"] == 3
    assert snap["gauges"]["depth"] == 3
    assert snap["gauges"]["high"] == 4
    hs = snap["histograms"]["lat_us"]
    assert hs["count"] == 4 and sum(hs["counts"]) == 4
    assert hs["counts"] == [1, 1, 1, 1]  # one per bucket + one +Inf
    assert hs["sum"] == pytest.approx(0.5 + 3.0 + 50.0 + 1e6)
    assert h.quantile(0.5) <= h.quantile(1.0)
    r.reset()
    assert r.snapshot()["counters"]["reqs"] == 0


def test_registry_get_or_create_and_kind_clash():
    r = MetricsRegistry()
    assert r.counter("x") is r.counter("x")
    with pytest.raises(ValueError, match="already registered"):
        r.gauge("x")


def test_counter_is_monotonic():
    r = MetricsRegistry()
    with pytest.raises(ValueError):
        r.counter("c").inc(-1)


def test_bucket_lattices():
    lat = default_latency_buckets(1.0, 100.0)
    assert lat == [1.0, 2.0, 5.0, 10.0, 20.0, 50.0, 100.0]
    assert pow2_buckets(1, 8) == [1.0, 2.0, 4.0, 8.0]


def test_collector_appears_in_snapshot():
    r = MetricsRegistry()
    r.register_collector(lambda: {"ext_thing": 7.0})
    assert r.snapshot()["collected"]["ext_thing"] == 7.0


# ---------------------------------------------------------------------------
# satellite 1: EXEC_COUNTERS snapshot tearing
# ---------------------------------------------------------------------------

def test_exec_counters_snapshot_never_tears():
    """Writers bump two keys atomically via ``bump_many``; every reader
    snapshot must observe the pair in lockstep (the pre-fix failure mode:
    ``dict(EXEC_COUNTERS)`` copied mid-update)."""
    stop = threading.Event()
    N = 4000

    def writer():
        for _ in range(N):
            EXEC_COUNTERS.bump_many(
                {"tickets_resolved": 1, "queue_wait_us": 7})

    torn = []

    def reader():
        while not stop.is_set():
            s = EXEC_COUNTERS.snapshot()
            if s["queue_wait_us"] != 7 * s["tickets_resolved"]:
                torn.append(s)
                return

    writers = [threading.Thread(target=writer) for _ in range(3)]
    r = threading.Thread(target=reader)
    r.start()
    for w in writers:
        w.start()
    for w in writers:
        w.join()
    stop.set()
    r.join()
    assert not torn, torn[:1]
    assert EXEC_COUNTERS["tickets_resolved"] == 3 * N
    assert EXEC_COUNTERS["queue_wait_us"] == 21 * N


def test_exec_counters_snapshot_during_dispatch(postings):
    """Snapshots (typed registry + legacy) stay consistent and exportable
    while the engine dispatches device buckets from another thread."""
    obs = Obs()
    eng = AsyncSearchEngine(postings, seed=3, flush_tier=64,
                            result_cache=0, max_inflight=8, obs=obs)
    log = zipf_query_log(sorted(eng.index), 16, seed=11)
    done = threading.Event()

    def serve():
        for q in log:
            eng.submit(q)
        eng.drain()
        done.set()

    t = threading.Thread(target=serve)
    t.start()
    while not done.is_set():
        snap = obs.registry.snapshot()
        parse_prometheus(to_prometheus(snap))  # raises on malformed output
        s = EXEC_COUNTERS.snapshot()
        assert set(s) == set(EXEC_COUNTERS._KEYS)
    t.join()
    assert EXEC_COUNTERS["tickets_resolved"] == len(log)


# ---------------------------------------------------------------------------
# satellite 2: balancer failure telemetry
# ---------------------------------------------------------------------------

def test_balancer_queued_weight_histogram_and_failures():
    bal = ReplicaBalancer(2)
    r0 = bal.acquire(weight=1.0)
    r1 = bal.acquire(weight=1024.0)
    assert {r0, r1} == {0, 1}  # least-loaded spreads the two buckets
    bal.release(r0, weight=1.0)
    bal.release(r1, weight=1024.0, failed=True)
    loads = bal.loads()
    assert all(d["in_flight"] == 0 for d in loads)
    assert sum(d["failures"] for d in loads) == 1
    for d in loads:
        qw = d["queued_weight"]
        assert len(qw["counts"]) == len(qw["buckets"]) + 1
        assert qw["counts"] == sorted(qw["counts"])  # cumulative
        assert qw["counts"][-1] == d["dispatched"]
    bal.reset()
    loads = bal.loads()
    assert all(d["failures"] == 0 and d["queued_weight"]["counts"][-1] == 0
               for d in loads)


@multi_device
def test_mid_collect_failure_resets_balancer_and_counts_once(postings):
    """A flight whose *collect* raises must return its row's in-flight
    weight, mark one per-row failure, and count exactly one
    ``dispatch_failures`` in both the legacy and typed surfaces."""
    obs = Obs()
    topo = make_topology(2, 1)
    eng = SearchEngine(postings, seed=3, topology=topo, shard_min_g=1 << 20)
    log = zipf_query_log(sorted(eng.index), 8, seed=11)
    plans = [(i, eng.plan(q)) for i, q in enumerate(log)]
    buckets = bucket_plans([(i, p) for i, p in plans
                            if p.algorithm == "device"])
    sig = next(iter(buckets))
    bucket = dispatch_bucket(
        lambda term: eng.device.sets[str(term)], sig, buckets[sig],
        use_pallas=eng.device.use_pallas, mesh=eng.device.mesh,
        shard_axis=eng.device.shard_axis,
        get_sharded_set=lambda term: eng.device.get_mesh_set(str(term)),
        topology=topo,
        get_replica_set=lambda r, term: eng.device.get_replica_set(
            r, str(term)),
        obs=obs)
    assert any(d["in_flight"] > 0 for d in topo.load_snapshot())
    assert obs.inflight.value == 1

    def boom():
        raise RuntimeError("device fell over mid-collect")

    bucket.pending = PendingBatch(n_queries=len(buckets[sig]),
                                  _collect=boom)
    with pytest.raises(RuntimeError, match="mid-collect"):
        bucket.collect()
    loads = topo.load_snapshot()
    assert all(d["in_flight"] == 0 for d in loads), loads
    assert sum(d["failures"] for d in loads) == 1
    assert EXEC_COUNTERS["dispatch_failures"] == 1
    assert obs.dispatch_failures.value == 1
    assert obs.inflight.value == 0
    # _finish is one-shot: a second collect attempt cannot double-count
    with pytest.raises(RuntimeError):
        bucket.collect()
    assert sum(d["failures"] for d in topo.load_snapshot()) == 1
    assert EXEC_COUNTERS["dispatch_failures"] == 1


# ---------------------------------------------------------------------------
# tracer unit behavior
# ---------------------------------------------------------------------------

def test_tracer_disabled_returns_shared_sentinel():
    t = Tracer(enabled=False)
    s = t.start("request")
    assert s is NULL_SPAN and s is s.child("plan")
    s.set(x=1)
    s.end()
    assert s.attrs == {} and not s.enabled
    assert t.open_count() == 0 and t.finished() == []


def test_tracer_span_tree_and_ring():
    t = Tracer(enabled=True, max_finished=4)
    root = t.start("request", route="device")
    with root.child("plan"):
        pass
    t.span_at("device", 10.0, 20.0, parent=root)
    root.end()
    root.end()  # idempotent
    assert t.open_count() == 0
    names = [s.name for s in t.finished()]
    assert sorted(names) == ["device", "plan", "request"]
    text = format_trace(t.finished())
    assert "request" in text and "plan" in text
    for i in range(10):
        t.span_at(f"s{i}", 0.0, 1.0)
    assert len(t.finished()) == 4 and t.dropped > 0


def test_tracer_backdated_start():
    fake = [100.0]
    t = Tracer(enabled=True, clock=lambda: fake[0])
    s = t.start("bucket", start_us=50.0 * 1e6)
    fake[0] = 101.0
    s.end()
    assert s.start_us == pytest.approx(50e6)
    assert s.duration_us == pytest.approx(51e6)


def test_context_manager_records_error():
    t = Tracer(enabled=True)
    with pytest.raises(ValueError):
        with t.start("request") as s:
            raise ValueError("nope")
    assert "error" in s.attrs and t.open_count() == 0


# ---------------------------------------------------------------------------
# satellite 3: span lifecycle invariants through the serving stack
# ---------------------------------------------------------------------------

def test_exactly_one_root_span_per_ticket_all_routes(postings):
    """Every submit — device-executed, cache-hit, or error-resolved —
    closes exactly one ``request`` root span."""
    obs = Obs(trace=True)
    eng = AsyncSearchEngine(postings, seed=3, flush_tier=64,
                            max_inflight=8, obs=obs)
    log = zipf_query_log(sorted(eng.index), 12, seed=11)
    tickets = [eng.submit(q) for q in log]
    eng.drain()
    repeats = [eng.submit(q) for q in log[:4]]  # result-cache hits
    eng.drain()
    assert all(t.done for t in tickets + repeats)
    roots = obs.tracer.finished("request")
    assert len(roots) == len(log) + 4
    assert obs.tracer.open_count() == 0
    routes = {s.attrs.get("route") for s in roots}
    assert "cache" in routes and "device" in routes
    device_roots = [s for s in roots if s.attrs.get("route") == "device"]
    assert all("bucket_span" in s.attrs for s in device_roots)
    assert all(s.attrs.get("error") is None for s in roots)
    # typed queue-wait histogram saw every resolution
    assert obs.queue_wait.count == len(roots)


def test_error_path_closes_root_span(postings, monkeypatch):
    obs = Obs(trace=True)
    eng = AsyncSearchEngine(postings, seed=3, flush_tier=64,
                            result_cache=0, max_inflight=8, obs=obs)
    log = zipf_query_log(sorted(eng.index), 6, seed=11)
    monkeypatch.setattr(
        "repro.serve.search.dispatch_bucket",
        lambda *a, **kw: (_ for _ in ()).throw(RuntimeError("boom")))
    tickets = [eng.submit(q) for q in log]
    eng.drain()
    assert all(t.done for t in tickets)
    for t in tickets:
        with pytest.raises(RuntimeError, match="boom"):
            _ = t.value
    roots = obs.tracer.finished("request")
    assert len(roots) == len(log)
    assert obs.tracer.open_count() == 0
    assert all(s.attrs.get("error") == "RuntimeError" for s in roots)


def test_bucket_spans_overlap_in_window(postings):
    """With the overlapped window the drain dispatches buckets
    back-to-back before collecting: their spans must genuinely overlap,
    and each carries dispatch/collect children plus the member request
    trace ids."""
    obs = Obs(trace=True)
    eng = AsyncSearchEngine(postings, seed=3, flush_tier=64,
                            result_cache=0, max_inflight=8, obs=obs)
    log = zipf_query_log(sorted(eng.index), 24, seed=11)
    for q in log:
        eng.submit(q)
    n_buckets = eng.drain()
    assert n_buckets >= 2
    bspans = sorted(obs.tracer.finished("bucket"),
                    key=lambda s: s.start_us)
    assert len(bspans) == n_buckets
    assert any(b.start_us < a.end_us
               for a, b in zip(bspans, bspans[1:])), (
        "no overlapping bucket spans in an overlapped drain")
    for s in bspans:
        assert s.attrs["traces"], "bucket span lost its member traces"
        assert s.attrs["batch"] >= 1
    for name in ("dispatch", "collect"):
        stage = obs.tracer.finished(name)
        assert len(stage) == n_buckets
        by_parent = {s.parent_id for s in stage}
        assert by_parent == {s.span_id for s in bspans}
    assert obs.tracer.finished("device") == []
    assert obs.tracer.open_count() == 0
    # profile store attributed every executed signature
    assert len(obs.profile.signatures()) >= 1
    assert obs.collect_latency.count == n_buckets
    assert obs.batch_size.count == n_buckets


def test_disabled_mode_adds_zero_spans_and_low_overhead(postings):
    """Metrics-only mode (the default) must record no spans at all; the
    submit path with tracing enabled stays within a loose factor of
    disabled mode on pure cache-hit traffic (the strict <=5% QPS gate
    runs on warmed device traffic in ``benchmarks/fig_observability.py``
    — this is the catastrophic-regression guard)."""
    eng = AsyncSearchEngine(postings, seed=3, flush_tier=64, max_inflight=8)
    assert not eng.obs.tracer.enabled  # global default: metrics only
    log = zipf_query_log(sorted(eng.index), 8, seed=11)
    for q in log:
        eng.submit(q)
    eng.drain()
    assert eng.obs.tracer.finished() == []
    assert eng.obs.tracer.open_count() == 0
    assert eng.obs.queue_wait.count == len(log)  # metrics still flow

    def wall(obs_mode):
        eng.obs = obs_mode
        t0 = time.perf_counter()
        for q in log:
            eng.submit(q)  # all cache hits: no device work
        eng.drain()
        return time.perf_counter() - t0

    disabled, enabled = Obs(), Obs(trace=True)
    base = [wall(disabled) for _ in range(5)]
    traced = [wall(enabled) for _ in range(5)]
    assert float(np.median(traced)) < 3.0 * max(1e-9,
                                                float(np.median(base)))
    eng.obs = disabled


def test_flusher_fills_snapshot_ring(postings):
    obs = Obs()
    eng = AsyncSearchEngine(postings, seed=3, flush_tier=4,
                            deadline_us=500.0, max_inflight=8,
                            snapshot_every_s=0.01, obs=obs)
    log = zipf_query_log(sorted(eng.index), 6, seed=11)
    def resolved_in_latest():
        latest = obs.ring.latest()
        if latest is None:
            return 0
        return latest[1]["collected"]["exec_tickets_resolved"]

    with eng:
        tickets = [eng.submit(q) for q in log]
        for t in tickets:
            assert t.wait(timeout=60.0)
        # the flusher pushes a cut at most every snapshot_every_s — wait
        # for one taken AFTER the resolutions landed
        deadline = time.time() + 10.0
        while resolved_in_latest() < len(log) and time.time() < deadline:
            time.sleep(0.01)
    assert len(obs.ring) >= 1
    assert resolved_in_latest() >= len(log)


# ---------------------------------------------------------------------------
# the served path under spans: flusher loop, collect's passes, compiles
# ---------------------------------------------------------------------------

def _serve(eng, log, gap_s=0.002):
    """Serve ``log`` through the background flusher, one query every
    ``gap_s`` with ``arrival_at`` stamped, and wait for every answer."""
    with eng:
        t0 = time.perf_counter()
        tickets = []
        for i, q in enumerate(log):
            tickets.append(eng.submit(q, arrival_at=t0 + i * gap_s))
            time.sleep(gap_s)
        for t in tickets:
            assert t.wait(timeout=60.0)
    return tickets


def _children(spans, parent):
    return [s for s in spans if s.parent_id == parent.span_id]


def test_served_buckets_have_fetch_and_scatter_and_no_device(postings):
    obs = Obs(trace=True, max_finished_spans=100_000)
    eng = AsyncSearchEngine(postings, seed=3, flush_tier=4,
                            deadline_us=1000.0, result_cache=0,
                            max_inflight=8, obs=obs)
    log = zipf_query_log(sorted(eng.index), 24, seed=11)
    _serve(eng, log)
    spans = obs.tracer.finished()
    assert obs.tracer.open_count() == 0
    assert not [s for s in spans if s.name == "device"]
    buckets = [s for s in spans if s.name == "bucket"]
    assert buckets
    for b in buckets:
        (collect,) = [s for s in _children(spans, b) if s.name == "collect"]
        names = [s.name for s in _children(spans, collect)]
        assert names.count("fetch") == b.attrs["passes"]
        assert names.count("scatter") == 1
        assert names.count("rerun") == b.attrs["passes"] - 1
        assert collect.start_us >= b.start_us
        assert collect.end_us <= b.end_us
    # every request answered from a bucket ends at its ticket's resolution
    assert {s.name for s in spans} >= {"wait", "take", "replan", "late"}
    for late in (s for s in spans if s.name == "late"):
        assert late.duration_us >= 0


def test_wait_never_overlaps_the_flushers_work(postings):
    obs = Obs(trace=True, max_finished_spans=100_000)
    eng = AsyncSearchEngine(postings, seed=3, flush_tier=4,
                            deadline_us=1000.0, result_cache=0,
                            max_inflight=8, obs=obs)
    _serve(eng, zipf_query_log(sorted(eng.index), 24, seed=12), gap_s=0.004)
    spans = obs.tracer.finished()
    waits = [s for s in spans if s.name == "wait"]
    work = [s for s in spans if s.name in ("take", "dispatch", "collect")]
    assert waits and work
    for w in waits:
        assert w.attrs["timeout_ms"] > 0 and w.attrs["queued"] >= 0
        for s in work:
            assert s.end_us <= w.start_us or s.start_us >= w.end_us, (w, s)


def test_overflowing_bucket_records_one_rerun(postings, monkeypatch):
    """A survivor capacity of 1 makes buckets overflow: each such bucket
    records one ``rerun`` over exactly its overflowing queries, and
    ``passes`` 2; the rerun spans match the executor's own count."""
    monkeypatch.setattr("repro.exec.plan.default_capacity", lambda ts: 1)
    obs = Obs(trace=True, max_finished_spans=100_000)
    eng = AsyncSearchEngine(postings, seed=3, flush_tier=4,
                            deadline_us=1000.0, result_cache=0,
                            max_inflight=8, obs=obs)
    before = EXEC_COUNTERS["rerun_calls"]
    tickets = _serve(eng, zipf_query_log(sorted(eng.index), 24, seed=11))
    spans = obs.tracer.finished()
    reruns = [s for s in spans if s.name == "rerun"]
    assert reruns
    assert len(reruns) == EXEC_COUNTERS["rerun_calls"] - before
    by_id = {s.span_id: s for s in spans}
    overflowed = {}
    for t in tickets:
        stats = t.value.stats
        if (t.span.attrs.get("route") == "device"
                and stats["capacity"] == stats["group_tuples"]):
            key = t.span.attrs["bucket_span"]
            overflowed[key] = overflowed.get(key, 0) + 1
    for r in reruns:
        bucket = by_id[by_id[r.parent_id].parent_id]
        assert bucket.attrs["passes"] == 2
        assert r.attrs["rows"] == bucket.attrs["rerun_rows"]
        assert r.attrs["rows"] == overflowed[bucket.span_id]
        assert r.attrs["capacity"] > 1
    for b in (s for s in spans if s.name == "bucket"):
        assert b.attrs["passes"] == 1 + (b.span_id in overflowed)


def test_fresh_shape_records_one_compile():
    obs = Obs(trace=True)
    quiet = Obs()
    before = quiet.programs_compiled.value
    x = np.arange(7 * 13, dtype=np.float32).reshape(7, 13)
    jax.jit(lambda a: a * 3.0 + 1.0)(x).block_until_ready()
    (span,) = obs.tracer.finished("compile")
    assert span.parent_id is None and span.attrs["secs"] >= 0
    assert span.duration_us == pytest.approx(span.attrs["secs"] * 1e6,
                                             abs=1.0)
    assert obs.programs_compiled.value == 1
    assert quiet.programs_compiled.value == before + 1
    assert quiet.tracer.finished() == []  # counted, never spanned
    snap = obs.snapshot()["counters"]
    assert snap["programs_compiled"] == 1
    assert snap["compile_cache_loads"] == 0


def test_disabled_tracer_records_nothing_while_serving(postings):
    obs = Obs()
    eng = AsyncSearchEngine(postings, seed=3, flush_tier=4,
                            deadline_us=1000.0, result_cache=0,
                            max_inflight=8, snapshot_every_s=0.001, obs=obs)
    tickets = _serve(eng, zipf_query_log(sorted(eng.index), 12, seed=13))
    assert all(t.done for t in tickets)
    assert obs.tracer.finished() == [] and obs.tracer.open_count() == 0
    assert obs.batch_size.count >= 1


@pytest.mark.parametrize("route", ["device", "cache", "host", "error"])
def test_resolved_at_follows_submitted_at(postings, monkeypatch, route):
    """``Ticket.resolved_at`` is stamped once, on the engine clock, at or
    after ``submitted_at``, and the request's root span ends there."""
    obs = Obs(trace=True)
    eng = AsyncSearchEngine(postings, seed=3, flush_tier=64,
                            max_inflight=8, obs=obs)
    q = zipf_query_log(sorted(eng.index), 1, seed=11)[0]
    if route == "cache":
        eng.submit(q)
        eng.drain()
    elif route == "host":
        q = [-1]  # an unknown term: answered empty on the host
    elif route == "error":
        monkeypatch.setattr(
            "repro.serve.search.dispatch_bucket",
            lambda *a, **kw: (_ for _ in ()).throw(RuntimeError("boom")))
    late = time.perf_counter() - 0.01
    ticket = eng.submit(q, arrival_at=late)
    eng.drain()
    assert ticket.done and ticket.resolved_at is not None
    assert ticket.submitted_at == pytest.approx(late)
    assert ticket.resolved_at >= ticket.submitted_at
    assert (ticket.error is not None) == (route == "error")
    root = ticket.span
    assert root.attrs["route"] == ("device" if route == "error" else route)
    assert root.end_us == pytest.approx(ticket.resolved_at * 1e6)
    assert root.start_us == pytest.approx(late * 1e6)
    (late_span,) = [s for s in obs.tracer.finished("late")
                    if s.parent_id == root.span_id]
    assert late_span.end_us <= root.end_us


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def test_prometheus_round_trip():
    obs = Obs()
    obs.queue_wait.observe(42.0)
    obs.queue_wait.observe(4200.0)
    obs.dispatch_failures.inc(3)
    obs.inflight.set(2)
    EXEC_COUNTERS.bump("batch_calls", 5)
    text = to_prometheus(obs.snapshot())
    parsed = parse_prometheus(text)
    h = parsed["repro_queue_wait_us"]
    assert h["type"] == "histogram" and h["count"] == 2
    assert h["sum"] == pytest.approx(4242.0)
    assert h["buckets"][-1][0] == float("inf")
    assert h["buckets"][-1][1] == 2  # +Inf cumulative == count
    assert parsed["repro_dispatch_failures"]["value"] == 3
    assert parsed["repro_inflight_buckets"]["value"] == 2
    assert parsed["repro_exec_batch_calls"]["value"] == 5


def test_prometheus_parser_rejects_garbage():
    with pytest.raises(ValueError, match="unparseable"):
        parse_prometheus("this is { not an exposition\n")
    bad = ('# TYPE h histogram\nh_bucket{le="1"} 5\n'
           'h_bucket{le="2"} 3\nh_sum 1\nh_count 5\n')
    with pytest.raises(ValueError, match="not cumulative"):
        parse_prometheus(bad)


def test_json_round_trip_and_validation():
    obs = Obs()
    obs.batch_size.observe(8)
    snap = parse_json(to_json(obs.snapshot()))
    assert snap["histograms"]["bucket_batch_size"]["count"] == 1
    with pytest.raises(ValueError, match="missing section"):
        parse_json("{}")
    broken = obs.snapshot()
    broken["histograms"]["bucket_batch_size"]["count"] = 99
    with pytest.raises(ValueError, match="count"):
        parse_json(to_json(broken))


def test_snapshot_ring_bounded():
    ring = SnapshotRing(maxlen=3)
    for i in range(5):
        ring.push(float(i), {"i": i})
    assert len(ring) == 3
    assert ring.latest() == (4.0, {"i": 4})
    assert [t for t, _ in ring.entries()] == [2.0, 3.0, 4.0]
    ring.clear()
    assert ring.latest() is None


# ---------------------------------------------------------------------------
# profile store + calibration loop
# ---------------------------------------------------------------------------

def test_profile_residual_attribution():
    model = CostModel(per_bucket_us=100.0, per_query_us=5.0)
    store = ProfileStore(cost_model=model)
    sig = _sig()
    store.observe(sig, 4, 100.0 + 5.0 * 4)   # exactly on-model
    store.observe(sig, 8, 100.0 + 5.0 * 8 + 30.0)  # +30us residual
    res = store.residuals()[sig_label(sig)]
    assert res["buckets"] == 2 and res["queries"] == 12
    assert res["residual_us"] == pytest.approx(30.0)
    assert res["mean_residual_us"] == pytest.approx(15.0)


def test_profile_fit_closes_calibration_loop():
    store = ProfileStore()
    for b in (1, 2, 4, 8, 16):
        store.observe(_sig(), b, 200.0 + 7.0 * b)
        store.observe(_sig(cap=512), b, 200.0 + 7.0 * b)
    fit = calibrate_from_profile(store)
    assert fit is not None
    assert fit.per_bucket_us == pytest.approx(200.0, rel=1e-6)
    assert fit.per_query_us == pytest.approx(7.0, rel=1e-6)
    assert fit.capacity_qps(64) > 0


def test_profile_fit_needs_two_operating_points():
    store = ProfileStore()
    for _ in range(10):
        store.observe(_sig(), 4, 120.0)
    assert store.fit_cost() is None
    assert calibrate_from_profile(store) is None


def test_profile_window_is_bounded():
    store = ProfileStore(max_samples=8)
    for i in range(50):
        store.observe(_sig(), 1 + i % 3, 10.0)
    res = store.residuals()[sig_label(_sig())]
    assert res["buckets"] == 50  # totals keep counting
    assert len(store._sigs[_sig()].samples) == 8  # window slides


def test_sig_label_variants():
    assert sig_label(_sig()) == "k2/t4x5/cap256"
    assert sig_label(_sig(shards=4)) == "k2/t4x5/cap256/s4"
    assert sig_label(_sig(replicas=2)) == "k2/t4x5/cap256/r2"


# ---------------------------------------------------------------------------
# adaptive controllers: telemetry snapshots
# ---------------------------------------------------------------------------

def test_capacity_model_telemetry():
    m = CapacityModel(min_observations=4, decay_s=None)
    # G = 1 << ts[-1] = 4096 — roomy enough for the learned tier to land
    # above the 500-survivor observations instead of clamping at G
    sig = ShapeSig(k=2, ts=(4, 12), gmaxes=(16, 4096), capacity_tier=64)
    m.observe_bucket(sig, [{"tuples_survived": 500}] * 4)
    tel = m.telemetry()
    entry = tel[str(adaptive_key(sig))]
    assert entry["observations"] == 4
    assert entry["window_max"] == 500
    assert entry["learned_tier"] == m.capacity_for(adaptive_key(sig), 0)
    assert entry["learned_tier"] >= 512  # >= quantile * margin, pow2


def test_adaptive_deadline_telemetry():
    d = AdaptiveDeadline(min_observations=2)
    for i in range(4):
        d.observe("k", i * 0.01)
    tel = d.telemetry()["k"]
    assert tel["gaps"] == 3 and tel["warm"]
    assert tel["gap_ewma_us"] == pytest.approx(10_000.0, rel=0.01)


# ---------------------------------------------------------------------------
# global obs plumbing
# ---------------------------------------------------------------------------

def test_global_obs_reset_discards_override():
    mine = set_obs(Obs(trace=True))
    assert get_obs() is mine
    from repro.obs import reset_obs

    reset_obs()
    fresh = get_obs()
    assert fresh is not mine and not fresh.tracer.enabled


def test_obs_reset_leaves_exec_counters_alone():
    obs = Obs()
    obs.dispatch_failures.inc()
    EXEC_COUNTERS.bump("batch_calls", 3)
    obs.reset()
    assert obs.dispatch_failures.value == 0
    assert EXEC_COUNTERS["batch_calls"] == 3
