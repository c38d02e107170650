"""End-to-end serving driver (the paper's application): build an inverted
index over a Zipf corpus, then serve a batched conjunctive-query workload
with the paper's keyword-count mix, with online algorithm selection
(RanGroupScan / HashBin per Section 3.4).

``--async-front`` serves the same log through the online front-end
instead: single-query submits into the deadline-aware admission queue,
with compile warming and the result cache on.  Add ``--flusher`` to let
the background flusher thread own the flush cadence (no manual ``pump``
calls anywhere — the autonomous serving runtime); ``--max-inflight N``
bounds its overlapped dispatch window (1 = collect each bucket before
dispatching the next, the synchronous shape).

``--mesh RxS`` (e.g. ``--mesh 2x2``) serves over a 2-D device topology:
R data-parallel replica rows x S z-shards per row.  Huge-G queries run on
the full mesh (batch split over the rows), small buckets spread across
the replicas via the topology's load balancer.  On CPU, export
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` first to get
forced host devices to lay out.

``--expr`` upgrades part of the log to boolean ∪/∩/∖ expressions in the
``parse`` surface syntax (``"(a|b)&c-d"``) — engines accept term lists,
``Expr`` DAGs, and strings interchangeably.  Expression queries ride the
same plan → bucket → execute → scatter pipeline (shape-bucketed by
expression structure) and share composite subtrees through the
subexpression cache; with ``--async-front`` the demo reports the
cache's hit/merge counters.

Run:  PYTHONPATH=src python examples/serve_search.py [--docs 20000] [--queries 200]
"""
import argparse
import time

import numpy as np

from repro.compile_cache import configure_compile_cache
from repro.data.pipeline import inverted_index, zipf_corpus
from repro.serve.search import AsyncSearchEngine, SearchEngine, zipf_query_log


def to_expr_log(queries):
    """Upgrade every third multi-term query to a boolean expression.

    ``[a, b, c]`` becomes ``"(a|b)&c"`` (and, with a 4th term, ``"-d"``) —
    distinct roots share union bases, the shape the subexpression cache
    serves without device work."""
    out = []
    for i, q in enumerate(queries):
        if i % 3 == 0 and len(q) >= 3:
            e = f"({q[0]}|{q[1]})&{q[2]}"
            if len(q) >= 4:
                e += f"-{q[3]}"
            out.append(e)
        else:
            out.append(q)
    return out


def serve_async(postings, queries, flusher: bool = False, topology=None,
                max_inflight: int = 8, metrics_dump: str = ""):
    """Submit one query at a time; flushes run on the manual pump cadence
    or — with ``flusher`` — on the background flusher thread."""
    from repro.core.engine import EXEC_COUNTERS

    obs = None
    if metrics_dump:
        from repro.obs import Obs

        obs = Obs(trace=True)
    # warm_b_tiers defaults to every pow2 tier up to flush_tier, so any
    # partial-flush size hits a pre-traced executable
    engine = AsyncSearchEngine(postings, w=256, m=2, deadline_us=2000,
                               flush_tier=8, warm_queries=queries,
                               warm_top_k=64, topology=topology,
                               max_inflight=max_inflight, obs=obs)
    EXEC_COUNTERS.reset()
    t0 = time.perf_counter()
    tickets = []
    if flusher:
        with engine:                      # start() ... stop() drains
            for q in queries:
                tickets.append(engine.submit(q))
            for t in tickets:
                t.wait(timeout=60.0)
    else:
        for q in queries:
            tickets.append(engine.submit(q))
            engine.pump()
        engine.drain()
    wall = time.perf_counter() - t0
    waits = np.asarray([t.wait_us for t in tickets])
    mode = "flusher" if flusher else "manual pump"
    print(f"async ({mode}): served {len(tickets)} queries in {wall:.2f}s "
          f"(cache hits {EXEC_COUNTERS['result_cache_hits']}, "
          f"jit executions {EXEC_COUNTERS['batch_calls']}, "
          f"serve-time traces {EXEC_COUNTERS['batch_traces']}, "
          f"flusher wakeups {EXEC_COUNTERS['flusher_wakeups']})")
    print(f"queue wait p50={np.percentile(waits, 50):.0f}us "
          f"p99={np.percentile(waits, 99):.0f}us")
    if EXEC_COUNTERS["expr_calls"] or EXEC_COUNTERS["subexpr_cache_hits"]:
        print(f"expression passes {EXEC_COUNTERS['expr_calls']}, "
              f"subexpr cache hits {EXEC_COUNTERS['subexpr_cache_hits']}, "
              f"host merges {EXEC_COUNTERS['subexpr_host_merges']}")
    if topology is not None:
        print(f"mesh2d passes {EXEC_COUNTERS['mesh2d_calls']} "
              f"(row dispatches {EXEC_COUNTERS['mesh2d_row_dispatches']}), "
              f"balancer dispatches {EXEC_COUNTERS['replica_dispatches']} "
              f"-> {[d['dispatched'] for d in topology.load_snapshot()]}")
    if obs is not None:
        from repro.obs.export import to_json, to_prometheus

        snap = obs.snapshot()
        if metrics_dump == "json":
            print(to_json(snap, indent=2))
        else:
            print(to_prometheus(snap))
        print(f"# open spans after drain: {obs.tracer.open_count()}")
        print(obs.trace_dump(limit=3))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--docs", type=int, default=20000)
    ap.add_argument("--queries", type=int, default=200)
    ap.add_argument("--device", action="store_true",
                    help="serve through the batched device engine "
                         "(plan -> bucket -> one jit execution per shape)")
    ap.add_argument("--async-front", action="store_true",
                    help="serve through AsyncSearchEngine (admission queue, "
                         "deadline flushing, result cache, compile warming)")
    ap.add_argument("--flusher", action="store_true",
                    help="with --async-front: background flusher thread owns "
                         "the flush cadence (no manual pump calls)")
    ap.add_argument("--mesh", type=str, default=None, metavar="RxS",
                    help="serve over a 2-D topology: R replica rows x S "
                         "z-shards (e.g. 2x2); needs R*S devices")
    ap.add_argument("--max-inflight", type=int, default=8,
                    help="with --async-front: bound on concurrently "
                         "dispatched buckets (1 = synchronous collect)")
    ap.add_argument("--expr", action="store_true",
                    help="upgrade part of the log to boolean ∪/∩/∖ "
                         "expressions (parse syntax, e.g. '(a|b)&c-d')")
    ap.add_argument("--metrics-dump", type=str, default="", nargs="?",
                    const="prometheus", choices=["", "prometheus", "json"],
                    help="with --async-front: serve with tracing on and "
                         "print the metrics exposition (and a span-tree "
                         "sample) after the run")
    args = ap.parse_args()
    configure_compile_cache(multi_device=bool(args.mesh))

    topology = None
    if args.mesh:
        from repro.exec.topology import make_topology

        replicas, shards = (int(x) for x in args.mesh.lower().split("x"))
        topology = make_topology(replicas, shards)
        print(f"topology: {topology.describe()} "
              f"({topology.replicas * topology.shards} devices)")

    print(f"building corpus ({args.docs} docs) ...")
    docs = zipf_corpus(args.docs, vocab=20000, mean_len=120, seed=1)
    postings = inverted_index(docs)
    if args.async_front:
        # live-traffic shape: prune stopword/hapax terms, draw the log from
        # a finite pool so exact repeats occur (the result cache's regime)
        from repro.serve.search import repeated_query_log

        kept = {t: p for t, p in postings.items()
                if 16 <= len(p) <= 0.04 * args.docs}
        queries = repeated_query_log(sorted(kept), args.queries,
                                     n_distinct=max(8, args.queries // 4),
                                     seed=2)
        if args.expr:
            queries = to_expr_log(queries)
        serve_async(kept, queries, flusher=args.flusher, topology=topology,
                    max_inflight=args.max_inflight,
                    metrics_dump=args.metrics_dump)
        return
    engine = SearchEngine(postings, w=256, m=2, use_device=args.device,
                          topology=topology)
    print(f"index built: {len(engine.index)} terms in {engine.build_s:.2f}s")

    queries = zipf_query_log(sorted(engine.index), args.queries, seed=2)
    if args.expr:
        queries = to_expr_log(queries)
    t0 = time.perf_counter()
    results = engine.query_batch(queries)
    wall = time.perf_counter() - t0

    lat = np.asarray([r.latency_us for r in results if r.algorithm != "empty"])
    algos = {}
    for r in results:
        algos[r.algorithm] = algos.get(r.algorithm, 0) + 1
    print(f"served {len(results)} queries in {wall:.2f}s "
          f"({1e3*wall/len(results):.2f} ms/query avg)")
    print(f"latency p50={np.percentile(lat,50):.0f}us "
          f"p95={np.percentile(lat,95):.0f}us p99={np.percentile(lat,99):.0f}us")
    print(f"algorithm mix: {algos}")
    hits = sum(len(r.doc_ids) for r in results)
    print(f"total results: {hits} doc ids")


if __name__ == "__main__":
    main()
