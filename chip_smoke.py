"""Serve the set-intersection engine on a TPU and check every answer.

Run from the root of a checkout:

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the 2x2 replicas x z-shards path

One chip: a seeded Zipf corpus of 1,000,000 documents is indexed, its
mid-frequency posting lists are mirrored on the device through
``AsyncSearchEngine``, and a bounded set of shape signatures is
compile-warmed.  Conjunctive queries at the paper's 68/23/9% mix of 2-,
3- and 4-term queries and ∪/∩/∖ expressions are served through
``submit`` with the background flusher, then ``SuggestEngine.suggest``
serves top-K overlap queries.  Every answer is compared with the host
oracle (``np.intersect1d``, ``exec.expr.eval_host``, exact top-K counts).

``--chips 4`` runs only the 2-D topology (2 replica rows x 2 z-shards)
serving path and what it is compared with: the same queries on a
one-chip engine in this process and on the host oracle.  That process
keeps no persistent compile cache (see ``repro.compile_cache``).

Any failed check raises, so the script exits non-zero and prints no
result line.  Without a TPU it stops before any work.  On success the
last line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from functools import reduce
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import configure_compile_cache  # noqa: E402
from repro.core.engine import (  # noqa: E402
    EXEC_COUNTERS, bucket_hlo_text, pow2_tiers,
)
from repro.core.partition import choose_t  # noqa: E402
from repro.data.pipeline import inverted_index, zipf_corpus  # noqa: E402
from repro.exec.expr import eval_host, parse  # noqa: E402
from repro.exec.topology import make_topology  # noqa: E402
from repro.serve.loadgen import QueryMix  # noqa: E402
from repro.serve.search import AsyncSearchEngine, SuggestEngine  # noqa: E402

W, M = 256, 2            # word width and images per group (the repo default)
FLUSH_TIER = 4           # largest bucket; warming covers tiers 1, 2, 4
TICKET_TIMEOUT_S = 900.0


class SmokeFailure(AssertionError):
    pass


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


@dataclasses.dataclass(frozen=True)
class Config:
    """Corpus, traffic and warm-up sizes of one run."""

    docs: int
    vocab: int = 100_000
    mean_len: int = 250
    alpha: float = 1.15
    band_hi: float = 0.04        # keep lists of 16 .. band_hi * docs postings
    conj_pool: int = 32          # distinct conjunctions ...
    conj_queries: int = 256      # ... drawn Zipf-by-rank into this many
    pareto_scale: float = 10.0   # spread of term picks over the term ids
    expr_pool: int = 8
    expr_queries: int = 32
    warm_sigs: int = 16
    suggest_t: int = 10          # depth of the suggest corpus' sets
    suggest_sets: int = 64
    suggest_queries: int = 32
    min_index_bytes: int = 0


ONE_CHIP = Config(docs=1_000_000, min_index_bytes=10**9)
# smaller corpus, wider band: lists past 32768 postings reach 2^12 groups,
# the shard_min_g threshold at which the planner routes to the 2-D mesh;
# term picks spread further so that smaller lists make single-device
# buckets for the replica balancer too.  A vocabulary of 10,000 keeps the
# same long lists but builds two engines' mirrors ten times faster than
# 100,000 terms, which took most of a four-chip run.
FOUR_CHIPS = Config(docs=200_000, vocab=10_000, band_hi=0.2, conj_pool=24,
                    conj_queries=96, pareto_scale=100.0, expr_pool=4,
                    expr_queries=8, warm_sigs=4)


def build_postings(cfg: Config, seed: int) -> dict:
    t0 = time.perf_counter()
    docs = zipf_corpus(cfg.docs, vocab=cfg.vocab, mean_len=cfg.mean_len,
                       alpha=cfg.alpha, seed=seed)
    postings = inverted_index(docs)
    kept = {t: p for t, p in postings.items()
            if 16 <= len(p) <= cfg.band_hi * cfg.docs}
    print(f"corpus: {cfg.docs} docs, {len(postings)} terms, {len(kept)} in "
          f"the posting band, {sum(map(len, kept.values()))} postings "
          f"({time.perf_counter() - t0:.1f} s)")
    return kept


def draw_traffic(cfg: Config, terms, seed: int):
    """Conjunctions at the paper's k-term mix, and ∪/∩/∖ expressions
    ``(a|b)&c`` / ``(a|b)&c-d`` built from 3- and 4-term draws; returns
    both lists and the two shuffled together."""
    rng = np.random.default_rng(seed)
    conj = QueryMix(pareto_scale=cfg.pareto_scale,
                    distinct_pool=cfg.conj_pool).sample(
        terms, cfg.conj_queries, rng)
    three_four = QueryMix(kw_dist=((3, 0.7), (4, 0.3)),
                          pareto_scale=cfg.pareto_scale)
    pool = []
    while len(pool) < cfg.expr_pool:
        q = three_four.sample(terms, 1, rng)[0]
        if len(q) >= 3:
            e = f"({q[0]}|{q[1]})&{q[2]}" + (f"-{q[3]}" if len(q) > 3 else "")
            pool.append(e)
    exprs = [pool[i] for i in rng.integers(0, len(pool), cfg.expr_queries)]
    distinct = {tuple(q) for q in conj}
    by_k = {k: sum(len(q) == k for q in distinct) for k in (1, 2, 3, 4)}
    print(f"traffic: {len(conj)} conjunctions ({len(distinct)} distinct, "
          f"by term count {by_k}), {len(exprs)} expressions "
          f"({len(set(exprs))} distinct)")
    mixed = conj + exprs
    return conj, exprs, [mixed[i] for i in rng.permutation(len(mixed))]


def oracle(postings: dict, q):
    if isinstance(q, str):
        return eval_host(parse(q), lambda t: postings[t])
    return reduce(np.intersect1d, [postings[t] for t in q])


def index_bytes(engine) -> int:
    return sum(s.vals.nbytes + s.images.nbytes
               for s in engine.device.sets.values())


def print_memory() -> None:
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"device 0 memory: {stats.get('bytes_in_use', 0)} bytes in "
              f"use, peak {stats['peak_bytes_in_use']}")
    else:
        print("device 0 memory: not reported by this backend")


def warm(engine, sample, cfg: Config) -> None:
    """Compile the ``warm_sigs`` most frequent signatures of ``sample``
    at every batch tier a flush can use."""
    t0 = time.perf_counter()
    sigs = engine.warm(sample, top_k=cfg.warm_sigs,
                       b_tiers=pow2_tiers(FLUSH_TIER))
    print(f"warm-up: {len(sigs)} signatures x tiers {pow2_tiers(FLUSH_TIER)} "
          f"in {time.perf_counter() - t0:.1f} s")


def serve(engine, queries, waves: int = 2):
    """Submit through the background flusher, one wave after another (so
    later waves can hit the result cache); returns results in order."""
    results = []
    t0 = time.perf_counter()
    with engine:                       # start() ... stop(), which re-raises
        step = -(-len(queries) // waves)
        for lo in range(0, len(queries), step):
            tickets = [engine.submit(q) for q in queries[lo:lo + step]]
            for t in tickets:
                check(t.wait(TICKET_TIMEOUT_S), "a ticket never resolved")
                check(t.error is None, f"a ticket resolved with {t.error!r}")
                results.append(t.value)
    print(f"served {len(queries)} requests in "
          f"{time.perf_counter() - t0:.1f} s")
    return results


def check_answers(postings: dict, queries, results, label: str) -> dict:
    routes: dict = {}
    mismatches = 0
    for q, res in zip(queries, results):
        route = res.algorithm + (" (cached)" if res.stats.get("cached")
                                 else "")
        routes[route] = routes.get(route, 0) + 1
        mismatches += not np.array_equal(res.doc_ids, oracle(postings, q))
    print(f"{label}: routes {routes}, oracle mismatches {mismatches}")
    check(mismatches == 0, f"{label}: {mismatches} answers differ")
    return routes


def check_kernel_in_bucket(engine, queries, results) -> None:
    """The compiled text of one served conjunctive bucket holds the
    Pallas kernels."""
    for q, res in zip(queries, results):
        if res.algorithm == "rangroupscan/device":
            plan = engine.plan(q)
            row = [engine.device.sets[str(t)] for t in plan.terms]
            text = bucket_hlo_text([row], capacity=plan.sig.capacity_tier)
            found = "tpu_custom_call" in text
            print(f"bucket {plan.sig.ts}: tpu_custom_call in compiled "
                  f"text: {found}")
            check(found, "no Pallas kernel in a served bucket")
            return
    check(False, "no conjunctive query was served on the device")


def suggest_phase(postings: dict, cfg: Config) -> None:
    """Top-K overlap among mid-frequency terms' posting lists: which terms
    share the most documents with this one."""
    ids = [t for t in sorted(postings) if choose_t(len(postings[t]), W)
           == cfg.suggest_t][:cfg.suggest_sets]
    check(len(ids) >= 2, f"fewer than 2 sets at depth {cfg.suggest_t}")
    corpus = {t: postings[t] for t in ids}
    t0 = time.perf_counter()
    eng = SuggestEngine(corpus, w=W, m=M)
    probes = ids[:cfg.suggest_queries]
    eng.warm(probes, k=8)
    print(f"suggest: {len(ids)} sets of depth {cfg.suggest_t}, built and "
          f"warmed in {time.perf_counter() - t0:.1f} s")
    t0 = time.perf_counter()
    routes: dict = {}
    mismatches = 0
    for p in probes:
        res = eng.suggest(p, 8)
        routes[res.algorithm] = routes.get(res.algorithm, 0) + 1
        counts = [(c, len(np.intersect1d(corpus[p], corpus[c])))
                  for c in ids if c != p]
        want = sorted((pc for pc in counts if pc[1] >= 1),
                      key=lambda pc: (-pc[1], pc[0]))[:8]
        mismatches += res.suggestions != want
    print(f"suggest: {len(probes)} calls in {time.perf_counter() - t0:.1f} s, "
          f"routes {routes}, oracle mismatches {mismatches}")
    check(mismatches == 0, f"suggest: {mismatches} answers differ")
    check(routes.get("suggest/device", 0) > 0, "no suggest call on device")


def print_counters(label: str, counters: dict, keys) -> None:
    print(f"{label} counters: " + ", ".join(
        f"{k} {counters[k]}" for k in keys))


def serve_one_chip(cfg: Config, seed: int) -> None:
    postings = build_postings(cfg, seed)
    t0 = time.perf_counter()
    engine = AsyncSearchEngine(postings, w=W, m=M, use_device=True,
                               flush_tier=FLUSH_TIER)
    nbytes = index_bytes(engine)
    print(f"index: {len(engine.device.sets)} device sets, {nbytes} bytes on "
          f"the device ({time.perf_counter() - t0:.1f} s)")
    print_memory()
    check(nbytes >= cfg.min_index_bytes,
          f"index holds {nbytes} bytes, under {cfg.min_index_bytes}")
    conj, exprs, queries = draw_traffic(cfg, sorted(postings), seed + 1)
    warm(engine, conj + exprs, cfg)
    EXEC_COUNTERS.reset()
    results = serve(engine, queries)
    routes = check_answers(postings, queries, results, "queries")
    print_counters("serving", EXEC_COUNTERS.snapshot(), (
        "batch_calls", "batch_traces", "rerun_calls", "expr_calls",
        "expr_traces", "expr_rerun_calls", "result_cache_hits"))
    check(routes.get("rangroupscan/device", 0) > 0,
          "no conjunctive query served on the device")
    check(routes.get("expr/device", 0) > 0,
          "no expression served on the device")
    check_kernel_in_bucket(engine, queries, results)
    suggest_phase(postings, cfg)
    print_memory()


def check_row_placement(engine, topology) -> None:
    """Every replica row's mirrors live on that row's devices, and the
    rows together use every device of the mesh."""
    used = set()
    for r in range(topology.replicas):
        row_devs = set(topology.replica_devices(r))
        anchor = {topology.replica_device(r)}
        plain = list(engine.device.replica_sets[r].values())
        mesh = [m.rows[r] for m in engine.device.sharded_sets.values()]
        check(plain and mesh, f"row {r} has no plain or no mesh mirrors")
        for ds in plain:
            check(ds.vals.devices() == anchor and ds.images.devices() == anchor,
                  f"a row-{r} mirror is off its anchor device")
        for ds in mesh:
            check(ds.vals.sharding.device_set == row_devs
                  and ds.images.sharding.device_set == row_devs,
                  f"a row-{r} mesh mirror is off the row's devices")
        check(not used & row_devs, "two replica rows share a device")
        used |= row_devs
        nbytes = sum(ds.vals.nbytes + ds.images.nbytes for ds in plain + mesh)
        print(f"row {r}: {len(plain)} plain and {len(mesh)} mesh mirrors, "
              f"{nbytes} bytes, on {sorted(d.id for d in row_devs)}")
    check(used == set(topology.mesh.devices.flat), "a mesh device is unused")


def serve_four_chips(cfg: Config, seed: int) -> None:
    postings = build_postings(cfg, seed)
    topology = make_topology(2, 2)
    mesh_engine = AsyncSearchEngine(postings, w=W, m=M, flush_tier=FLUSH_TIER,
                                    topology=topology)
    one_engine = AsyncSearchEngine(postings, w=W, m=M, use_device=True,
                                   flush_tier=FLUSH_TIER)
    for label, eng in (("2x2", mesh_engine), ("one chip", one_engine)):
        print(f"index ({label}): {len(eng.device.sets)} device sets, "
              f"{index_bytes(eng)} bytes on device 0")
    conj, exprs, queries = draw_traffic(cfg, sorted(postings), seed + 1)
    warm(mesh_engine, conj + exprs, cfg)
    EXEC_COUNTERS.reset()
    mesh_results = serve(mesh_engine, queries)
    snap = EXEC_COUNTERS.snapshot()
    EXEC_COUNTERS.reset()
    one_results = serve(one_engine, queries)
    traces = ("batch_traces", "mesh2d_traces", "expr_traces")
    print_counters("2x2 serving", snap, traces)
    print_counters("one-chip serving", EXEC_COUNTERS.snapshot(), traces)
    routes = check_answers(postings, queries, mesh_results, "2x2 topology")
    check_answers(postings, queries, one_results, "one chip")
    differ = sum(not np.array_equal(a.doc_ids, b.doc_ids)
                 for a, b in zip(mesh_results, one_results))
    check(differ == 0, f"{differ} answers differ between 2x2 and one chip")
    rows = [d["dispatched"] for d in topology.load_snapshot()]
    print(f"2x2: mesh2d_calls {snap['mesh2d_calls']}, mesh2d_row_dispatches "
          f"{snap['mesh2d_row_dispatches']}, replica_dispatches "
          f"{snap['replica_dispatches']} (per row {rows})")
    check(snap["mesh2d_calls"] > 0, "no bucket ran on the 2-D mesh")
    check(snap["replica_dispatches"] > 0 and all(rows),
          "a replica row received no dispatch")
    check(any(r.endswith("/mesh2d") for r in routes), "no mesh2d route")
    check_row_placement(mesh_engine, topology)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    devices = jax.devices()
    check(devices[0].platform == "tpu",
          f"no TPU: JAX found {devices[0].platform} devices")
    check(len(devices) >= args.chips,
          f"{args.chips} chips asked for, {len(devices)} found")
    print(f"devices: {len(devices)} x {devices[0].device_kind}")
    cache = configure_compile_cache(multi_device=args.chips > 1)
    print(f"compile cache: {cache or 'off (programs span several devices)'}")
    t0 = time.perf_counter()
    if args.chips == 4:
        serve_four_chips(FOUR_CHIPS, args.seed)
    else:
        serve_one_chip(ONE_CHIP, args.seed)
    print(f"total: {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
