"""Conjunctions: a request is a tuple of distinct terms, and its answer is
every document that holds all of them.

The data is the configuration's posting lists (``gen.posting_lists``),
the pool the traffic's k-term mix (``gen.query_pool``), the engine
``AsyncSearchEngine`` over the lists.  The reference is the intersection
of a conjunction's lists, by ``np.intersect1d``.

The configuration guarantees exact answers: every document of the
intersection and no other.  The control breaks that guarantee in the way
that would tempt a faster engine: it keeps the filter and drops the
verification, answering with the smallest list's documents whose hash bit
is set in a one-word-per-element bitmap of every other list (a Bloom
filter with one hash).  It keeps every true document and adds false
ones.
"""
from __future__ import annotations

import time
from functools import reduce
from typing import Dict, List, Sequence, Tuple

import numpy as np

from bench import gen
from bench.harness import log


def data(config: Dict, seed: int) -> Dict[int, np.ndarray]:
    return gen.posting_lists(config, seed)


def engine(config: Dict, lists: Dict[int, np.ndarray], obs):
    from repro.serve.search import AsyncSearchEngine

    eng_cfg = config["engine"]
    t = time.perf_counter()
    eng = AsyncSearchEngine(
        lists, w=eng_cfg["w"], m=eng_cfg["m"], use_device=True,
        deadline_us=eng_cfg["deadline_us"], flush_tier=eng_cfg["flush_tier"],
        max_inflight=eng_cfg["max_inflight"],
        result_cache=eng_cfg["result_cache"],
        hashbin_ratio=eng_cfg["hashbin_ratio"], obs=obs)
    built = time.perf_counter() - t
    phases = {"preprocess_s": eng.build_s,
              "device_sets_s": built - eng.build_s}
    n_post = sum(len(v) for v in lists.values())
    nbytes = sum(s.vals.nbytes + s.images.nbytes
                 for s in eng.device.sets.values())
    log(f"index: {len(lists)} terms, {n_post} postings; preprocess_prefix "
        f"{eng.build_s:.3f} s, {len(eng.device.sets)} device sets "
        f"{phases['device_sets_s']:.3f} s, {nbytes} bytes on the device")
    return eng, phases


def pool(traffic: Dict, lists: Dict[int, np.ndarray]) -> List[Tuple[int, ...]]:
    return gen.query_pool(traffic, gen.query_terms(traffic, lists))


def warm(eng, pool: Sequence[Tuple[int, ...]], tiers: Sequence[int]) -> str:
    from repro.core.engine import warm_executables

    sigs = eng.warm([list(q) for q in pool], top_k=len(pool), b_tiers=tiers)
    # a bucket whose survivors overflow its capacity re-runs them at full
    # capacity (one more program per signature and tier): warm that
    # program for every signature, whether or not this seed overflows
    reruns = {}
    for q in pool:
        plan = eng.plan(list(q))
        if plan.algorithm == "device":
            reruns.setdefault(plan.sig, plan.terms)
    for sig, q_terms in reruns.items():
        warm_executables([[eng.device.sets[str(x)] for x in q_terms]],
                         b_tiers=tiers, capacity=1 << sig.ts[-1],
                         use_pallas=eng.device.use_pallas)
    return f"{len(sigs)} signatures x tiers {list(tiers)}, and their re-runs"


def submit(eng, query: Tuple[int, ...], arrival_at):
    return eng.submit(list(query), arrival_at=arrival_at)


def answer(value) -> np.ndarray:
    return value.doc_ids


def route(value) -> str:
    return value.algorithm


def reference(lists: Dict[int, np.ndarray], query: Sequence[int]) -> np.ndarray:
    """Sorted documents that hold every term of ``query``."""
    return reduce(np.intersect1d, [lists[t] for t in query]).astype(np.uint32)


def _bloom_hash(x: np.ndarray, bits: int) -> np.ndarray:
    x = x.astype(np.uint64)
    x = (x * np.uint64(0x9E3779B97F4A7C15)) & np.uint64(0xFFFFFFFFFFFFFFFF)
    return (x >> np.uint64(32)) % np.uint64(bits)


def control(lists: Dict[int, np.ndarray], query: Sequence[int]) -> np.ndarray:
    """The reference with exactness broken: filter without verification."""
    sets = sorted((lists[t] for t in query), key=len)
    out = sets[0]
    for other in sets[1:]:
        bits = 32 * len(other)
        bitmap = np.zeros(bits, dtype=bool)
        bitmap[_bloom_hash(other, bits)] = True
        out = out[bitmap[_bloom_hash(out, bits)]]
    return out.astype(np.uint32)
