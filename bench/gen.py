"""Data and traffic of one cell, made from the configuration, the traffic
file and the run's seed.

What each input decides:

- The configuration fixes every posting list's length.  Document
  frequencies follow one Zipf law over the collection's term ranks, whose
  exponent and tokens per document are fitted (:func:`fit_zipf`) to the
  collection's published document, term and posting counts; the shard
  holds ``documents`` of them, and the terms whose frequency lies in the
  posting band are the index.  So the lengths, and with them the shape
  signatures the engine compiles, are the same for every seed.
- The traffic file fixes the log's distinct conjunctions (from its own
  ``pool_seed``) and the multiset of gaps between arrivals.
- The run's seed chooses the documents in every list and the order in
  which the log's requests and gaps arrive.  Every seed therefore serves
  the same set of sizes and arrivals, in another order.

The k-term draw follows ``repro.serve.loadgen.QueryMix`` (the paper's
k-term mix), copied here so that the yardstick does not move when the
program does, with one departure: a k-term query always holds k distinct
terms (the original could fold repeats into a shorter query).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Sequence, Tuple

import numpy as np

# independent streams of one run seed
_DOCS, _ORDER = 0, 1
_HEAD = 10_000        # ranks summed exactly; the tail is integrated


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """A generator for one stream of a run seed (any whole number)."""
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) % (1 << 64), stream]))


def _rank_sum(f, ranks: int) -> float:
    """``sum(f(r) for r in 1..ranks)``: exact over the first ranks, then
    the trapezoid rule on a logarithmic grid (``f`` is smooth there)."""
    head = np.arange(1, min(ranks, _HEAD) + 1, dtype=np.float64)
    total = float(f(head).sum())
    if ranks > _HEAD:
        x = np.linspace(math.log(_HEAD + 0.5), math.log(ranks + 0.5), 4001)
        r = np.exp(x)
        total += float(np.trapezoid(f(r) * r, x))
    return total


def _doc_share(cfg: Dict, ranks: np.ndarray) -> np.ndarray:
    """The share of documents that hold each term rank.

    A document draws ``tokens_per_doc`` tokens, rank ``r`` with
    probability ``q_r`` proportional to ``r ** -zipf_alpha`` over the
    collection's ``vocabulary``; term ``r`` is in ``1 - exp(-L q_r)`` of
    the documents.
    """
    alpha = cfg["zipf_alpha"]
    norm = _rank_sum(lambda r: r ** -alpha, cfg["vocabulary"])
    return -np.expm1(-cfg["tokens_per_doc"] * ranks ** -alpha / norm)


def fit_zipf(documents: int, terms: int, postings: int) -> Tuple[float, float]:
    """``(zipf_alpha, tokens_per_doc)`` that reproduce a collection.

    Two conditions fix the two numbers: the documents hold ``postings``
    (document, term) pairs, ``postings / documents`` distinct terms each;
    and the vocabulary is ``terms`` long, its rarest term expected in one
    document.
    """
    target = postings / documents

    def tokens_for(alpha: float) -> float:
        norm = _rank_sum(lambda r: r ** -alpha, terms)
        lo, hi = 1.0, 1e12
        for _ in range(80):
            tokens = math.sqrt(lo * hi)
            held = _rank_sum(lambda r: -np.expm1(-tokens * r ** -alpha / norm),
                             terms)
            lo, hi = (tokens, hi) if held < target else (lo, tokens)
        return tokens

    lo, hi = 0.5, 3.0
    for _ in range(40):
        alpha = 0.5 * (lo + hi)
        cfg = {"zipf_alpha": alpha, "tokens_per_doc": tokens_for(alpha),
               "vocabulary": terms}
        rarest = documents * _doc_share(cfg, np.array([float(terms)]))[0]
        lo, hi = (alpha, hi) if rarest > 1.0 else (lo, alpha)
    return alpha, cfg["tokens_per_doc"]


def band_lengths(cfg: Dict) -> Tuple[np.ndarray, np.ndarray]:
    """(term ids, list lengths) of the shard's terms inside the posting
    band.  A term's id is its rank minus one, so lower ids are more
    frequent."""
    lo, hi = cfg["posting_band"]
    n_docs = cfg["documents"]
    alpha = cfg["zipf_alpha"]
    norm = _rank_sum(lambda r: r ** -alpha, cfg["vocabulary"])

    def rank_at(share: float) -> float:      # the rank held by ``share``
        return (cfg["tokens_per_doc"] / (norm * -math.log1p(-share))) \
            ** (1.0 / alpha)

    first = max(1, int(rank_at(min(1.0, (hi + 1) / n_docs) - 1e-12)) - 2)
    last = min(cfg["vocabulary"], int(rank_at(lo / n_docs)) + 2)
    ranks = np.arange(first, last + 1, dtype=np.float64)
    df = np.floor(n_docs * _doc_share(cfg, ranks)).astype(np.int64)
    keep = (df >= lo) & (df <= hi)
    return ranks[keep].astype(np.int64) - 1, df[keep]


def posting_lists(cfg: Dict, seed: int) -> Dict[int, np.ndarray]:
    """Every band term's sorted list of document ids, drawn from ``seed``.

    A list of length ``n`` over ``N`` documents takes one document from
    each of ``n`` equal strata of ``[0, N)``, uniformly within the
    stratum: exactly ``n`` distinct ids, independent across terms, so two
    lists meet in about ``n1 * n2 / N`` documents.
    """
    rng = seed_rng(seed, _DOCS)
    n_docs = cfg["documents"]
    out = {}
    for term, n in zip(*band_lengths(cfg)):
        edges = np.arange(n + 1, dtype=np.int64) * n_docs // n
        width = np.diff(edges)
        ids = edges[:-1] + (rng.random(n) * width).astype(np.int64)
        out[int(term)] = ids.astype(np.uint32)
    return out


@dataclasses.dataclass(frozen=True)
class Log:
    """One run's open-loop traffic: when each request is due (seconds
    from the window's start) and which distinct conjunction it asks."""

    pool: Tuple[Tuple[int, ...], ...]   # distinct conjunctions
    times: np.ndarray                   # (n,) ascending, in [0, seconds)
    which: np.ndarray                   # (n,) index into pool
    seconds: float

    def __len__(self) -> int:
        return len(self.times)


def query_terms(traffic: Dict, lists: Dict[int, np.ndarray]) -> List[int]:
    """The index's terms the mix draws from: those whose lists hold at
    least ``min_postings`` documents, by id."""
    return sorted(t for t, v in lists.items()
                  if len(v) >= traffic["min_postings"])


def query_pool(traffic: Dict, terms: Sequence[int]) -> List[Tuple[int, ...]]:
    """The mix's ``distinct_queries`` distinct conjunctions.

    Fixed by the traffic file: each draws k from ``k_mix`` and then k
    distinct terms uniformly from ``terms``, from ``pool_seed``.
    """
    rng = np.random.default_rng(traffic["pool_seed"])
    terms = np.asarray(sorted(terms))
    ks, ps = zip(*traffic["k_mix"])
    ps = np.asarray(ps, dtype=np.float64) / sum(ps)
    if max(ks) > len(terms):
        raise ValueError(f"{len(terms)} terms cannot make {max(ks)}-term "
                         "conjunctions")
    pool, seen = [], set()
    while len(pool) < traffic["distinct_queries"]:
        k = int(rng.choice(ks, p=ps))
        q = tuple(sorted(int(t) for t in rng.choice(terms, k, replace=False)))
        if q not in seen:
            seen.add(q)
            pool.append(q)
    return pool


def build_log(traffic: Dict, pool: Sequence[Tuple[int, ...]],
              rate_qps: float, seconds: float, seed: int) -> Log:
    """``round(rate_qps * seconds)`` Poisson-like arrivals in
    ``[0, seconds)``.

    The requests are the pool's first entries, each asked once (the pool
    is repeated from its start only where the window holds more requests
    than it), and the gaps between arrivals are exponential, from
    ``pool_seed``, scaled to fill the window: both multisets are fixed by
    the traffic file, the rate and the window, and ``seed`` shuffles them.
    """
    n = max(1, int(round(rate_qps * seconds)))
    fixed = np.random.default_rng([traffic["pool_seed"], n])
    gaps = fixed.exponential(1.0, size=n + 1)
    rng = seed_rng(seed, _ORDER)
    gaps = gaps[rng.permutation(n + 1)]
    times = np.cumsum(gaps)[:n] * (seconds / gaps.sum())
    which = np.arange(n) % len(pool)
    return Log(pool=tuple(pool), times=times, which=which[rng.permutation(n)],
               seconds=float(seconds))
