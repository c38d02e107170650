"""The plain reference, its control, and the comparison that decides
``correct``.

The reference is set arithmetic over the benchmark's own generated
lists: the intersection of a conjunction's lists, by ``np.intersect1d``.
It imports nothing of the program and uses nothing the program made.

The configuration guarantees exact answers: every document of the
intersection and no other.  The control breaks that guarantee in the way
that would tempt a faster engine: it keeps the filter and drops the
verification, answering with the smallest list's documents whose hash bit
is set in a one-word-per-element bitmap of every other list (a Bloom
filter with one hash).  It keeps every true document and adds false
ones.
"""
from __future__ import annotations

from functools import reduce
from typing import Dict, Iterable, Sequence, Tuple

import numpy as np

# every number compared, with its limit: an exact comparison has limit 0
LIMITS = {"mismatched": 0, "errored": 0, "unresolved": 0}


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


def compare(answers: Iterable[Tuple[Tuple[int, ...], object]],
            truth: Dict[Tuple[int, ...], np.ndarray]):
    """Count the answers that differ from ``truth``.

    ``answers`` yields ``(query, doc_ids)``; ``doc_ids`` is None for a
    request that never resolved and an exception for one that resolved
    with an error.  Returns the counts and, per answer, whether it was
    right.
    """
    counts = dict.fromkeys(LIMITS, 0)
    ok = []
    for query, got in answers:
        if got is None:
            kind = "unresolved"
        elif isinstance(got, BaseException):
            kind = "errored"
        elif not np.array_equal(np.asarray(got, dtype=np.uint32),
                                truth[query]):
            kind = "mismatched"
        else:
            kind = None
        if kind:
            counts[kind] += 1
        ok.append(kind is None)
    return counts, np.array(ok, dtype=bool)


def verdict(counts: Dict[str, int]) -> bool:
    return all(counts[k] <= limit for k, limit in LIMITS.items())


def compared_block(counts: Dict[str, int]) -> Dict[str, Dict[str, int]]:
    """Each number compared beside its limit, for the result line."""
    return {k: {"value": int(counts[k]), "limit": LIMITS[k]} for k in LIMITS}
