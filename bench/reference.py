"""The comparison that decides ``correct``.

Each request kind (``bench/kinds/``) has its own plain reference, which
imports nothing of the program and uses nothing the program made, and its
own control.  Here every answer is compared with the reference's, and
the differences are counted by how the request failed.
"""
from __future__ import annotations

from typing import Dict, Hashable, Iterable, Tuple

import numpy as np

# every number compared, with its limit: an exact comparison has limit 0
LIMITS = {"mismatched": 0, "errored": 0, "unresolved": 0}


def compare(answers: Iterable[Tuple[Hashable, object]],
            truth: Dict[Hashable, np.ndarray]):
    """Count the answers that differ from ``truth``.

    ``answers`` yields ``(request, answer)``; ``answer`` is None for a
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
