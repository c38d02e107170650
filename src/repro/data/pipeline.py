"""Deterministic, stateless data pipeline (index-addressable batches).

``batch_at(step)`` is a pure function of (seed, step) — resume after a
restart is exact with no iterator state to persist beyond the step counter
(recorded in the checkpoint manifest).  Tokens come from a splitmix-style
integer hash, giving an unbounded, reproducible synthetic stream; a Zipf
corpus generator provides realistic document data for the dedup/search
substrates.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterator, List, Optional, Sequence

import numpy as np


def _splitmix(x: np.ndarray) -> np.ndarray:
    x = (x + np.uint64(0x9E3779B97F4A7C15)) & np.uint64(0xFFFFFFFFFFFFFFFF)
    z = x
    mask64 = np.uint64(0xFFFFFFFFFFFFFFFF)
    z = ((z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)) & mask64
    z = ((z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)) & mask64
    return z ^ (z >> np.uint64(31))


@dataclasses.dataclass(frozen=True)
class SyntheticLMData:
    vocab: int
    batch: int
    seq: int
    seed: int = 0

    def batch_at(self, step: int) -> Dict[str, np.ndarray]:
        # Learnable-but-unbounded stream: within each 16-token run the next
        # token is the affine map (31*t + 7) mod V of the previous one; run
        # starts are splitmix-hashed (deterministic in (seed, step, index)).
        n = self.batch * (self.seq + 1)
        base = np.arange(n, dtype=np.uint64) + np.uint64(step) * np.uint64(n) \
            + (np.uint64(self.seed) << np.uint64(40))
        starts = (_splitmix(base) % np.uint64(self.vocab)).astype(np.int64)
        starts = starts.reshape(self.batch, self.seq + 1)
        toks = starts.copy()
        pos_in_run = np.arange(self.seq + 1) % 16
        for j in range(1, self.seq + 1):
            if pos_in_run[j] == 0:
                continue
            toks[:, j] = (toks[:, j - 1] * 31 + 7) % self.vocab
        toks = toks.astype(np.int32)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        step = 0
        while True:
            yield self.batch_at(step)
            step += 1


# documents per vectorized block of :func:`zipf_corpus`: bounds the
# block's (doc, term) key array while keeping per-block overhead small
_CORPUS_BLOCK_DOCS = 1 << 16


def zipf_corpus(n_docs: int, vocab: int = 50000, mean_len: int = 200,
                alpha: float = 1.2, seed: int = 0) -> List[np.ndarray]:
    """Documents as arrays of term-ids with a Zipf unigram distribution —
    produces realistically skewed posting-list lengths for the search
    engine (frequent terms -> long lists, as in the paper's Bing data).

    Each document draws ``Poisson(mean_len)`` (at least 8) Zipf terms,
    folded into ``[0, vocab)`` and deduplicated into a sorted array.  The
    draws come in blocks of documents, one ``rng.zipf`` call per block;
    the generator's stream is the same as one call per document, so the
    corpus depends only on the arguments."""
    rng = np.random.default_rng(seed)
    lengths = rng.poisson(mean_len, size=n_docs).clip(min=8)
    docs: List[np.ndarray] = []
    for lo in range(0, n_docs, _CORPUS_BLOCK_DOCS):
        lens = lengths[lo:lo + _CORPUS_BLOCK_DOCS]
        terms = (rng.zipf(alpha, size=int(lens.sum())) - 1) % vocab
        doc = np.repeat(np.arange(len(lens), dtype=np.int64), lens)
        key = np.unique(doc * vocab + terms)   # sorted by (doc, term), deduped
        bounds = np.searchsorted(key, np.arange(1, len(lens)) * vocab)
        docs.extend(np.split((key % vocab).astype(np.uint32), bounds))
    return docs


def inverted_index(docs: Sequence[np.ndarray]) -> Dict[int, np.ndarray]:
    """term -> sorted array of doc ids, keyed in order of each term's
    first appearance."""
    lens = np.fromiter((len(d) for d in docs), dtype=np.int64, count=len(docs))
    terms = (np.concatenate(docs) if len(docs)
             else np.empty(0, dtype=np.uint32))
    doc_ids = np.repeat(np.arange(len(docs), dtype=np.uint32), lens)
    order = np.argsort(terms, kind="stable")   # doc ids stay ascending per term
    by_term = terms[order]
    starts = np.flatnonzero(np.diff(by_term, prepend=-1))
    uniq = by_term[starts]
    lists = np.split(doc_ids[order], starts[1:])
    first_seen = np.argsort(order[starts], kind="stable")
    return {int(uniq[i]): lists[i] for i in first_seen}
