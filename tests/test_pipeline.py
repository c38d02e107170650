"""The vectorized corpus builders against plain per-document loops."""
from collections import defaultdict

import numpy as np
import pytest

from repro.data import pipeline
from repro.data.pipeline import inverted_index, zipf_corpus


def _zipf_corpus_loop(n_docs, vocab=50000, mean_len=200, alpha=1.2, seed=0):
    rng = np.random.default_rng(seed)
    docs = []
    lengths = rng.poisson(mean_len, size=n_docs).clip(min=8)
    for i in range(n_docs):
        terms = rng.zipf(alpha, size=lengths[i])
        docs.append(np.unique((terms - 1) % vocab).astype(np.uint32))
    return docs


def _inverted_index_loop(docs):
    post = defaultdict(list)
    for doc_id, terms in enumerate(docs):
        for t in terms.tolist():
            post[t].append(doc_id)
    return {t: np.asarray(sorted(ids), dtype=np.uint32)
            for t, ids in post.items()}


@pytest.mark.parametrize("n_docs,vocab,mean_len,alpha,seed", [
    (1, 50, 5, 1.2, 0),
    (300, 2000, 40, 1.2, 1),
    (257, 700, 25, 1.1, 7),
    (0, 100, 10, 1.2, 3),
])
def test_zipf_corpus_matches_per_document_loop(n_docs, vocab, mean_len,
                                               alpha, seed):
    got = zipf_corpus(n_docs, vocab=vocab, mean_len=mean_len, alpha=alpha,
                      seed=seed)
    want = _zipf_corpus_loop(n_docs, vocab=vocab, mean_len=mean_len,
                             alpha=alpha, seed=seed)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == np.uint32
        np.testing.assert_array_equal(g, w)


def test_zipf_corpus_blocks_do_not_change_the_corpus(monkeypatch):
    want = zipf_corpus(100, vocab=500, mean_len=20, seed=5)
    monkeypatch.setattr(pipeline, "_CORPUS_BLOCK_DOCS", 7)
    got = zipf_corpus(100, vocab=500, mean_len=20, seed=5)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("docs", [
    "zipf",
    "unsorted_with_repeats",
    "empty_docs",
])
def test_inverted_index_matches_per_document_loop(docs):
    if docs == "zipf":
        docs = zipf_corpus(400, vocab=3000, mean_len=30, seed=2)
    elif docs == "unsorted_with_repeats":
        docs = [np.asarray(d, np.uint32) for d in
                ([5, 3, 5, 9], [], [9, 1], [3], [7, 7, 7])]
    else:
        docs = [np.empty(0, np.uint32)] * 3
    got = inverted_index(docs)
    want = _inverted_index_loop(docs)
    assert list(got) == list(want)          # same keys, same order
    for t in want:
        assert got[t].dtype == np.uint32
        np.testing.assert_array_equal(got[t], want[t])
