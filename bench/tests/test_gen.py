"""The generator: lengths fixed by the configuration, documents and order
by the seed, every seed the same multiset of work."""
import json

import numpy as np
import pytest

from bench import gen, harness

TINY = {"documents": 20000, "vocabulary": 30000, "zipf_alpha": 1.2294,
        "tokens_per_doc": 72.55, "posting_band": [16, 800]}
MIX = {"k_mix": [[2, 0.68], [3, 0.23], [4, 0.09]], "min_postings": 400,
       "distinct_queries": 300, "pool_seed": 7}
BIG = 2**33 + 12345   # seeds reach past 32 bits


def test_lists_are_exact_sorted_distinct_and_seeded():
    ids, lens = gen.band_lengths(TINY)
    a = gen.posting_lists(TINY, BIG)
    b = gen.posting_lists(TINY, BIG)
    c = gen.posting_lists(TINY, BIG + 1)
    assert sorted(a) == ids.tolist()
    for term, n in zip(ids, lens):
        x = a[int(term)]
        assert len(x) == n and len(np.unique(x)) == n
        assert np.all(np.diff(x.astype(np.int64)) > 0)
        assert x.dtype == np.uint32 and int(x.max()) < TINY["documents"]
        np.testing.assert_array_equal(x, b[int(term)])
        assert len(c[int(term)]) == n
    assert any(not np.array_equal(a[t], c[t]) for t in a)


def test_band_is_every_rank_whose_frequency_lies_in_it():
    ids, lens = gen.band_lengths(TINY)
    ranks = np.arange(1, TINY["vocabulary"] + 1, dtype=np.float64)
    df = np.floor(TINY["documents"] * gen._doc_share(TINY, ranks))
    lo, hi = TINY["posting_band"]
    want = np.flatnonzero((df >= lo) & (df <= hi))
    np.testing.assert_array_equal(ids, want)
    np.testing.assert_array_equal(lens, df[want])
    assert np.all(np.diff(lens) <= 0)       # ids follow frequency rank


@pytest.mark.parametrize("docs,terms,postings", [
    (20000, 30000, 800000), (24622347, 35636425, 5742630292)])
def test_fit_reproduces_the_collection(docs, terms, postings):
    alpha, tokens = gen.fit_zipf(docs, terms, postings)
    cfg = {"zipf_alpha": alpha, "tokens_per_doc": tokens,
           "vocabulary": terms}
    held = docs * gen._rank_sum(
        lambda r: gen._doc_share(cfg, r), terms)
    assert held == pytest.approx(postings, rel=1e-3)
    rarest = docs * gen._doc_share(cfg, np.array([float(terms)]))[0]
    assert rarest == pytest.approx(1.0, rel=1e-3)


@pytest.mark.parametrize("name", [c["name"] for c in
                                  harness.benchmark()["configs"]])
def test_configuration_is_the_fit_of_its_collection(name):
    conf = next(c for c in harness.benchmark()["configs"]
                if c["name"] == name)
    with open(harness.ROOT / conf["file"]) as f:
        cfg = json.load(f)
    coll = cfg["collection"]
    alpha, tokens = gen.fit_zipf(coll["documents"], coll["terms"],
                                 coll["postings"])
    assert cfg["zipf_alpha"] == pytest.approx(alpha, rel=1e-6)
    assert cfg["tokens_per_doc"] == pytest.approx(tokens, rel=1e-6)
    assert cfg["vocabulary"] == coll["terms"]
    assert cfg["documents"] == coll["documents"] // cfg["shards"]
    assert cfg["posting_band"] == [16, int(0.04 * cfg["documents"])]


def test_pool_is_distinct_draws_from_the_long_lists_and_follows_the_mix():
    lists = gen.posting_lists(TINY, 5)
    terms = gen.query_terms(MIX, lists)
    assert terms and all(len(lists[t]) >= MIX["min_postings"]
                         for t in terms)
    assert len(terms) == sum(len(v) >= MIX["min_postings"]
                             for v in lists.values())
    pool = gen.query_pool(MIX, terms)
    assert len(set(pool)) == 300
    assert all(len(set(q)) == len(q) and list(q) == sorted(q) for q in pool)
    assert all(t in terms for q in pool for t in q)
    share2 = sum(len(q) == 2 for q in pool) / len(pool)
    assert 0.5 < share2 < 0.85
    assert pool == gen.query_pool(MIX, terms)


def test_log_is_one_multiset_in_seeded_order():
    terms = gen.query_terms(MIX, gen.posting_lists(TINY, 5))
    pool = gen.query_pool(MIX, terms)
    a = gen.build_log(MIX, pool, 100.0, 2.0, BIG)
    b = gen.build_log(MIX, pool, 100.0, 2.0, BIG)
    c = gen.build_log(MIX, pool, 100.0, 2.0, BIG + 1)
    assert len(a) == len(c) == 200
    np.testing.assert_array_equal(a.times, b.times)
    np.testing.assert_array_equal(a.which, b.which)
    assert not np.array_equal(a.times, c.times)
    assert np.all(np.diff(a.times) > 0) and 0 <= a.times[0]
    assert a.times[-1] < 2.0
    np.testing.assert_array_equal(np.sort(a.which), np.sort(c.which))
    gaps_a = np.sort(np.diff(np.concatenate([[0], a.times])))
    gaps_c = np.sort(np.diff(np.concatenate([[0], c.times])))
    assert np.allclose(np.median(gaps_a), np.median(gaps_c), rtol=0.1)


def test_log_is_a_miss_stream_up_to_the_pool_size():
    pool = gen.query_pool(MIX, gen.query_terms(MIX,
                                               gen.posting_lists(TINY, 5)))
    within = gen.build_log(MIX, pool, 150.0, 2.0, BIG)
    assert len(set(within.which.tolist())) == len(within) == 300
    past = gen.build_log(MIX, pool, 200.0, 2.0, BIG)
    counts = np.bincount(past.which, minlength=len(pool))
    assert counts.sum() == 400 and counts.max() == 2 and counts.min() == 1
