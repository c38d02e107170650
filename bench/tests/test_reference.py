"""The reference against brute force, and the control against the
reference."""
import numpy as np

from bench import gen, reference
from bench.kinds import conj

TINY = {"documents": 5000, "vocabulary": 30000, "zipf_alpha": 1.2294,
        "tokens_per_doc": 72.55, "posting_band": [16, 400]}
MIX = {"k_mix": [[2, 0.68], [3, 0.23], [4, 0.09]], "min_postings": 100,
       "distinct_queries": 40, "pool_seed": 3}


def _setup():
    lists = gen.posting_lists(TINY, 99)
    return lists, gen.query_pool(MIX, gen.query_terms(MIX, lists))


def test_reference_matches_brute_force():
    lists, pool = _setup()
    for q in pool:
        want = sorted(set.intersection(*(set(lists[t].tolist()) for t in q)))
        got = conj.reference(lists, q)
        assert got.dtype == np.uint32 and got.tolist() == want


def test_control_keeps_every_true_document_and_adds_false_ones():
    lists, pool = _setup()
    extra = 0
    for q in pool:
        truth = conj.reference(lists, q)
        ctl = conj.control(lists, q)
        assert np.isin(truth, ctl).all()
        extra += len(ctl) - len(truth)
    assert extra > 0


def test_compare_counts_each_kind_of_failure():
    lists, pool = _setup()
    truth = {q: conj.reference(lists, q) for q in pool}
    q1 = next(q for q in pool if len(truth[q]))
    q0, q2, q3 = [q for q in pool if q != q1][:3]
    answers = [(q0, truth[q0]), (q1, truth[q1][:-1]), (q2, None),
               (q3, RuntimeError("bucket failed"))]
    counts, ok = reference.compare(answers, truth)
    assert counts == {"mismatched": 1, "errored": 1, "unresolved": 1}
    assert ok.tolist() == [True, False, False, False]
    assert not reference.verdict(counts)
    assert reference.verdict(reference.compare(answers[:1], truth)[0])
    block = reference.compared_block(counts)
    assert block["mismatched"] == {"value": 1, "limit": 0}
