"""The plain reference against a naive scan, and against the served path at
a tiny size, for all four endpoints (Pallas kernels in interpret mode)."""

import numpy as np
import pytest

from bench import corpus
from bench.reference import Reference

TINY = {"family": "version", "n_base": 2, "n_variants": 3, "base_len": 120,
        "mutation_rate": 0.01, "alphabet": "acgt"}


def naive_doc_tf(docs, pat):
    out = {}
    m = len(pat)
    for i, doc in enumerate(docs):
        hits = sum(1 for p in range(len(doc) - m + 1)
                   if np.array_equal(doc[p:p + m], pat))
        if hits:
            out[i] = hits
    return out


@pytest.fixture(scope="module")
def docs():
    return corpus.generate(TINY, np.random.default_rng([2**31 + 7, 0]))


def test_occurrences_match_a_naive_scan(docs):
    ref = Reference(docs, 4)
    rng = np.random.default_rng(0)
    for m in (1, 3, 8, 11):
        for _ in range(6):
            d = int(rng.integers(0, len(docs)))
            p = int(rng.integers(0, len(docs[d]) - m))
            pat = docs[d][p:p + m]
            want = naive_doc_tf(docs, pat)
            got_docs, got_tf = ref.doc_tf(pat)
            assert dict(zip(got_docs.tolist(), got_tf.tolist())) == want
    assert ref.count(np.asarray([9, 9])) == 0       # out of the alphabet
    assert ref.list(np.zeros(0, np.int32)) == []


def test_generator_is_seeded_and_shaped():
    a = corpus.generate(TINY, np.random.default_rng([5, 0]))
    b = corpus.generate(TINY, np.random.default_rng([5, 0]))
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert len(a) == 6 and all(len(x) == 120 for x in a)
    concat = corpus.generate(dict(TINY, family="concat"), np.random.default_rng([5, 0]))
    assert len(concat) == 2 and all(len(x) == 360 for x in concat)


def test_reference_agrees_with_the_served_path(docs):
    from repro.core.suffix import concat_documents
    from repro.serve.retrieval import RetrievalService
    from repro.serve.runtime import RuntimeConfig, ServeRuntime

    ref = Reference(docs, 4)
    coll = concat_documents(docs)
    svc = RetrievalService.build(coll, block_size=64, beta=16.0,
                                 use_search_kernel=True, use_list_kernel=True)
    rng = np.random.default_rng(3)
    pats = []
    for m in (2, 4, 6, 6, 9):
        d = int(rng.integers(0, len(docs)))
        p = int(rng.integers(0, len(docs[d]) - m))
        pats.append(docs[d][p:p + m])
    terms = [[pats[i], pats[(i + 2) % len(pats)]] for i in range(len(pats))]
    max_buf = 1 << max(ref.occ(p) for p in pats).bit_length()
    rt = ServeRuntime(svc, RuntimeConfig(max_batch=8, k=3, max_df=coll.d + 1,
                                         max_buf=max_buf, default_deadline_s=1e9))
    reqs = ([("count", corpus.served_pattern(p)) for p in pats]
            + [("list", corpus.served_pattern(p)) for p in pats]
            + [("topk", corpus.served_pattern(p)) for p in pats]
            + [("tfidf", [corpus.served_pattern(t) for t in ts]) for ts in terms])
    answers = rt.serve(reqs)
    assert all(a.path == "full" and not a.degraded for a in answers)
    n = len(pats)
    assert [a.result for a in answers[:n]] == [ref.count(p) for p in pats]
    assert [a.result for a in answers[n:2 * n]] == [ref.list(p) for p in pats]
    assert [a.result for a in answers[2 * n:3 * n]] == [ref.topk(p, 3) for p in pats]
    for a, ts in zip(answers[3 * n:], terms):
        want = ref.tfidf(ts, 3)
        assert [d for d, _ in a.result] == [d for d, _ in want]
        np.testing.assert_allclose([s for _, s in a.result], [s for _, s in want],
                                   rtol=1e-6, atol=1e-6)
