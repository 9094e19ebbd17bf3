"""The plain reference against a naive scan and a direct count, at four
symbols and at bytes, and against the served path at a tiny size, for all
four endpoints (Pallas kernels in interpret mode); the synthetic corpus
plug-in, and the cells' collections as the program sees them."""

import hashlib

import numpy as np
import pytest

from bench import corpus, harness, spec
from bench.reference import Reference

synthetic = spec.load_module(spec.BENCH_DIR / "corpora" / "synthetic.py")

TINY = {"family": "version", "n_base": 2, "n_variants": 3, "base_len": 120,
        "mutation_rate": 0.01, "alphabet": "acgt"}

#: words of a UTF-8 text: letters of two and three bytes, the lead bytes
#: 0xC3, 0xC5 and 0xE2 among them
WORDS = ("äitiään", "ja", "isä", "sääntö", "öljyä", "yö", "tänään", "€uro",
         "Åbo", "kő", "hyvää", "päivää")


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
    return synthetic.generate(TINY, np.random.default_rng([2**31 + 7, 0]))


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


def _byte_docs(rng, n_docs=4, n_words=80) -> list[np.ndarray]:
    return [np.frombuffer(" ".join(rng.choice(WORDS, n_words)).encode(), np.uint8)
            for _ in range(n_docs)]


def direct_positions(docs, pat) -> list[int]:
    """Every start of ``pat`` in the documents, each laid out after the
    last one and its separator, by comparing at every position."""
    out, start, m = [], 0, len(pat)
    for doc in docs:
        out += [start + p for p in range(len(doc) - m + 1)
                if np.array_equal(doc[p:p + m], pat)]
        start += len(doc) + 1
    return out


@pytest.mark.parametrize("m", [6, 8, 11])
@pytest.mark.parametrize("alphabet", [4, 256])
def test_occurrences_match_a_direct_count(alphabet, m):
    """Patterns taken from the text, half of them (at bytes) starting at a
    byte of 0x80 or above, and patterns of random symbols."""
    rng = np.random.default_rng([2**31 + 101, alphabet, m])
    if alphabet == 4:
        docs = synthetic.generate(TINY, rng)
    else:
        docs = _byte_docs(rng)
    ref = Reference(docs, alphabet)
    pats = []
    for i in range(24):
        d = int(rng.integers(0, len(docs)))
        starts = np.arange(len(docs[d]) - m + 1)
        if alphabet == 256 and i % 2:
            starts = starts[docs[d][starts] >= 0x80]
        p = int(rng.choice(starts))
        pats.append(docs[d][p:p + m])
    pats += [rng.integers(0, alphabet, m) for _ in range(8)]
    if alphabet == 256:
        assert sum(int(p[0]) >= 0x80 for p in pats) >= 12
    for pat in pats:
        assert ref.occurrences(pat).tolist() == direct_positions(docs, pat)


def test_a_word_that_starts_above_0x7f_is_counted():
    """Keys of more than seven bytes in base 257 overflow int64; the word of
    ten bytes and its phrase of 13 are counted in full."""
    texts = ["äitiään ja isä äitiään äitiään ja", "sano äitiään ja äitiään",
             "äitiään ääni"]
    docs = [np.frombuffer(t.encode(), np.uint8) for t in texts]
    ref = Reference(docs, 256)
    for word, occ, df in (("äitiään", 6, 3), ("äitiään ja", 3, 2), ("ääni", 1, 1)):
        pat = np.frombuffer(word.encode(), np.uint8)
        assert (ref.occ(pat), ref.count(pat)) == (occ, df), word
        assert ref.occurrences(pat).tolist() == direct_positions(docs, pat)


def test_generator_is_seeded_and_shaped():
    a = synthetic.generate(TINY, np.random.default_rng([5, 0]))
    b = synthetic.generate(TINY, np.random.default_rng([5, 0]))
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert len(a) == 6 and all(len(x) == 120 for x in a)
    assert synthetic.alphabet_size(TINY) == 4
    concat = synthetic.generate(dict(TINY, family="concat"), np.random.default_rng([5, 0]))
    assert len(concat) == 2 and all(len(x) == 360 for x in concat)


#: sha256 of each cell's collection as ``concat_documents`` lays it out
#: (documents shifted by one, each followed by its terminator 0; int32),
#: recorded from the generator before it became a corpus plug-in
COLLECTIONS = {
    "version-p001.mixed-open":
        "2985162646d48240e78786656b4f75e5fbfe9d4ab105d336c9656f4bb1d7798d",
    "concat-p003.mixed-open":
        "8fc8eeaba059299d99460ac98d1161f569f634b19792f4e7e1f3afc8ce69aed6",
}


@pytest.mark.parametrize("workload", sorted(COLLECTIONS))
def test_the_collections_did_not_move(workload):
    from repro.core.suffix import concat_documents

    cell = spec.load_cell(workload)
    docs, alphabet_size = harness.collection(cell)
    assert alphabet_size == 4
    text = np.ascontiguousarray(concat_documents(docs).text, "<i4")
    assert hashlib.sha256(text.tobytes()).hexdigest() == COLLECTIONS[workload]


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
