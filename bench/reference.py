"""Plain reference for the four retrieval endpoints, over the raw documents.

It finds a pattern's occurrences by direct string matching and answers
from those positions alone.  A table of the text's k-mers, each keyed by its
exact value in base ``alphabet_size + 1``, narrows the candidates to those
that match the pattern's first ``min(m, k)`` symbols; each is then compared
on the rest, symbol by symbol.  k is the longest, up to ``KMAX``, whose keys
fit in int64 (``base**k < 2**63``): 8 at base 5, 7 at base 257 (bytes).  It
imports nothing of the program and uses nothing the program built:

* ``count``: df, the number of documents that hold the pattern;
* ``list``: every such document, ascending;
* ``topk``: the k documents with the most occurrences, as (doc, tf), ranked
  by tf descending and doc ascending;
* ``tfidf``: documents ranked by sum_t tf(D, t) * lg(d / max(df_t, 1)),
  score descending and doc ascending (ranked OR, or ranked AND), in float64.

Patterns are raw symbol arrays, as a corpus plug-in's ``generate`` makes
documents.
"""

from __future__ import annotations

import numpy as np

#: longest prefix that the k-mer table keys on
KMAX = 8


def key_length(base: int) -> int:
    """The longest k up to ``KMAX`` whose base-``base`` keys are exact in
    int64."""
    return max(k for k in range(1, KMAX + 1) if base ** k < 2 ** 63)


class Reference:
    def __init__(self, docs: list[np.ndarray], alphabet_size: int, pad: int = 4096):
        self.d = len(docs)
        self.base = alphabet_size + 1          # the separator is ``alphabet_size``
        self.kmax = key_length(self.base)
        sep = np.full(1, alphabet_size, np.int64)
        parts = []
        for doc in docs:
            parts += [np.asarray(doc, np.int64), sep]
        parts.append(np.full(pad, alphabet_size, np.int64))
        self.text = np.concatenate(parts)
        lens = np.asarray([len(doc) + 1 for doc in docs], np.int64)
        self.doc_starts = np.concatenate([[0], np.cumsum(lens)[:-1]])
        self.n = int(lens.sum())               # symbols, separators included
        self._tables: dict[int, tuple[np.ndarray, np.ndarray]] = {}
        self._memo: dict[bytes, tuple[np.ndarray, np.ndarray]] = {}

    def _table(self, k: int):
        if k not in self._tables:
            codes = np.zeros(self.n, np.int64)
            for i in range(k):
                codes = codes * self.base + self.text[i: i + self.n]
            order = np.argsort(codes, kind="stable")
            self._tables[k] = (codes[order], order)
        return self._tables[k]

    def occurrences(self, pattern) -> np.ndarray:
        """Ascending text positions where ``pattern`` starts."""
        pat = np.asarray(pattern, np.int64)
        m = len(pat)
        if m == 0 or pat.min() < 0 or pat.max() >= self.base - 1:
            return np.zeros(0, np.int64)       # empty, or not of the alphabet
        k = min(m, self.kmax)
        codes, order = self._table(k)
        key = 0
        for s in pat[:k]:
            key = key * self.base + int(s)
        lo, hi = np.searchsorted(codes, [key, key + 1])
        cand = np.sort(order[lo:hi])
        if m > k and cand.size:
            window = self.text[cand[:, None] + np.arange(k, m)]
            cand = cand[(window == pat[k:]).all(axis=1)]
        return cand

    def doc_tf(self, pattern) -> tuple[np.ndarray, np.ndarray]:
        """(documents ascending, occurrences in each)."""
        key = np.asarray(pattern, np.int64).tobytes()
        if key not in self._memo:
            docs = np.searchsorted(self.doc_starts, self.occurrences(pattern),
                                   side="right") - 1
            self._memo[key] = np.unique(docs, return_counts=True)
        return self._memo[key]

    def occ(self, pattern) -> int:
        return int(self.doc_tf(pattern)[1].sum())

    def count(self, pattern) -> int:
        return len(self.doc_tf(pattern)[0])

    def list(self, pattern) -> list[int]:
        return self.doc_tf(pattern)[0].tolist()

    def topk(self, pattern, k: int) -> list[tuple[int, int]]:
        docs, tf = self.doc_tf(pattern)
        order = np.lexsort((docs, -tf))[:k]
        return [(int(docs[i]), int(tf[i])) for i in order]

    def tfidf_scores(self, terms, conjunctive: bool = False) -> dict[int, float]:
        """Score of every candidate document (float64)."""
        scores: dict[int, float] = {}
        hits: dict[int, int] = {}
        for term in terms:
            docs, tf = self.doc_tf(term)
            w = float(np.log2(self.d / max(len(docs), 1)))
            for doc, f in zip(docs.tolist(), tf.tolist()):
                scores[doc] = scores.get(doc, 0.0) + f * w
                hits[doc] = hits.get(doc, 0) + 1
        if conjunctive:
            scores = {doc: s for doc, s in scores.items() if hits[doc] == len(terms)}
        return scores

    def tfidf(self, terms, k: int, conjunctive: bool = False) -> list[tuple[int, float]]:
        scores = self.tfidf_scores(terms, conjunctive)
        return sorted(scores.items(), key=lambda kv: (-kv[1], kv[0]))[:k]
