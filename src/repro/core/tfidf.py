"""TF-IDF ranked multi-term queries (Section 6.5).

The index composition is exactly the paper's: RLCSA-style CSA for term
ranges, PDL (+F) as the abstract per-term inverted lists, and a Sadakane
counting structure for document frequencies.  Weights:

    w(D, Q) = sum_i f(tf(D, q_i)) * g(df(q_i)),
    f(tf) = tf,   g(df) = lg(d / max(df, 1)).

Two query engines:

* ``tfidf_topk`` — exact batched engine: every term's (doc, tf) pairs are
  fully aggregated (PDL decompress + brute merge, the strategy the paper
  found fastest for PDL merging), scores summed by document, ranked-AND
  filters documents that miss any term.  One jitted program; vmap over a
  padded batch of queries.

* ``tfidf_topk_incremental`` — the paper's k' = 2k, 4k, ... loop with
  lower/upper score bounds and early termination, host-orchestrated over
  jitted per-term extractions.  Returns the same top-k set (weights of a
  disjunctive early stop may be partial, as the paper notes).
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.common import IDX, as_i32
from repro.core.csa import CSA
from repro.core.pdl import PDLIndex, pdl_doc_freqs, pdl_topk
from repro.core.sada import SadaCount, sada_count

BIG = np.iinfo(np.int32).max


def idf_weight(d: int, df):
    """g(df) = lg(d / max(df, 1))."""
    df = jnp.maximum(df, 1).astype(jnp.float32)
    return jnp.log2(jnp.float32(d) / df)


def tfidf_topk(
    pdl: PDLIndex,
    csa: CSA,
    sada: SadaCount,
    ranges,            # int32[T, 2] (lo, hi) per term; empty terms lo >= hi
    term_valid,        # bool[T]
    k: int,
    conjunctive: bool,
    max_buf: int = 2048,
    dfs=None,          # optional int32[T] per-term df override (sharded: global)
    n_docs: int | None = None,  # optional d override for g(df) (sharded: global)
):
    """Exact ranked-AND / ranked-OR top-k.  Returns (docs[k], scores[k]).

    Per-document scores are accumulated **term-major in a fixed order**:
    each candidate document looks up its integer tf in every term's sorted
    (doc, tf) list and folds ``tf * g(df)`` over the (static) term slots.
    A document's float score therefore depends only on its own per-term tf
    values and the weights — not on which other documents share the buffer
    — which is what makes the cross-shard merge bit-identical: a document
    scored inside one shard of a partitioned collection (with global ``dfs``
    / ``n_docs`` injected) produces the exact float the unsharded program
    produces.

    ``dfs``/``n_docs`` default to this index's own Sada counts and ``pdl.d``
    (the single-index behavior); the docs-sharded service passes the
    psum-merged global df and the global document count so idf weights are
    collection-wide.
    """
    ranges = as_i32(ranges)
    T = ranges.shape[0]
    term_valid = jnp.asarray(term_valid, dtype=jnp.bool_)

    def per_term(rng, tv):
        lo, hi = rng[0], rng[1]
        docs, tf, nseg = pdl_doc_freqs(pdl, csa, lo, hi, max_buf=max_buf)
        keep = tv & (jnp.arange(max_buf, dtype=IDX) < nseg)
        # rows stay sorted ascending: invalid tails are already BIG-padded
        docs = jnp.where(keep, docs, BIG)
        tf = jnp.where(keep, tf, 0)
        return docs, tf

    docs_t, tf_t = jax.vmap(per_term)(ranges, term_valid)   # [T, max_buf]
    if dfs is None:
        dfs = jax.vmap(lambda r: sada_count(sada, r[0], r[1]))(ranges)
    w = idf_weight(pdl.d if n_docs is None else n_docs, dfs)  # f32[T]

    # candidate set: each distinct doc across all term lists exactly once
    flat = docs_t.reshape(-1)
    M = flat.shape[0]
    s_docs = jnp.sort(flat)
    first = jnp.concatenate([jnp.ones(1, jnp.bool_), s_docs[1:] != s_docs[:-1]])
    cand_ok = first & (s_docs < BIG)
    cand = jnp.where(cand_ok, s_docs, BIG)

    # fixed-order weighted fold over the (static) term slots
    score = jnp.zeros(M, jnp.float32)
    seg_terms = jnp.zeros(M, IDX)
    for t in range(T):
        j = jnp.clip(jnp.searchsorted(docs_t[t], cand), 0, max_buf - 1)
        hit = (docs_t[t][j] == cand) & cand_ok
        score = score + jnp.where(hit, tf_t[t][j], 0).astype(jnp.float32) * w[t]
        seg_terms = seg_terms + hit.astype(IDX)

    seg_ok = cand_ok
    n_required = jnp.sum(term_valid.astype(IDX))
    if conjunctive:
        seg_ok = seg_ok & (seg_terms == n_required)

    return rank_topk_scores(cand, score, seg_ok, k)


def rank_topk_scores(docs, scores, ok, k: int):
    """Rank by (score desc, doc asc), take k: (docs[k] padded -1,
    scores[k] f32).  ``docs`` uses BIG for absent entries; the same total
    order the cross-shard k-way merge applies, so merging per-shard top-k
    lists through this function reproduces the unsharded ranking."""
    neg = jnp.where(ok, -scores, jnp.float32(np.inf))
    dkey = jnp.where(ok, docs, BIG)
    order = jnp.lexsort((dkey, neg))
    topd = dkey[order[:k]]
    tops = -neg[order[:k]]
    good = topd < BIG
    return (
        jnp.where(good, topd, -1).astype(IDX),
        jnp.where(good, tops, 0.0).astype(jnp.float32),
    )


def tfidf_topk_batch(
    pdl, csa, sada, ranges_batch, term_valid_batch, k, conjunctive, max_buf=2048,
    dfs_batch=None, n_docs: int | None = None,
):
    """vmap over a [Q, T, 2] batch of padded queries.  ``dfs_batch``
    (int32[Q, T]) and ``n_docs`` override the df / document-count inputs of
    the idf weight — the sharded engine's global-statistics injection."""
    ranges_batch = as_i32(ranges_batch)
    term_valid_batch = jnp.asarray(term_valid_batch, dtype=jnp.bool_)
    if dfs_batch is None:
        return jax.vmap(
            lambda r, tv: tfidf_topk(
                pdl, csa, sada, r, tv, k, conjunctive, max_buf, n_docs=n_docs
            )
        )(ranges_batch, term_valid_batch)
    return jax.vmap(
        lambda r, tv, df: tfidf_topk(
            pdl, csa, sada, r, tv, k, conjunctive, max_buf,
            dfs=df, n_docs=n_docs,
        )
    )(ranges_batch, term_valid_batch, as_i32(dfs_batch))


def term_ranges_batch(csa: CSA, patterns, lengths, *, use_kernel: bool = False):
    """Fused multi-term range finding for padded query batches.

    patterns: int32[Q, T, max_m] (term-padded, query-padded); lengths:
    int32[Q, T] with 0 marking absent term slots.  Returns
    (ranges int32[Q, T, 2], valid bool[Q, T]) — the exact input layout of
    ``tfidf_topk_batch`` — in one backward-search program (no host loop).

    ``use_kernel`` selects the range-search path exactly as the planner
    does: ``True`` launches the whole [Q*T] term batch as ONE fused Pallas
    backward search, ``False`` takes the XLA pair descent, ``None``
    auto-detects (kernel iff TPU).  All paths are bit-identical."""
    from repro.core.csa import csa_search_planned

    patterns = as_i32(patterns)
    lengths = as_i32(lengths)
    Q, T, m = patterns.shape
    lo, hi = csa_search_planned(
        csa, patterns.reshape(Q * T, m), lengths.reshape(-1),
        use_kernel=use_kernel,
    )
    hi = jnp.where(lengths.reshape(-1) > 0, hi, lo)
    ranges = jnp.stack([lo, hi], axis=-1).reshape(Q, T, 2)
    return ranges, lengths > 0


# ---------------------------------------------------------------------------
# The paper's incremental algorithm (Section 6.5 numbered loop)
# ---------------------------------------------------------------------------


def tfidf_topk_incremental(
    pdl: PDLIndex,
    csa: CSA,
    sada: SadaCount,
    ranges: np.ndarray,   # [T, 2] host array
    k: int,
    conjunctive: bool,
    max_buf: int = 2048,
):
    """Host-orchestrated k' doubling with score bounds.

    Step 1-6 of Section 6.5: extract k' docs per term (PDL lists are sorted
    by tf), maintain lower/upper bounds on w(D, Q), stop when the top-k set
    is provably stable.  Returns (docs list, lower-bound scores list).
    """
    T = len(ranges)
    d = pdl.d
    dfs = [int(sada_count(sada, int(lo), int(hi))) for lo, hi in ranges]
    gs = [float(np.log2(d / max(df, 1))) for df in dfs]

    # full per-term lists (tf-sorted); the incremental loop reads prefixes,
    # the conjunctive filter checks membership against the complete lists
    # ("completely decompressed document lists", step 2)
    full: list[tuple[np.ndarray, np.ndarray]] = []
    full_maps: list[dict[int, int]] = []
    for lo, hi in ranges:
        docs, tf = pdl_topk(pdl, csa, int(lo), int(hi), min(max_buf, pdl.d))
        docs = np.asarray(docs)
        tf = np.asarray(tf)
        keep = docs >= 0
        full.append((docs[keep], tf[keep]))
        full_maps.append({int(a): int(b) for a, b in zip(docs[keep], tf[keep])})

    kp = 2 * k
    while True:
        # step 1: extract k' more documents per term
        prefix: dict[int, dict[int, int]] = {}
        next_tf = []
        for t in range(T):
            docs, tf = full[t]
            head = min(kp, len(docs))
            for j in range(head):
                prefix.setdefault(int(docs[j]), {})[t] = int(tf[j])
            next_tf.append(int(tf[head]) if head < len(docs) else 0)

        # steps 3-4: lower / upper bounds for every extracted document
        lower, upper = {}, {}
        for doc, seen in prefix.items():
            lower[doc] = sum(seen.get(t, 0) * gs[t] for t in range(T))
            upper[doc] = sum(
                (seen[t] if t in seen else next_tf[t]) * gs[t] for t in range(T)
            )

        # step 2: conjunctive filter against complete lists
        if conjunctive:
            cand = {
                doc: w
                for doc, w in lower.items()
                if all(doc in full_maps[t] for t in range(T))
            }
        else:
            cand = lower

        ranked = sorted(cand.items(), key=lambda kv: (-kv[1], kv[0]))[:k]
        exhausted = all(kp >= len(full[t][0]) for t in range(T))
        if exhausted:
            return [doc for doc, _ in ranked], [w for _, w in ranked]

        # steps 5-6: early termination when the top-k set cannot change
        kth = ranked[k - 1][1] if len(ranked) >= k else -np.inf
        unseen_upper = sum(next_tf[t] * gs[t] for t in range(T))
        top_set = {doc for doc, _ in ranked}
        seen_safe = all(
            upper[doc] <= kth for doc in cand if doc not in top_set
        )
        if len(ranked) >= k and unseen_upper <= kth and seen_safe:
            return [doc for doc, _ in ranked], [w for _, w in ranked]
        kp *= 2
