"""Mesh-sharded retrieval: per-shard index stacks + cross-shard merge.

The single-device ``RetrievalService`` holds the whole index pytree in one
memory domain; once the CSA wavelet matrix outgrows
``BACKWARD_SEARCH_VMEM_BUDGET`` its build selects the XLA pair descent
instead of the fused Pallas backward-search kernel.  This module restores
the kernel path by sharding the collection over a 1-D ``docs`` mesh axis
(``repro.dist.sharding``):

* **Partitioning** — documents are split into contiguous shards
  (``doc_shard_bounds``); each shard indexes its own sub-collection
  (``repro.core.suffix.subcollection``, global sigma preserved) with a full
  per-shard stack: CSA wavelet matrix, ILCP runs, PDL blocks, Sadakane
  counting.  Because every document ends in its own terminator and patterns
  never contain it, a pattern's matches inside a shard's documents are
  exactly its matches inside the shard's text: per-shard occ / df /
  document sets sum (disjoint-union) to the global answer.

* **Execution** — ONE ``jax.jit`` program per endpoint x shape bucket, AOT
  compiled into the same shape-bucketed cache as the single-device engine.
  Inside the program one ``shard_map`` over the docs axis runs the
  executors: each device switches on its axis index to its own shard's
  branch (the per-shard pytrees are heterogeneous — different n, runs,
  PDL grammars — so they cannot be stacked and vmapped).  Shard s's work
  therefore runs on device s only, and the fused kernels — which Mosaic
  cannot partition automatically — launch once **per shard** with a
  per-shard VMEM footprint (the per-shard launch-count contract in
  ``repro.analysis.contracts``).  Per-shard results come out stacked
  [S, ...] along the docs axis and are merged by a ``shard_map``-ped
  reduction stage.

* **Merge algebra** (all on device, collectives allowlisted to
  ``psum`` / ``all_gather``):

  - counting:  global df / occ are ``psum`` s of per-shard counts (exact:
    integer sums over disjoint document sets);
  - listing:   shard-local doc ids are offset by the shard's document
    base, ``all_gather`` ed, and merge-sorted ascending — no dedup is
    needed because shards are document-disjoint;
  - top-k:     per-shard top-k rows are gathered and k-way merged by the
    canonical (tf desc, id asc) key; the union of shard-local top-k lists
    is a superset of the global top-k because a document's tf is local to
    its shard;
  - tf-idf:    a first ``psum`` stage produces collection-wide df per
    term; each shard then scores its own candidates with the **global**
    idf weights and document count (``tfidf_topk_batch(dfs_batch=...,
    n_docs=...)``), so a document's float score is bit-identical to the
    unsharded program's (the fixed-term-order scorer in
    ``repro.core.tfidf``); a final gather + (score desc, id asc) merge
    ranks the union.

Placement note: ``jax.jit`` rejects mixed single-device placements, so the
per-shard index leaves are placed **replicated** over the docs mesh
(``docs_index_shardings``); only the taken branch reads them.  True
per-device residency (shard s's leaves living
only on device s) is the multi-host follow-up recorded in
docs/SHARDING.md; the kernel-path restoration is unaffected because the
kernel's working set is the per-launch (per-shard) wavelet matrix.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.common import IDX
from repro.core.sada import sada_count_batch
from repro.core.suffix import Collection, subcollection
from repro.core.tfidf import rank_topk_scores, term_ranges_batch, tfidf_topk_batch
from repro.data.collections import normalize_patterns, pad_patterns
from repro.dist.sharding import (
    DOCS_AXIS,
    doc_shard_bounds,
    docs_index_shardings,
    docs_mesh_size,
)
from repro.serve import faults
from repro.serve.planner import ENGINE_CODES, plan_queries
from repro.serve.retrieval import (
    MAX_PATTERN_LEN,
    RetrievalService,
    _bucket_batch,
    _bucket_len,
    _list_program,
    _topk_program,
    kernel_selection,
)

_BIG = np.iinfo(np.int32).max


def _per_shard(mesh, fn, shard_idx, *args, stacked=()):
    """``fn(s, shard_s, *args, *stacked_s)`` for every docs shard ``s``, on
    its own device; outputs stacked ``[S, ...]`` along the docs axis.

    One ``shard_map`` over the docs axis whose body switches on the
    device's axis index to its shard's executors: the per-shard pytrees
    differ in shape, so they ride in replicated and only the taken branch
    reads them.  A Mosaic kernel cannot be partitioned automatically, so
    this is also what lets the fused kernels run on a docs mesh.
    ``stacked`` operands are ``[S, ...]`` per-shard values (earlier
    outputs of this function); shard ``s`` sees its own row."""

    def body(idx, args, stacked):
        rows = [x[0] for x in stacked]
        branches = [
            functools.partial(
                lambda s, *ops: jax.tree.map(
                    lambda x: x[None], fn(s, idx[s], *ops)
                ),
                s,
            )
            for s in range(len(idx))
        ]
        return jax.lax.switch(
            jax.lax.axis_index(DOCS_AXIS), branches, *args, *rows
        )

    return jax.shard_map(
        body, mesh=mesh, check_vma=False,
        in_specs=(P(), P(), P(DOCS_AXIS)),
        out_specs=P(DOCS_AXIS),
    )(shard_idx, args, tuple(stacked))


def _shard_args(shards):
    """The per-shard index pytrees as one nested jit argument."""
    return tuple(
        (s.csa, s.ilcp, s.pdl_list, s.pdl_topk, s.sada, s.da) for s in shards
    )


# ---------------------------------------------------------------------------
# Fused sharded programs (ONE jit program per endpoint x bucket)
# ---------------------------------------------------------------------------


def _sharded_plan_program(
    mesh, doc_bases, use_kernel,
    shard_idx, patterns, lengths, threshold, forced,
):
    """Per-shard plans + psum-merged global occ / df.

    Returns (lo [S, B], hi [S, B], engine [S, B], occ [B], df [B]): ranges
    and engine choices are shard-local (each shard dispatches on its own
    occ/df balance), occurrence and document counts are collection-global.
    """
    def plan(s, shard, patterns, lengths, threshold, forced):
        csa, _ilcp, _pdl, _pdlt, sada, _da = shard
        p = plan_queries(csa, sada, patterns, lengths, threshold, forced,
                         use_kernel=use_kernel)
        return p.lo, p.hi, p.engine, p.occ, p.df

    lo, hi, engine, occ_sb, df_sb = _per_shard(
        mesh, plan, shard_idx, patterns, lengths, threshold, forced
    )

    def merge(occ_local, df_local):
        g_occ = jax.lax.psum(jnp.sum(occ_local, axis=0), DOCS_AXIS)
        g_df = jax.lax.psum(jnp.sum(df_local, axis=0), DOCS_AXIS)
        return g_occ, g_df

    g_occ, g_df = jax.shard_map(
        merge, mesh=mesh, check_vma=False,
        in_specs=(P(DOCS_AXIS, None), P(DOCS_AXIS, None)),
        out_specs=(P(None), P(None)),
    )(occ_sb, df_sb)
    return lo, hi, engine, g_occ, g_df


def _sharded_list_program(
    mesh, doc_bases, max_df, brute_win, max_buf, use_kernel, use_list_kernel,
    shard_idx, patterns, lengths, threshold, forced,
):
    """Listing: per-shard engines -> offset ids -> gather -> merge-sort.

    ``use_list_kernel`` rides through to each shard's ``_list_program``:
    on the kernel path the fused ILCP listing kernel launches once PER
    SHARD (like backward search), with a per-shard VMEM footprint —
    restoring the listing kernel for stacks past ILCP_LIST_VMEM_BUDGET."""
    def listing(s, shard, patterns, lengths, threshold, forced):
        csa, ilcp, pdl, _pdlt, sada, da = shard
        docs, cnt, _plan = _list_program(
            max_df, brute_win, max_buf, use_kernel, use_list_kernel,
            csa, ilcp, pdl, da, sada, patterns, lengths, threshold, forced,
        )
        return jnp.where(docs >= 0, docs + doc_bases[s], -1), cnt

    docs_sb, cnt_sb = _per_shard(           # [S, B, max_df], [S, B]
        mesh, listing, shard_idx, patterns, lengths, threshold, forced
    )

    def merge(docs_local, cnt_local):
        total = jax.lax.psum(jnp.sum(cnt_local, axis=0), DOCS_AXIS)
        allv = jax.lax.all_gather(docs_local, DOCS_AXIS, axis=0, tiled=True)
        S, B, W = allv.shape
        flat = jnp.swapaxes(allv, 0, 1).reshape(B, S * W)
        keys = jnp.where(flat < 0, _BIG, flat)
        s = jnp.sort(keys, axis=1)[:, :W]       # shards are doc-disjoint:
        docs = jnp.where(s == _BIG, -1, s)      # concat + sort, no dedup
        return docs.astype(IDX), jnp.minimum(total, W).astype(IDX)

    return jax.shard_map(
        merge, mesh=mesh, check_vma=False,
        in_specs=(P(DOCS_AXIS, None, None), P(DOCS_AXIS, None)),
        out_specs=(P(None, None), P(None)),
    )(docs_sb, cnt_sb)


def _sharded_topk_program(
    mesh, doc_bases, k, max_df, brute_win, max_buf, use_kernel,
    shard_idx, patterns, lengths, threshold, forced,
):
    """Top-k: per-shard top-k -> gather -> k-way merge by (tf desc, id asc).

    Exact because documents are shard-disjoint: a document's tf is computed
    entirely inside its shard, so every global top-k document appears in
    its own shard's local top-k."""
    def topk(s, shard, patterns, lengths, threshold, forced):
        csa, _ilcp, _pdl, pdl_t, sada, _da = shard
        docs, tfs, _plan = _topk_program(
            k, max_df, brute_win, max_buf, use_kernel,
            csa, pdl_t, sada, patterns, lengths, threshold, forced,
        )
        return jnp.where(docs >= 0, docs + doc_bases[s], -1), tfs

    docs_sb, tf_sb = _per_shard(            # [S, B, k] each
        mesh, topk, shard_idx, patterns, lengths, threshold, forced
    )

    def merge(docs_local, tf_local):
        alld = jax.lax.all_gather(docs_local, DOCS_AXIS, axis=0, tiled=True)
        allt = jax.lax.all_gather(tf_local, DOCS_AXIS, axis=0, tiled=True)
        S, B, K = alld.shape
        d2 = jnp.swapaxes(alld, 0, 1).reshape(B, S * K)
        t2 = jnp.swapaxes(allt, 0, 1).reshape(B, S * K)
        ok = d2 >= 0
        dkey = jnp.where(ok, d2, _BIG)
        tkey = jnp.where(ok, -t2, _BIG)
        order = jnp.lexsort((dkey, tkey), axis=-1)[:, :K]
        docs = jnp.take_along_axis(dkey, order, axis=1)
        tfs = jnp.take_along_axis(t2, order, axis=1)
        good = docs < _BIG
        return (
            jnp.where(good, docs, -1).astype(IDX),
            jnp.where(good, tfs, 0).astype(IDX),
        )

    return jax.shard_map(
        merge, mesh=mesh, check_vma=False,
        in_specs=(P(DOCS_AXIS, None, None), P(DOCS_AXIS, None, None)),
        out_specs=(P(None, None), P(None, None)),
    )(docs_sb, tf_sb)


def _sharded_tfidf_program(
    mesh, doc_bases, n_docs, k, conjunctive, max_buf, use_kernel,
    shard_idx, patterns, lengths,
):
    """tf-idf in two merge stages: psum global df, then score per shard
    with global weights and gather-merge by (score desc, id asc)."""
    Q, T, _m = patterns.shape

    def term_ranges(s, shard, patterns, lengths):
        csa, _ilcp, _pdl, _pdlt, sada, _da = shard
        ranges, valid = term_ranges_batch(
            csa, patterns, lengths, use_kernel=use_kernel
        )
        flat = ranges.reshape(Q * T, 2)
        dfs = sada_count_batch(sada, flat[:, 0], flat[:, 1]).reshape(Q, T)
        return ranges, valid, dfs

    ranges_sb, valid_sb, dfs_sb = _per_shard(   # [S, Q, T, 2], [S, Q, T] x 2
        mesh, term_ranges, shard_idx, patterns, lengths
    )

    def merge_df(dfs_local):
        return jax.lax.psum(jnp.sum(dfs_local, axis=0), DOCS_AXIS)

    g_dfs = jax.shard_map(
        merge_df, mesh=mesh, check_vma=False,
        in_specs=P(DOCS_AXIS, None, None),
        out_specs=P(None, None),
    )(dfs_sb)                                   # [Q, T] global df, replicated

    def score(s, shard, g_dfs, ranges, valid):
        csa, _ilcp, _pdl, pdl_t, sada, _da = shard
        docs, scores = tfidf_topk_batch(
            pdl_t, csa, sada, ranges, valid, k, conjunctive,
            max_buf=max_buf, dfs_batch=g_dfs, n_docs=n_docs,
        )
        return jnp.where(docs >= 0, docs + doc_bases[s], -1), scores

    docs_sb, score_sb = _per_shard(             # [S, Q, k] each
        mesh, score, shard_idx, g_dfs, stacked=(ranges_sb, valid_sb)
    )

    def merge(docs_local, score_local):
        alld = jax.lax.all_gather(docs_local, DOCS_AXIS, axis=0, tiled=True)
        alls = jax.lax.all_gather(score_local, DOCS_AXIS, axis=0, tiled=True)
        S, Qb, K = alld.shape
        d2 = jnp.swapaxes(alld, 0, 1).reshape(Qb, S * K)
        s2 = jnp.swapaxes(alls, 0, 1).reshape(Qb, S * K)
        ok = d2 >= 0
        dkey = jnp.where(ok, d2, _BIG)
        md, ms = jax.vmap(lambda dd, ss, oo: rank_topk_scores(dd, ss, oo, K))(
            dkey, s2, ok
        )
        return md, ms

    return jax.shard_map(
        merge, mesh=mesh, check_vma=False,
        in_specs=(P(DOCS_AXIS, None, None), P(DOCS_AXIS, None, None)),
        out_specs=(P(None, None), P(None, None)),
    )(docs_sb, score_sb)


# ---------------------------------------------------------------------------
# Service
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ShardedRetrievalService:
    """Docs-mesh-sharded drop-in for ``RetrievalService``.

    Serves the same endpoint surface (``plan`` / ``count`` /
    ``list_docs[_arrays]`` / ``topk[_arrays]`` / ``tfidf[_arrays]``, with
    ``engine=`` including the ``"reference"`` oracle), so ``ServeRuntime``
    and the benchmarks run unchanged on top of it."""

    coll: Collection                  # the global collection
    mesh: object                      # 1-D ("docs",) mesh
    shards: list                      # per-shard RetrievalService stacks
    doc_bases: np.ndarray             # int32[S] first global doc id per shard
    occ_df_threshold: float = 4.0
    use_search_kernel: bool = False
    use_list_kernel: bool = False
    brute_window: int | None = None
    _cache: dict = dataclasses.field(default_factory=dict, repr=False)
    compile_counts: dict = dataclasses.field(default_factory=dict, repr=False)
    fingerprints: dict = dataclasses.field(default_factory=dict, repr=False)

    # -- construction --------------------------------------------------------

    @classmethod
    def build(
        cls, coll: Collection, mesh, block_size: int = 64, beta: float = 16.0,
        sada_variant: str = "sparse", sample_rate: int = 16,
        use_search_kernel: bool | None = None,
        use_list_kernel: bool | None = None,
        brute_window: int | None = None,
        validate: bool = True,
    ):
        n_shards = docs_mesh_size(mesh)
        bounds = doc_shard_bounds(coll.d, n_shards)
        shards = []
        for dlo, dhi in bounds:
            sub = subcollection(coll, dlo, dhi)
            shard = RetrievalService.build(
                sub, block_size=block_size, beta=beta,
                sada_variant=sada_variant, sample_rate=sample_rate,
                brute_window=brute_window, validate=False,
            )
            # jit rejects mixed single-device placements: leaves live
            # replicated over the docs mesh (see module docstring)
            for name in ("csa", "ilcp", "pdl_list", "pdl_topk", "sada", "da"):
                leaf = getattr(shard, name)
                setattr(
                    shard, name,
                    jax.device_put(leaf, docs_index_shardings(mesh, leaf)),
                )
            shards.append(shard)
        # one selection for every shard's launch, as the flat build makes
        # it: a kernel runs when every shard's tables fit its budget
        search_k, list_k = kernel_selection(
            shards, mesh.devices.flat[0].platform
        )
        if use_search_kernel is None:
            use_search_kernel = search_k
        if use_list_kernel is None:
            use_list_kernel = list_k
        svc = cls(
            coll=coll,
            mesh=mesh,
            shards=shards,
            doc_bases=np.asarray([b[0] for b in bounds], np.int32),
            use_search_kernel=use_search_kernel,
            use_list_kernel=use_list_kernel,
            brute_window=brute_window,
        )
        if validate:
            from repro.serve.validate import validate_sharded_service

            svc.fingerprints.update(validate_sharded_service(svc))
        return svc

    @property
    def n_shards(self) -> int:
        return len(self.shards)

    def shard_doc_range(self, s: int) -> tuple[int, int]:
        lo = int(self.doc_bases[s])
        hi = (
            int(self.doc_bases[s + 1])
            if s + 1 < self.n_shards else self.coll.d
        )
        return lo, hi

    # -- compile cache (same discipline as RetrievalService) -----------------

    def _compiled(self, kind: str, statics: tuple, build_fn, args: tuple):
        key = (kind, statics)
        exe = self._cache.get(key)
        if exe is None:
            faults.fire(f"compile:{kind}")
            exe = jax.jit(build_fn()).lower(*args).compile()
            self._cache[key] = exe
            self.compile_counts[kind] = self.compile_counts.get(kind, 0) + 1
        return exe

    def _pad_batch(self, patterns):
        patterns = normalize_patterns(
            patterns, sigma=self.coll.sigma, max_len=MAX_PATTERN_LEN
        )
        pats, lens = pad_patterns(patterns)
        B, m = pats.shape
        Bb, mb = _bucket_batch(B), _bucket_len(m)
        out = np.zeros((Bb, mb), np.int32)
        out[:B, :m] = pats
        lns = np.zeros(Bb, np.int32)
        lns[:B] = lens
        return jnp.asarray(out), jnp.asarray(lns), B

    def _knobs(self, engine: str):
        return (
            jnp.float32(self.occ_df_threshold),
            jnp.int32(ENGINE_CODES[engine]),
        )

    #: one Brute-L window shared by every shard, from the global ``d``: each
    #: shard dispatches on its own occ and df, and both are at most the
    #: global ones, so the flat service's cover bounds every shard's rows
    _brute_window_for = RetrievalService._brute_window_for

    # -- endpoints -----------------------------------------------------------

    def plan(self, patterns, engine: str = "auto"):
        """Sharded query plan: global ``occ`` / ``df`` [B] (psum-merged),
        shard-local ``lo`` / ``hi`` / ``engine_shard`` [S, B].  ``engine``
        mirrors the single-device dict key for the global entries."""
        pats, lens, B = self._pad_batch(patterns)
        thresh, forced = self._knobs(engine)
        faults.fire("plan")
        args = (_shard_args(self.shards), pats, lens, thresh, forced)
        exe = self._compiled(
            "plan", (pats.shape,),
            lambda: functools.partial(
                _sharded_plan_program, self.mesh, tuple(self.doc_bases),
                self.use_search_kernel,
            ),
            args,
        )
        lo, hi, eng, occ, df = exe(*args)
        return {
            "lo": np.asarray(lo)[:, :B],
            "hi": np.asarray(hi)[:, :B],
            "engine_shard": np.asarray(eng)[:, :B],
            "occ": np.asarray(occ)[:B],
            "df": np.asarray(df)[:B],
        }

    def count(self, patterns, engine: str = "auto"):
        if engine.startswith("reference"):
            return sum(
                np.asarray(sh._ranges_dfs(patterns)[2], np.int64).astype(np.int32)
                for sh in self.shards
            )
        return self.plan(patterns)["df"]

    def list_docs_arrays(self, patterns, max_df: int = 256,
                         engine: str = "auto", max_buf: int = 4096):
        if not len(patterns):
            return np.zeros((0, max_df), np.int32), np.zeros(0, np.int32)
        pats, lens, B = self._pad_batch(patterns)
        thresh, forced = self._knobs(engine)
        win = self._brute_window_for(engine, max_buf)
        faults.fire("executor:list")
        args = (_shard_args(self.shards), pats, lens, thresh, forced)
        exe = self._compiled(
            "list", (pats.shape, max_df, win, max_buf),
            lambda: functools.partial(
                _sharded_list_program, self.mesh, tuple(self.doc_bases),
                max_df, win, max_buf, self.use_search_kernel,
                self.use_list_kernel,
            ),
            args,
        )
        docs, cnt = exe(*args)
        return faults.poison(
            "executor:list", (np.asarray(docs)[:B], np.asarray(cnt)[:B])
        )

    def list_docs(self, patterns, max_df: int = 256, engine: str = "auto",
                  max_buf: int = 4096):
        if engine.startswith("reference"):
            sub = engine.split(":", 1)[1] if ":" in engine else "auto"
            return self._list_docs_reference(patterns, max_df, sub, max_buf)
        docs, cnt = self.list_docs_arrays(patterns, max_df, engine, max_buf)
        return [docs[i, : cnt[i]].tolist() for i in range(len(cnt))]

    def topk_arrays(self, patterns, k: int = 10, engine: str = "auto",
                    max_buf: int = 4096):
        if not len(patterns):
            return np.zeros((0, k), np.int32), np.zeros((0, k), np.int32)
        pats, lens, B = self._pad_batch(patterns)
        thresh, forced = self._knobs(engine)
        max_df = self._topk_max_df(max_buf)
        win = self._brute_window_for(engine, max_buf)
        faults.fire("executor:topk")
        args = (_shard_args(self.shards), pats, lens, thresh, forced)
        exe = self._compiled(
            "topk", (pats.shape, k, max_df, win, max_buf),
            lambda: functools.partial(
                _sharded_topk_program, self.mesh, tuple(self.doc_bases),
                k, max_df, win, max_buf, self.use_search_kernel,
            ),
            args,
        )
        docs, tfs = exe(*args)
        return faults.poison(
            "executor:topk", (np.asarray(docs)[:B], np.asarray(tfs)[:B])
        )

    def topk(self, patterns, k: int = 10, engine: str = "auto",
             max_buf: int = 4096):
        if engine.startswith("reference"):
            sub = engine.split(":", 1)[1] if ":" in engine else "auto"
            return self._topk_reference(patterns, k, sub, max_buf)
        docs, tfs = self.topk_arrays(patterns, k, engine, max_buf)
        return [
            [(int(d), int(t)) for d, t in zip(docs[i], tfs[i]) if d >= 0]
            for i in range(docs.shape[0])
        ]

    def _topk_max_df(self, max_buf: int) -> int:
        # per-shard rows: a shard holds at most its own documents + 1
        d_max = max(sh.coll.d for sh in self.shards)
        return min(d_max + 1, max_buf)

    def tfidf_arrays(self, queries, k: int = 10, conjunctive: bool = False,
                     max_terms: int = 4, max_buf: int = 2048):
        Q = len(queries)
        if Q == 0:
            return np.zeros((0, k), np.int32), np.zeros((0, k), np.float32)
        queries = [
            normalize_patterns(
                list(terms)[:max_terms], sigma=self.coll.sigma,
                max_len=MAX_PATTERN_LEN,
            )
            for terms in queries
        ]
        m = max((len(t) for terms in queries for t in terms), default=1)
        Qb, mb = _bucket_batch(Q), _bucket_len(max(m, 1))
        pats = np.zeros((Qb, max_terms, mb), np.int32)
        lens = np.zeros((Qb, max_terms), np.int32)
        for qi, terms in enumerate(queries):
            for ti, t in enumerate(terms):
                pats[qi, ti, : len(t)] = t
                lens[qi, ti] = len(t)
        pats = jnp.asarray(pats)
        lens = jnp.asarray(lens)
        faults.fire("executor:tfidf")
        args = (_shard_args(self.shards), pats, lens)
        exe = self._compiled(
            "tfidf", (pats.shape, k, conjunctive, max_buf),
            lambda: functools.partial(
                _sharded_tfidf_program, self.mesh, tuple(self.doc_bases),
                self.coll.d, k, conjunctive, max_buf, self.use_search_kernel,
            ),
            args,
        )
        docs, scores = exe(*args)
        return faults.poison(
            "executor:tfidf", (np.asarray(docs)[:Q], np.asarray(scores)[:Q])
        )

    def tfidf(self, queries, k: int = 10, conjunctive: bool = False,
              max_terms: int = 4, max_buf: int = 2048, engine: str = "auto"):
        if engine.startswith("reference"):
            return self._tfidf_reference(queries, k, conjunctive, max_terms,
                                         max_buf)
        docs, scores = self.tfidf_arrays(queries, k, conjunctive, max_terms,
                                         max_buf)
        return [
            [(int(d), float(s)) for d, s in zip(docs[i], scores[i]) if d >= 0]
            for i in range(docs.shape[0])
        ]

    # -- reference path: per-shard host oracles + host merge -----------------

    def _list_docs_reference(self, patterns, max_df, engine, max_buf):
        if not len(patterns):
            return []
        per = [
            sh._list_docs_reference(patterns, max_df, engine, max_buf)
            for sh in self.shards
        ]
        out = []
        for qi in range(len(per[0])):
            merged = sorted(
                int(d) + int(self.doc_bases[s])
                for s, rows in enumerate(per)
                for d in rows[qi]
            )
            out.append(merged[:max_df])
        return out

    def _topk_reference(self, patterns, k, engine, max_buf):
        if not len(patterns):
            return []
        per = [
            sh._topk_reference(patterns, k, engine, max_buf)
            for sh in self.shards
        ]
        out = []
        for qi in range(len(per[0])):
            pool = [
                (int(d) + int(self.doc_bases[s]), int(t))
                for s, rows in enumerate(per)
                for d, t in rows[qi]
            ]
            pool.sort(key=lambda dt: (-dt[1], dt[0]))
            out.append(pool[:k])
        return out

    def _tfidf_reference(self, queries, k, conjunctive, max_terms, max_buf):
        """Per-shard scoring with *global* df / document count (the exact
        floats the device merge produces), ranked on host."""
        Q = len(queries)
        ranges = np.zeros((len(self.shards), Q, max_terms, 2), np.int32)
        valid = np.zeros((Q, max_terms), bool)
        dfs = np.zeros((Q, max_terms), np.int64)
        for s, sh in enumerate(self.shards):
            for qi, terms in enumerate(queries):
                if not terms:
                    continue
                lo, hi, df = sh._ranges_dfs(terms[:max_terms])
                for ti in range(len(lo)):
                    ranges[s, qi, ti] = (lo[ti], hi[ti])
                    valid[qi, ti] = True
                    dfs[qi, ti] += int(df[ti])
        out = [[] for _ in range(Q)]
        pools = [[] for _ in range(Q)]
        for s, sh in enumerate(self.shards):
            docs, scores = tfidf_topk_batch(
                sh.pdl_topk, sh.csa, sh.sada, ranges[s], valid, k,
                conjunctive, max_buf=max_buf,
                dfs_batch=dfs.astype(np.int32), n_docs=self.coll.d,
            )
            docs = np.asarray(docs)
            scores = np.asarray(scores)
            for qi in range(Q):
                pools[qi] += [
                    (int(d) + int(self.doc_bases[s]), float(w))
                    for d, w in zip(docs[qi], scores[qi]) if d >= 0
                ]
        for qi in range(Q):
            pools[qi].sort(key=lambda dw: (-dw[1], dw[0]))
            out[qi] = pools[qi][:k]
        return out

    # -- introspection (repro.analysis contract surface) ---------------------

    ENDPOINT_KINDS = ("plan", "list", "topk", "tfidf")

    def endpoint_program(self, kind: str, *, use_kernel: bool | None = None,
                         use_list_kernel: bool | None = None,
                         max_df: int = 64, k: int = 10, max_buf: int = 512,
                         conjunctive: bool = False):
        """(fn, args_builder) of the sharded fused program for ``kind`` —
        the contract auditor's tracing surface (per-shard launch counts,
        collective allowlist)."""
        if use_kernel is None:
            use_kernel = self.use_search_kernel
        if use_list_kernel is None:
            use_list_kernel = self.use_list_kernel
        bases = tuple(self.doc_bases)
        if kind == "plan":
            fn = functools.partial(
                _sharded_plan_program, self.mesh, bases, use_kernel
            )

            def args(B, m):
                return (_shard_args(self.shards),) + self._audit_batch(B, m)
        elif kind == "list":
            fn = functools.partial(
                _sharded_list_program, self.mesh, bases, max_df,
                self._brute_window_for("auto", max_buf), max_buf, use_kernel,
                use_list_kernel,
            )

            def args(B, m):
                return (_shard_args(self.shards),) + self._audit_batch(B, m)
        elif kind == "topk":
            fn = functools.partial(
                _sharded_topk_program, self.mesh, bases, k,
                self._topk_max_df(max_buf),
                self._brute_window_for("auto", max_buf), max_buf, use_kernel,
            )

            def args(B, m):
                return (_shard_args(self.shards),) + self._audit_batch(B, m)
        elif kind == "tfidf":
            fn = functools.partial(
                _sharded_tfidf_program, self.mesh, bases, self.coll.d,
                k, conjunctive, max_buf, use_kernel,
            )

            def args(B, m):
                pats = jnp.zeros((B, 2, _bucket_len(m)), jnp.int32)
                lens = jnp.ones((B, 2), jnp.int32)
                return (_shard_args(self.shards), pats, lens)
        else:
            raise ValueError(f"unknown endpoint kind {kind!r}")
        return fn, args

    def _audit_batch(self, B: int, m: int):
        pats = jnp.zeros((B, _bucket_len(m)), jnp.int32)
        lens = jnp.ones(B, jnp.int32)
        return pats, lens, jnp.float32(self.occ_df_threshold), jnp.int32(-1)

    def trace_endpoint(self, kind: str, B: int = 8, m: int = 8, **kw):
        fn, args = self.endpoint_program(kind, **kw)
        return jax.make_jaxpr(fn)(*args(_bucket_batch(B), m))

    def compiled_executables(self) -> dict:
        return dict(self._cache)

    def space_report(self) -> dict:
        per = [sh.space_report() for sh in self.shards]
        return {
            "n": self.coll.n,
            "d": self.coll.d,
            "n_shards": self.n_shards,
            "shards": per,
        }
