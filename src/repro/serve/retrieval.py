"""Batched document-retrieval serving — the paper's contribution deployed
as the framework's retrieval layer.

One service object owns the full index stack over a document collection:

    CSA (RLCSA-accounted FM-index)        pattern -> SA range
    ILCP                                  listing (Sada-I) + counting
    PDL (+F)                              listing + top-k with frequencies
    Sadakane (compressed variants)        document counting
    TF-IDF                                ranked multi-term AND/OR

Execution architecture — a three-stage on-device engine:

1. **Planner** (repro.serve.planner): one fused pass over the padded
   pattern batch computes (lo, hi) ranges, df (Sada), occ, and a per-query
   engine assignment as an int32 array.  This is the paper's Section 6.2.2
   dispatch policy (Brute-L when occ/df is small, PDL otherwise) with the
   branching moved from Python onto the device.  The range search runs as
   ONE fused Pallas backward-search launch per batch on TPU
   (repro.kernels.backward_search; backend auto-detected) and as the
   pair-descent XLA program elsewhere — both bit-identical to the
   reference.  Planner occ stats also size the Brute-L locate window per
   compile bucket (dispatch-aware, grow-only powers of two), replacing the
   static max_buf window.
2. **Masked batch executors** (repro.core.{listing,ilcp,pdl,tfidf}):
   vmapped fixed-shape ``*_batch`` entry points.  Every engine runs over
   the full batch with the queries not assigned to it collapsed to empty
   ranges; outputs are padded (B, max_df) arrays with -1 sentinels, and the
   final result is a ``jnp.where`` select by engine id.
3. **Shape-bucketed compile cache** (this module): ``count``,
   ``list_docs``, ``topk``, and ``tfidf`` each lower planner + executors to
   ONE compiled program per (batch-bucket, length-bucket, k, max_df, ...)
   signature.  Batch sizes round up to powers of two and pattern lengths to
   multiples of 8, so recompilation is bounded regardless of traffic; the
   AOT executables are compiled exactly once per bucket (``compile_counts``
   exposes the tally for tests and monitoring).

Engine mode is a *traced* input (an int code, -1 = auto), so switching
between auto/brute/ilcp/pdl reuses the same executable.  The original
per-query host loop survives as ``engine="reference"`` (optionally
``"reference:brute"`` etc. to force a sub-engine) and is the parity oracle
for the batched path — results are bit-identical by construction because
both sides run the same per-query programs.
"""

from __future__ import annotations

import dataclasses
import functools
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.common import IDX
from repro.core.csa import build_csa
from repro.core.ilcp import (
    build_ilcp,
    ilcp_count_docs_batch,
    ilcp_list_docs_da,
    ilcp_list_docs_da_planned,
)
from repro.core.listing import (
    brute_list_csa,
    brute_list_csa_batch,
    brute_topk,
    brute_topk_batch,
)
from repro.core.pdl import (
    build_pdl,
    pdl_list_docs,
    pdl_list_docs_batch,
    pdl_topk,
    pdl_topk_batch,
)
from repro.core.sada import build_sada, sada_count_batch
from repro.core.suffix import Collection, build_suffix_data
from repro.core.tfidf import term_ranges_batch, tfidf_topk_batch
from repro.data.collections import normalize_patterns, pad_patterns
from repro.kernels import ops
from repro.serve import faults, obs
from repro.serve.planner import (
    ENGINE_BRUTE,
    ENGINE_CODES,
    ENGINE_EMPTY,
    ENGINE_ILCP,
    ENGINE_PDL,
    masked_ranges,
    plan_queries,
)

_BIG = np.iinfo(np.int32).max


# ---------------------------------------------------------------------------
# Shape buckets
# ---------------------------------------------------------------------------


def _bucket_batch(b: int) -> int:
    """Round a batch size up to the next power of two (>= 1)."""
    return 1 if b <= 1 else 1 << (b - 1).bit_length()


def _bucket_len(m: int) -> int:
    """Round a pattern length up to a multiple of 8 (>= 8)."""
    return max(8, -(-m // 8) * 8)


#: smallest dispatch-aware Brute-L window; windows grow in powers of two up
#: to the endpoint's ``max_buf``, so each bucket recompiles at most
#: lg(max_buf / floor) times as traffic reveals larger brute ranges.
BRUTE_WINDOW_FLOOR = 32

#: largest servable pattern-length bucket.  Patterns longer than this never
#: reach the device: ``normalize_patterns`` collapses them to empty queries
#: (empty results), so one absurd request cannot force a giant compile.
MAX_PATTERN_LEN = 4096


def _pow2_ceil(x: int) -> int:
    return 1 if x <= 1 else 1 << (x - 1).bit_length()


_ENGINE_NAMES = {ENGINE_EMPTY: "empty", ENGINE_BRUTE: "brute",
                 ENGINE_ILCP: "ilcp", ENGINE_PDL: "pdl"}


def _engine_rows(engine) -> dict:
    """Rows of a plan by the engine it assigned them to."""
    counts = np.bincount(engine, minlength=len(_ENGINE_NAMES))
    return {name: int(counts[code]) for code, name in _ENGINE_NAMES.items()}


def _to_device(*arrays):
    """Host arrays put on the device, one counted transfer each."""
    obs.add(transfers=len(arrays))
    return tuple(jnp.asarray(a) for a in arrays)


def _to_host(arrays, rows: int):
    """A program's outputs copied to the host (one blocking, counted
    transfer each), trimmed to the batch's true ``rows``."""
    obs.add(transfers=len(arrays))
    return tuple(np.asarray(a)[:rows] for a in arrays)


# ---------------------------------------------------------------------------
# Fused programs (pure functions of the index pytrees; compiled per bucket)
# ---------------------------------------------------------------------------


def _sorted_rows(docs):
    """Canonical listing layout: ascending doc ids, -1 padding at the end."""
    keys = jnp.where(docs < 0, _BIG, docs)
    s = jnp.sort(keys, axis=1)
    return jnp.where(s == _BIG, -1, s).astype(IDX)


def _plan_program(use_kernel, csa, sada, patterns, lengths, threshold, forced):
    with jax.named_scope("plan"):
        return plan_queries(
            csa, sada, patterns, lengths, threshold, forced,
            use_kernel=use_kernel,
        )


def _list_program(
    max_df, brute_win, max_buf, use_kernel, use_list_kernel,
    csa, ilcp, pdl, da, sada, patterns, lengths, threshold, forced,
):
    """list_docs as one program: plan, run all engines masked, select.

    ``brute_win`` is the Brute-L locate window — sized per compile bucket
    from planner occ stats (dispatch-aware), not the static ``max_buf``.
    ``use_list_kernel`` selects the ILCP executor's backend: the fused
    Pallas listing kernel (one launch — the program's second, after the
    planner's backward search) or the XLA vmap'd while_loop.
    """
    with jax.named_scope("plan"):
        plan = plan_queries(
            csa, sada, patterns, lengths, threshold, forced,
            use_kernel=use_kernel,
        )
    with jax.named_scope("brute"):
        bl, bh = masked_ranges(plan, ENGINE_BRUTE)
        docs_b, cnt_b, _ = brute_list_csa_batch(csa, bl, bh, brute_win, max_df)
    with jax.named_scope("ilcp"):
        il, ih = masked_ranges(plan, ENGINE_ILCP)
        docs_i, cnt_i = ilcp_list_docs_da_planned(
            ilcp, da, il, ih, max_df, use_kernel=use_list_kernel
        )
    with jax.named_scope("pdl"):
        pl, ph = masked_ranges(plan, ENGINE_PDL)
        docs_p, cnt_p = pdl_list_docs_batch(pdl, csa, pl, ph, max_df, max_buf)

    eng = plan.engine[:, None]
    docs = jnp.where(
        eng == ENGINE_BRUTE, docs_b,
        jnp.where(eng == ENGINE_ILCP, docs_i, docs_p),
    )
    docs = jnp.where(eng == ENGINE_EMPTY, -1, docs)
    cnt = jnp.where(
        plan.engine == ENGINE_BRUTE, cnt_b,
        jnp.where(plan.engine == ENGINE_ILCP, cnt_i, cnt_p),
    )
    cnt = jnp.where(plan.engine == ENGINE_EMPTY, 0, cnt).astype(IDX)
    return _sorted_rows(docs), cnt, plan


def _topk_program(
    k, max_df, brute_win, max_buf, use_kernel,
    csa, pdl_t, sada, patterns, lengths, threshold, forced,
):
    """top-k as one program.  Brute-assigned queries take the sorted-window
    path (exact tf within the occ window); ILCP has no top-k structure, so
    its queries ride the PDL lists, as in the paper's Section 6.3 lineup."""
    with jax.named_scope("plan"):
        plan = plan_queries(
            csa, sada, patterns, lengths, threshold, forced,
            use_kernel=use_kernel,
        )
    with jax.named_scope("brute"):
        bl, bh = masked_ranges(plan, ENGINE_BRUTE)
        d_b, c_b, f_b = brute_list_csa_batch(csa, bl, bh, brute_win, max_df)
        tb_docs, tb_tf = brute_topk_batch(d_b, c_b, f_b, k)

    with jax.named_scope("pdl"):
        use_pdl = (plan.engine == ENGINE_PDL) | (plan.engine == ENGINE_ILCP)
        pl = jnp.where(use_pdl, plan.lo, 0)
        ph = jnp.where(use_pdl, plan.hi, 0)
        tp_docs, tp_tf = pdl_topk_batch(pdl_t, csa, pl, ph, k, max_buf)

    is_brute = (plan.engine == ENGINE_BRUTE)[:, None]
    docs = jnp.where(is_brute, tb_docs, tp_docs)
    tfs = jnp.where(is_brute, tb_tf, tp_tf)
    empty = (plan.engine == ENGINE_EMPTY)[:, None]
    return jnp.where(empty, -1, docs), jnp.where(empty, 0, tfs), plan


def _tfidf_program(
    k, conjunctive, max_buf, use_kernel,
    csa, pdl_t, sada, patterns, lengths,
):
    """Multi-term ranked query as one program: fused term range search +
    batched ranked-AND/OR scoring.  ``use_kernel`` selects the same
    backward-search backend as the planner (True = one fused Pallas launch
    for the whole [Q*T] term batch)."""
    with jax.named_scope("plan"):
        ranges, valid = term_ranges_batch(
            csa, patterns, lengths, use_kernel=use_kernel
        )
    with jax.named_scope("tfidf"):
        return tfidf_topk_batch(
            pdl_t, csa, sada, ranges, valid, k, conjunctive, max_buf=max_buf
        )


def kernel_selection(parts, platform: str) -> tuple[bool, bool]:
    """(use_search_kernel, use_list_kernel) that ``build`` selects on
    ``platform`` for an index made of ``parts`` (one per docs shard, each
    with ``csa`` / ``ilcp`` / ``da``): each fused Pallas kernel on TPU when
    every part's resident tables fit its VMEM budget, the XLA executor
    otherwise."""
    on_tpu = platform == "tpu"
    search = on_tpu and all(
        ops.backward_search_fits(p.csa.wm.words, p.csa.wm.ones_prefix)
        for p in parts
    )
    listing = on_tpu and all(
        ops.ilcp_list_fits(p.ilcp.vilcp, p.ilcp.rmq.table, p.ilcp.run_starts,
                           p.da, d=p.ilcp.d)
        for p in parts
    )
    return search, listing


@dataclasses.dataclass
class RetrievalService:
    coll: Collection
    csa: object
    ilcp: object
    pdl_list: object
    pdl_topk: object
    sada: object
    da: object
    occ_df_threshold: float = 4.0     # paper: brute wins when occ/df < ~4
    use_search_kernel: bool = False   # fused Pallas backward search runs
    use_list_kernel: bool = False     # fused Pallas ILCP listing runs
    brute_window: int | None = None   # None = size per bucket from occ stats
    _cache: dict = dataclasses.field(default_factory=dict, repr=False)
    _brute_windows: dict = dataclasses.field(default_factory=dict, repr=False)
    compile_counts: dict = dataclasses.field(default_factory=dict, repr=False)
    #: per-structure CRC32s recorded by build-time validation (``repro.
    #: serve.validate``); a load path compares them via verify_fingerprints
    fingerprints: dict = dataclasses.field(default_factory=dict, repr=False)
    #: host seconds per build stage (suffix arrays, each structure, validate)
    build_seconds: dict = dataclasses.field(default_factory=dict, repr=False)

    # -- construction --------------------------------------------------------

    @classmethod
    def build(
        cls, coll: Collection, block_size: int = 64, beta: float = 16.0,
        sada_variant: str = "sparse", sample_rate: int = 16,
        use_search_kernel: bool | None = None,
        use_list_kernel: bool | None = None,
        brute_window: int | None = None,
        validate: bool = True,
        mesh=None,
        clock=time.perf_counter,
    ):
        if mesh is not None:
            # docs-axis sharded service: contiguous document shards, each
            # with its own index stack, merged on-device (docs/SHARDING.md)
            from repro.serve.sharded import ShardedRetrievalService

            return ShardedRetrievalService.build(
                coll, mesh, block_size=block_size, beta=beta,
                sada_variant=sada_variant, sample_rate=sample_rate,
                use_search_kernel=use_search_kernel,
                use_list_kernel=use_list_kernel,
                brute_window=brute_window, validate=validate,
            )
        seconds = {}
        last = clock()

        def stage(name, value):
            nonlocal last
            now = clock()
            seconds[name] = now - last
            last = now
            return value

        data = stage("suffix", build_suffix_data(coll))
        csa = stage("csa", build_csa(data, sample_rate=sample_rate))
        ilcp = stage("ilcp", build_ilcp(data))
        pdl_list = stage("pdl_list", build_pdl(
            data, block_size=block_size, beta=beta, mode="list"
        ))
        pdl_topk = stage("pdl_topk", build_pdl(
            data, block_size=block_size, beta=None, mode="topk"
        ))
        sada = stage("sada", build_sada(data, sada_variant))
        svc = cls(
            coll=coll,
            csa=csa,
            ilcp=ilcp,
            pdl_list=pdl_list,
            pdl_topk=pdl_topk,
            sada=sada,
            da=jnp.asarray(data.da),
            brute_window=brute_window,
            build_seconds=seconds,
        )
        # ``None`` selects from what the build can observe (platform and
        # VMEM budgets); an explicit flag is obeyed, and an index that does
        # not fit then fails to compile instead of changing path
        search_k, list_k = kernel_selection([svc], jax.default_backend())
        svc.use_search_kernel = (search_k if use_search_kernel is None
                                 else use_search_kernel)
        svc.use_list_kernel = list_k if use_list_kernel is None else use_list_kernel
        if validate:
            # structural invariants + checksums: a corrupted index is
            # rejected here, before it can serve wrong answers
            from repro.serve.validate import validate_service

            svc.fingerprints.update(stage("validate", validate_service(svc)))
        return svc

    # -- compile cache -------------------------------------------------------

    def _compiled(self, kind: str, statics: tuple, build_fn, args: tuple):
        """One AOT executable per (kind, statics) bucket.  The executable is
        lowered and compiled exactly once; subsequent calls with any batch
        that pads into the same bucket reuse it with zero retracing."""
        key = (kind, statics)
        exe = self._cache.get(key)
        if exe is None:
            faults.fire(f"compile:{kind}")
            with obs.span("serve.compile", kind=kind, statics=str(statics)):
                exe = jax.jit(build_fn()).lower(*args).compile()
            self._cache[key] = exe
            self.compile_counts[kind] = self.compile_counts.get(kind, 0) + 1
        return exe

    def _pad_batch(self, patterns):
        """Dense [B_bucket, m_bucket] pattern batch + lengths + true size.

        Every pattern passes the unified input gate first (see
        ``normalize_patterns``): structurally bad input raises
        InvalidQueryError; empty / over-long / out-of-alphabet patterns
        become empty queries with empty results."""
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
        return *_to_device(out, lns), B

    def _knobs(self, engine: str):
        return _to_device(np.float32(self.occ_df_threshold),
                          np.int32(ENGINE_CODES[engine]))

    def _pack(self, patterns, engine: str):
        """(patterns, lengths, threshold, forced, true batch size) of one
        planned call, on the device."""
        with obs.span("serve.pack") as sp:
            pats, lens, B = self._pad_batch(patterns)
            sp.set(rows=B, padded_rows=pats.shape[0])
            return (pats, lens, *self._knobs(engine), B)

    def _brute_window_for(self, kind: str, bucket_key: tuple, patterns,
                          engine: str, max_buf: int) -> int:
        """Dispatch-aware Brute-L window (ROADMAP item): sized per compile
        bucket from the planner's occ stats instead of the static
        ``max_buf``.

        The plan pass is one (cached) compiled program; the window is the
        power-of-two cover of the largest occ among brute-assigned queries,
        clamped to [BRUTE_WINDOW_FLOOR, max_buf], and grows monotonically
        per bucket so recompiles are bounded by lg(max_buf).  Results are
        unchanged: the brute executor masks the window against each query's
        true occ, and queries past max_buf truncate exactly as the
        reference path does."""
        if self.brute_window is not None:
            return min(self.brute_window, max_buf)
        with obs.span("serve.window_plan") as sp:
            plan = self.plan(patterns, engine)
            sp.set(engine_rows=lambda: _engine_rows(plan["engine"]))
        occ = plan["occ"][plan["engine"] == ENGINE_BRUTE]
        needed = int(occ.max()) if occ.size else 0
        win = min(max(_pow2_ceil(needed), BRUTE_WINDOW_FLOOR), max_buf)
        key = (kind, bucket_key)
        win = max(win, self._brute_windows.get(key, 0))
        self._brute_windows[key] = win
        return win

    # -- planned endpoints (single compiled program per shape bucket) --------

    def plan(self, patterns, engine: str = "auto"):
        """Query plan for a pattern batch: host arrays (lo, hi, occ, df,
        engine), trimmed to the true batch size."""
        pats, lens, thresh, forced, B = self._pack(patterns, engine)
        faults.fire("plan")
        exe = self._compiled(
            "plan", (pats.shape,),
            lambda: functools.partial(_plan_program, self.use_search_kernel),
            (self.csa, self.sada, pats, lens, thresh, forced),
        )
        names = ("lo", "hi", "occ", "df", "engine")
        with obs.span("serve.run"):
            plan = exe(self.csa, self.sada, pats, lens, thresh, forced)
            return dict(zip(names, _to_host(
                [getattr(plan, name) for name in names], B)))

    def ranges(self, patterns):
        p = self.plan(patterns)
        norm = normalize_patterns(
            patterns, sigma=self.coll.sigma, max_len=MAX_PATTERN_LEN
        )
        lens = np.asarray([len(x) for x in norm], np.int32)
        return p["lo"], p["hi"], lens

    def count(self, patterns, engine: str = "auto"):
        """df per pattern (Sada variant; ILCP counting cross-checks).

        ``engine="reference"`` computes the same counts through the
        per-query host path — the runtime's last-resort degradation."""
        if engine.startswith("reference"):
            return self._ranges_dfs(patterns)[2]
        return self.plan(patterns)["df"]

    def count_ilcp(self, patterns):
        lo, hi, lens = self.ranges(patterns)
        return np.asarray(
            ilcp_count_docs_batch(
                self.ilcp, jnp.asarray(lo), jnp.asarray(hi), jnp.asarray(lens)
            )
        )

    def list_docs_arrays(self, patterns, max_df: int = 256, engine: str = "auto",
                         max_buf: int = 4096):
        """Array-level listing endpoint: (docs int32[B, max_df] ascending,
        -1 padded, counts int32[B]) — the zero-copy serving layout."""
        if not len(patterns):
            return np.zeros((0, max_df), np.int32), np.zeros(0, np.int32)
        pats, lens, thresh, forced, B = self._pack(patterns, engine)
        win = self._brute_window_for(
            "list", (pats.shape, max_df, max_buf), patterns, engine, max_buf
        )
        faults.fire("executor:list")
        args = (self.csa, self.ilcp, self.pdl_list, self.da, self.sada,
                pats, lens, thresh, forced)
        exe = self._compiled(
            "list", (pats.shape, max_df, win, max_buf),
            lambda: functools.partial(
                _list_program, max_df, win, max_buf,
                self.use_search_kernel, self.use_list_kernel,
            ),
            args,
        )
        with obs.span("serve.run"):
            docs, cnt, _plan = exe(*args)
            out = _to_host((docs, cnt), B)
        return faults.poison("executor:list", out)

    def list_docs(self, patterns, max_df: int = 256, engine: str = "auto",
                  max_buf: int = 4096):
        """Document listing with the paper's df/occ dispatch policy.

        ``engine``: "auto" | "brute" | "ilcp" | "pdl" run on the batched
        engine; "reference" (or "reference:<engine>") runs the per-query
        host loop — the parity oracle."""
        if engine.startswith("reference"):
            sub = engine.split(":", 1)[1] if ":" in engine else "auto"
            return self._list_docs_reference(patterns, max_df, sub, max_buf)
        docs, cnt = self.list_docs_arrays(patterns, max_df, engine, max_buf)
        return [docs[i, : cnt[i]].tolist() for i in range(len(cnt))]

    def topk_arrays(self, patterns, k: int = 10, engine: str = "auto",
                    max_buf: int = 4096):
        """Array-level top-k endpoint: (docs int32[B, k] padded -1,
        tf int32[B, k]), ranked by (tf desc, id asc)."""
        if not len(patterns):
            return np.zeros((0, k), np.int32), np.zeros((0, k), np.int32)
        pats, lens, thresh, forced, B = self._pack(patterns, engine)
        max_df = self._topk_max_df(max_buf)
        win = self._brute_window_for(
            "topk", (pats.shape, k, max_buf), patterns, engine, max_buf
        )
        faults.fire("executor:topk")
        args = (self.csa, self.pdl_topk, self.sada, pats, lens, thresh, forced)
        exe = self._compiled(
            "topk", (pats.shape, k, max_df, win, max_buf),
            lambda: functools.partial(
                _topk_program, k, max_df, win, max_buf, self.use_search_kernel
            ),
            args,
        )
        with obs.span("serve.run"):
            docs, tfs, _plan = exe(*args)
            out = _to_host((docs, tfs), B)
        return faults.poison("executor:topk", out)

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

    def tfidf_arrays(self, queries, k: int = 10, conjunctive: bool = False,
                     max_terms: int = 4, max_buf: int = 2048):
        """Array-level ranked multi-term endpoint: (docs int32[Q, k] padded
        -1, scores f32[Q, k])."""
        Q = len(queries)
        if Q == 0:
            return np.zeros((0, k), np.int32), np.zeros((0, k), np.float32)
        with obs.span("serve.pack") as sp:
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
            pats, lens = _to_device(pats, lens)
            sp.set(rows=Q, padded_rows=Qb)
        faults.fire("executor:tfidf")
        args = (self.csa, self.pdl_topk, self.sada, pats, lens)
        exe = self._compiled(
            "tfidf", (pats.shape, k, conjunctive, max_buf),
            lambda: functools.partial(
                _tfidf_program, k, conjunctive, max_buf, self.use_search_kernel
            ),
            args,
        )
        with obs.span("serve.run"):
            out = _to_host(exe(*args), Q)
        return faults.poison("executor:tfidf", out)

    def tfidf(self, queries, k: int = 10, conjunctive: bool = False,
              max_terms: int = 4, max_buf: int = 2048, engine: str = "auto"):
        """queries: list of term-pattern lists.  Returns ranked (doc, score)."""
        if engine.startswith("reference"):
            return self._tfidf_reference(queries, k, conjunctive, max_terms, max_buf)
        docs, scores = self.tfidf_arrays(queries, k, conjunctive, max_terms, max_buf)
        return [
            [(int(d), float(s)) for d, s in zip(docs[i], scores[i]) if d >= 0]
            for i in range(docs.shape[0])
        ]

    # -- reference per-query path (parity oracle) ----------------------------

    def _dispatch(self, occ: int, df: int, engine: str) -> str:
        if engine != "auto":
            return engine
        return "brute" if occ < self.occ_df_threshold * max(df, 1) else "pdl"

    def _ranges_dfs(self, patterns):
        # same input gate as the batched path (_pad_batch) so the reference
        # oracle and the planned pipeline agree on hardened inputs
        patterns = normalize_patterns(
            patterns, sigma=self.coll.sigma, max_len=MAX_PATTERN_LEN
        )
        pats, lens = pad_patterns(patterns)
        from repro.core.csa import csa_search_batch

        lo, hi = csa_search_batch(self.csa, jnp.asarray(pats), jnp.asarray(lens))
        # same contract as the planner: zero-length patterns are empty, not
        # the full range (keeps reference/batched parity bit-exact)
        hi = jnp.where(jnp.asarray(lens) > 0, hi, lo)
        dfs = sada_count_batch(self.sada, lo, hi)
        return np.asarray(lo), np.asarray(hi), np.asarray(dfs)

    def _list_docs_reference(self, patterns, max_df, engine, max_buf):
        if not len(patterns):
            return []
        lo, hi, dfs = self._ranges_dfs(patterns)
        out = []
        for qi in range(len(lo)):
            l, h = int(lo[qi]), int(hi[qi])
            if l >= h:
                out.append([])
                continue
            eng = self._dispatch(h - l, int(dfs[qi]), engine)
            if eng == "brute":
                # window min(occ, max_buf) covers the same positions as the
                # batched executor's fixed max_buf window (validity-masked)
                docs, cnt, _ = brute_list_csa(
                    self.csa, l, h, min(h - l, max_buf), max_df
                )
            elif eng == "ilcp":
                docs, cnt = ilcp_list_docs_da(self.ilcp, self.da, l, h, max_df)
            else:
                docs, cnt = pdl_list_docs(
                    self.pdl_list, self.csa, l, h, max_df, max_buf=max_buf
                )
            out.append(sorted(np.asarray(docs)[: int(cnt)].tolist()))
        return out

    def _topk_max_df(self, max_buf: int) -> int:
        return min(self.coll.d + 1, max_buf)

    def _topk_reference(self, patterns, k, engine, max_buf):
        if not len(patterns):
            return []
        lo, hi, dfs = self._ranges_dfs(patterns)
        max_df = self._topk_max_df(max_buf)
        out = []
        for qi in range(len(lo)):
            l, h = int(lo[qi]), int(hi[qi])
            if l >= h:
                out.append([])
                continue
            eng = self._dispatch(h - l, int(dfs[qi]), engine)
            if eng == "brute":
                d, c, f = brute_list_csa(
                    self.csa, l, h, min(h - l, max_buf), max_df
                )
                docs, tfs = brute_topk(d, c, f, k)
            else:
                docs, tfs = pdl_topk(self.pdl_topk, self.csa, l, h, k,
                                     max_buf=max_buf)
            out.append(
                [(int(d), int(t)) for d, t in zip(np.asarray(docs), np.asarray(tfs))
                 if d >= 0]
            )
        return out

    def _tfidf_reference(self, queries, k, conjunctive, max_terms, max_buf):
        Q = len(queries)
        ranges = np.zeros((Q, max_terms, 2), np.int32)
        valid = np.zeros((Q, max_terms), bool)
        for qi, terms in enumerate(queries):
            if not terms:
                continue
            lo, hi, _ = self._ranges_dfs(terms[:max_terms])
            for ti in range(len(lo)):
                ranges[qi, ti] = (lo[ti], hi[ti])
                valid[qi, ti] = True
        docs, scores = tfidf_topk_batch(
            self.pdl_topk, self.csa, self.sada, ranges, valid, k, conjunctive,
            max_buf=max_buf,
        )
        out = []
        for qi in range(Q):
            out.append(
                [(int(d), float(s)) for d, s in zip(np.asarray(docs[qi]),
                                                    np.asarray(scores[qi])) if d >= 0]
            )
        return out

    # -- introspection --------------------------------------------------------

    #: endpoint kinds with a compiled program per shape bucket (the compile
    #: cache's key space; ``count`` rides the ``plan`` program)
    ENDPOINT_KINDS = ("plan", "list", "topk", "tfidf")

    def endpoint_program(self, kind: str, *, use_kernel: bool | None = None,
                         use_list_kernel: bool | None = None,
                         max_df: int = 64, k: int = 10, max_buf: int = 512,
                         conjunctive: bool = False):
        """The exact fused program + example arguments the compile cache
        would lower for ``kind`` — exposed so ``repro.analysis`` can audit
        the jaxpr of every endpoint (launch counts, callbacks, dtypes,
        VMEM) without executing anything.

        Returns ``(fn, args_builder)`` where ``args_builder(B, m)`` makes
        the padded example arguments for a (batch-bucket, length-bucket)
        signature.  ``use_kernel=None`` / ``use_list_kernel=None`` inherit
        the service's backends (the latter only matters to ``list``)."""
        if use_kernel is None:
            use_kernel = self.use_search_kernel
        if use_list_kernel is None:
            use_list_kernel = self.use_list_kernel
        if kind == "plan":
            fn = functools.partial(_plan_program, use_kernel)

            def args(B, m):
                return (self.csa, self.sada) + self._audit_batch(B, m)
        elif kind == "list":
            fn = functools.partial(
                _list_program, max_df, min(BRUTE_WINDOW_FLOOR, max_buf),
                max_buf, use_kernel, use_list_kernel,
            )

            def args(B, m):
                return (self.csa, self.ilcp, self.pdl_list, self.da,
                        self.sada) + self._audit_batch(B, m)
        elif kind == "topk":
            fn = functools.partial(
                _topk_program, k, self._topk_max_df(max_buf),
                min(BRUTE_WINDOW_FLOOR, max_buf), max_buf, use_kernel,
            )

            def args(B, m):
                return (self.csa, self.pdl_topk, self.sada) + \
                    self._audit_batch(B, m)
        elif kind == "tfidf":
            fn = functools.partial(
                _tfidf_program, k, conjunctive, max_buf, use_kernel
            )

            def args(B, m):
                pats = jnp.zeros((B, 2, _bucket_len(m)), jnp.int32)
                lens = jnp.ones((B, 2), jnp.int32)
                return (self.csa, self.pdl_topk, self.sada, pats, lens)
        else:
            raise ValueError(f"unknown endpoint kind {kind!r}")
        return fn, args

    def _audit_batch(self, B: int, m: int):
        pats = jnp.zeros((B, _bucket_len(m)), jnp.int32)
        lens = jnp.ones(B, jnp.int32)
        return pats, lens, jnp.float32(self.occ_df_threshold), jnp.int32(-1)

    def trace_endpoint(self, kind: str, B: int = 8, m: int = 8, **kw):
        """ClosedJaxpr of one endpoint program at a (B, m) bucket — the
        auditor's raw material."""
        fn, args = self.endpoint_program(kind, **kw)
        return jax.make_jaxpr(fn)(*args(_bucket_batch(B), m))

    def compiled_executables(self) -> dict:
        """The live AOT compile cache, keyed (kind, statics) — exposed for
        post-hoc audits of what this process actually lowered."""
        return dict(self._cache)

    def space_report(self) -> dict:
        """Bits-per-character accounting in the paper's units."""
        n = self.coll.n
        return {
            "n": n,
            "d": self.coll.d,
            "csa_rlcsa_bpc": self.csa.modeled_bits_rlcsa() / n,
            "ilcp_listing_bpc": self.ilcp.modeled_bits_listing() / n,
            "ilcp_counting_bpc": self.ilcp.modeled_bits_counting() / n,
            "pdl_list_bpc": self.pdl_list.modeled_bits() / n,
            "pdl_topk_bpc": self.pdl_topk.modeled_bits() / n,
            "sada_bpc": self.sada.modeled_bits() / n,
            "bwt_runs": self.csa.bwt_runs,
            "ilcp_runs": self.ilcp.nruns,
        }
