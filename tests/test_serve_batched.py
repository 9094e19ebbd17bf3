"""Batched query-engine tests: the planned, masked, jit-compiled pipeline
must be bit-identical to the ``engine="reference"`` per-query path on
randomized repetitive collections, and the shape-bucketing cache must
compile at most once per bucket."""

import numpy as np
import jax
import pytest

from repro.data.collections import SyntheticSpec, generate, random_substring_patterns
from repro.serve.planner import (
    ENGINE_BRUTE,
    ENGINE_EMPTY,
    ENGINE_ILCP,
    ENGINE_PDL,
)
from repro.serve.retrieval import RetrievalService

MAX_BUF = 512

SPECS = {
    "version": SyntheticSpec("version", n_base=3, n_variants=7, base_len=90,
                             mutation_rate=0.01, seed=5),
    "dna": SyntheticSpec("dna", n_base=1, n_variants=16, base_len=150,
                         mutation_rate=0.003, seed=9),
}


@pytest.fixture(scope="module", params=list(SPECS))
def svc_pats(request):
    coll = generate(SPECS[request.param])
    svc = RetrievalService.build(coll, block_size=16, beta=8.0)
    pats = random_substring_patterns(coll, 300, 5, 24)
    assert pats, "workload generation produced no patterns"
    return svc, pats


# ---------------------------------------------------------------------------
# Parity: batched pipeline == reference per-query path, all engines
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine", ["auto", "brute", "ilcp", "pdl"])
def test_list_docs_parity(svc_pats, engine):
    svc, pats = svc_pats
    got = svc.list_docs(pats[:10], max_df=64, engine=engine, max_buf=MAX_BUF)
    ref = svc.list_docs(
        pats[:10], max_df=64, engine=f"reference:{engine}", max_buf=MAX_BUF
    )
    assert got == ref


@pytest.mark.parametrize("engine", ["auto", "brute", "pdl"])
def test_topk_parity(svc_pats, engine):
    svc, pats = svc_pats
    got = svc.topk(pats[:10], k=5, engine=engine, max_buf=MAX_BUF)
    ref = svc.topk(pats[:10], k=5, engine=f"reference:{engine}", max_buf=MAX_BUF)
    assert got == ref


@pytest.mark.parametrize("conjunctive", [False, True])
def test_tfidf_parity(svc_pats, conjunctive):
    svc, pats = svc_pats
    queries = [[pats[0], pats[1]], [pats[2]], [pats[3], pats[0], pats[2]]]
    got = svc.tfidf(queries, k=5, conjunctive=conjunctive, max_buf=MAX_BUF)
    ref = svc.tfidf(
        queries, k=5, conjunctive=conjunctive, max_buf=MAX_BUF,
        engine="reference",
    )
    assert got == ref


def test_missing_pattern_is_empty(svc_pats):
    svc, pats = svc_pats
    # a symbol outside the collection alphabet never occurs; a zero-length
    # pattern is empty by the serving contract (not the full range)
    bogus = np.full(6, svc.coll.sigma + 3, np.int32)
    empty = np.zeros(0, np.int32)
    batch = [pats[0], bogus, pats[1], empty]
    got = svc.list_docs(batch, max_df=32, max_buf=MAX_BUF)
    ref = svc.list_docs(batch, max_df=32, engine="reference", max_buf=MAX_BUF)
    assert got == ref
    assert got[1] == [] and got[3] == []
    assert svc.topk(batch, k=3, max_buf=MAX_BUF)[1] == []
    assert int(svc.count(batch)[1]) == 0 and int(svc.count(batch)[3]) == 0


def test_search_kernel_path_parity():
    """The fused Pallas backward-search path (use_search_kernel=True,
    interpret mode on CPU) must be bit-identical to engine="reference",
    including missing patterns (out-of-alphabet symbol) and empty rows."""
    coll = generate(SPECS["version"])
    svc = RetrievalService.build(
        coll, block_size=16, beta=8.0, use_search_kernel=True
    )
    assert svc.use_search_kernel
    pats = random_substring_patterns(coll, 60, 5, 24)
    bogus = np.full(6, coll.sigma + 3, np.int32)
    batch = pats[:12] + [bogus, np.zeros(0, np.int32)]

    got = svc.list_docs(batch, max_df=64, max_buf=MAX_BUF)
    ref = svc.list_docs(batch, max_df=64, engine="reference", max_buf=MAX_BUF)
    assert got == ref
    assert got[-2] == [] and got[-1] == []

    assert svc.topk(batch, k=5, max_buf=MAX_BUF) == svc.topk(
        batch, k=5, engine="reference", max_buf=MAX_BUF
    )
    assert np.array_equal(svc.count(batch), svc.count_ilcp(batch))

    # plan parity against a kernel-free service over the same collection
    plain = RetrievalService.build(
        coll, block_size=16, beta=8.0, use_search_kernel=False
    )
    pk, pf = svc.plan(batch), plain.plan(batch)
    for name in ("lo", "hi", "occ", "df", "engine"):
        assert np.array_equal(pk[name], pf[name]), name


def test_plan_engine_assignment(svc_pats):
    svc, pats = svc_pats
    plan = svc.plan(pats[:12])
    assert set(plan["engine"]).issubset(
        {ENGINE_EMPTY, ENGINE_BRUTE, ENGINE_ILCP, ENGINE_PDL}
    )
    nonempty = plan["occ"] > 0
    # auto never assigns ILCP (the paper's recommendation is brute-vs-PDL)
    assert np.all(np.isin(plan["engine"][nonempty], [ENGINE_BRUTE, ENGINE_PDL]))
    forced = svc.plan(pats[:12], engine="ilcp")
    assert np.all(forced["engine"][nonempty] == ENGINE_ILCP)
    # the policy itself: occ < threshold * df -> brute
    occ, df = plan["occ"][nonempty], np.maximum(plan["df"][nonempty], 1)
    want = np.where(occ < svc.occ_df_threshold * df, ENGINE_BRUTE, ENGINE_PDL)
    assert np.array_equal(plan["engine"][nonempty], want)


def test_count_matches_truth(svc_pats):
    svc, pats = svc_pats
    from repro.core.suffix import build_suffix_data, sa_range_for_pattern

    data = build_suffix_data(svc.coll)
    got = svc.count(pats[:12])
    for i, p in enumerate(pats[:12]):
        lo, hi = sa_range_for_pattern(data, p)
        assert int(got[i]) == len(set(data.da[lo:hi].tolist()))


# ---------------------------------------------------------------------------
# Shape-bucketing compile cache
# ---------------------------------------------------------------------------


def test_one_compile_per_bucket():
    coll = generate(
        SyntheticSpec("version", n_base=2, n_variants=5, base_len=80,
                      mutation_rate=0.01, seed=11)
    )
    # brute_window pinned: the derived window has dedicated tests below
    svc = RetrievalService.build(
        coll, block_size=16, beta=8.0, brute_window=MAX_BUF
    )
    pats = random_substring_patterns(coll, 200, 5, 16)
    assert len(pats) >= 9

    compile_events = []
    recording = []
    jax.monitoring.register_event_listener(
        lambda name, **kw: compile_events.append(name)
        if recording and "compile" in name
        else None
    )

    # batch sizes 5 and 7 land in the same power-of-two bucket (8)
    svc.list_docs(pats[:5], max_df=32, max_buf=MAX_BUF)
    assert svc.compile_counts["list"] == 1

    recording.append(True)  # arm the listener: bucket is warm now
    out7 = svc.list_docs(pats[:7], max_df=32, max_buf=MAX_BUF)
    out5 = svc.list_docs(pats[:5], max_df=32, engine="pdl", max_buf=MAX_BUF)
    recording.clear()

    assert svc.compile_counts["list"] == 1, "same bucket must not recompile"
    assert not compile_events, f"hot path triggered XLA compiles: {compile_events}"
    assert len(out7) == 7 and len(out5) == 5

    # a new bucket (16) compiles exactly once more
    svc.list_docs(pats[:9], max_df=32, max_buf=MAX_BUF)
    assert svc.compile_counts["list"] == 2
    svc.list_docs(pats[:16], max_df=32, max_buf=MAX_BUF)
    assert svc.compile_counts["list"] == 2

    # engine mode is traced, not static: no recompile across engines
    for engine in ("auto", "brute", "ilcp", "pdl"):
        svc.list_docs(pats[:7], max_df=32, engine=engine, max_buf=MAX_BUF)
    assert svc.compile_counts["list"] == 2

    # other endpoints keep their own per-bucket tally
    svc.topk(pats[:5], k=3, max_buf=MAX_BUF)
    svc.topk(pats[:8], k=3, max_buf=MAX_BUF)
    assert svc.compile_counts["topk"] == 1
    svc.tfidf([[pats[0], pats[1]]], k=3, max_buf=MAX_BUF)
    svc.tfidf([[pats[2]]], k=3, max_buf=MAX_BUF)
    assert svc.compile_counts["tfidf"] == 1


def _least_cover(threshold, d):
    """Least integer occ that the planner's float32 rule does not send to
    Brute-L at df = d, found by walking up from 0."""
    edge = np.float32(threshold) * np.float32(max(d, 1))
    w = 0
    while np.float32(w) < edge:
        w += 1
    return w


def _ran_window(svc, kind):
    """The Brute-L windows of the ``kind`` programs in the compile cache
    (the static just before ``max_buf``)."""
    return {statics[-2] for k, statics in svc._cache if k == kind}


def _served(svc, kind, pats, engine):
    if kind == "list":
        return svc.list_docs(pats, max_df=32, engine=engine, max_buf=MAX_BUF)
    return svc.topk(pats, k=4, engine=engine, max_buf=MAX_BUF)


@pytest.fixture(scope="module")
def window_svc():
    # 8 documents: a power of two, so that the edge test's thresholds and
    # float32 edges are exact
    coll = generate(
        SyntheticSpec("version", n_base=2, n_variants=4, base_len=80,
                      mutation_rate=0.01, seed=11)
    )
    svc = RetrievalService.build(coll, block_size=16, beta=8.0)
    pats = random_substring_patterns(coll, 100, 3, 12)
    assert len(pats) >= 6
    return svc, pats


@pytest.mark.parametrize("engine", ["auto", "brute"])
@pytest.mark.parametrize("kind", ["list", "topk"])
def test_auto_brute_window(window_svc, kind, engine):
    """The Brute-L window is derived from the threshold and d, not from a
    plan of the batch: min(max_buf, least cover) for auto dispatch,
    max_buf for forced brute; answers stay bit-identical to the reference
    path, and another batch of the same bucket compiles nothing."""
    svc, pats = window_svc
    assert svc.brute_window is None
    want = (MAX_BUF if engine == "brute"
            else min(MAX_BUF, _least_cover(svc.occ_df_threshold, svc.coll.d)))
    if engine == "auto":
        assert (svc.plan(pats[:5])["engine"] == ENGINE_BRUTE).any()
    ref = "reference" if engine == "auto" else "reference:brute"
    assert _served(svc, kind, pats[:5], engine) == \
        _served(svc, kind, pats[:5], ref)
    assert want in _ran_window(svc, kind)
    assert svc._brute_window_for(engine, MAX_BUF) == want

    # batches of 5 and 6 share the bucket of 8
    compiles, cached = dict(svc.compile_counts), len(svc._cache)
    assert _served(svc, kind, pats[:6], engine) == \
        _served(svc, kind, pats[:6], ref)
    assert svc.compile_counts == compiles and len(svc._cache) == cached


@pytest.mark.parametrize("edge", ["below", "at"])
def test_brute_window_float32_edge(window_svc, edge, monkeypatch):
    """A pattern in every document whose occ sits at threshold * df - 1
    (Brute-L, inside the window) or at threshold * df (PDL): the derived
    window is the planner's own float32 edge, and list and top-k stay
    bit-identical to the reference path."""
    svc, pats = window_svc
    d = svc.coll.d
    plan = svc.plan(pats)
    every = [i for i in range(len(pats)) if plan["df"][i] == d]
    assert every, "no pattern occurs in every document"
    top = max(every, key=lambda i: plan["occ"][i])
    occ = int(plan["occ"][top])
    assert d & (d - 1) == 0
    threshold = (occ + 1) / d if edge == "below" else occ / d
    monkeypatch.setattr(svc, "occ_df_threshold", threshold)
    (engine,) = svc.plan([pats[top]])["engine"]
    assert engine == (ENGINE_BRUTE if edge == "below" else ENGINE_PDL)
    win = svc._brute_window_for("auto", MAX_BUF)
    assert win == _least_cover(threshold, d) == (occ + 1 if edge == "below"
                                                 else occ)
    batch = [pats[top]] + pats[:4]
    for kind in ("list", "topk"):
        assert _served(svc, kind, batch, "auto") == \
            _served(svc, kind, batch, "reference")
        assert win in _ran_window(svc, kind)


@pytest.mark.parametrize("sharded", [
    pytest.param(False, id="flat"),
    pytest.param(True, id="sharded", marks=pytest.mark.skipif(
        jax.device_count() < 4, reason="the docs mesh needs >= 4 devices")),
])
def test_list_and_topk_run_no_plan_program(sharded, monkeypatch):
    """A ``list`` or ``topk`` call runs one program, its own: the Brute-L
    window needs no plan of the batch, flat or on a docs mesh."""
    coll = generate(
        SyntheticSpec("version", n_base=2, n_variants=4, base_len=60,
                      mutation_rate=0.01, seed=3)
    )
    mesh = None
    if sharded:
        from repro.dist.sharding import make_docs_mesh

        mesh = make_docs_mesh(4)
    svc = RetrievalService.build(coll, block_size=16, beta=8.0,
                                 validate=False, mesh=mesh)
    pats = random_substring_patterns(coll, 20, 4, 8)
    cls = type(svc)

    def no_plan(*a, **kw):
        raise AssertionError("a list/topk call planned its batch")

    ran = []
    compiled = cls._compiled
    monkeypatch.setattr(cls, "plan", no_plan)
    monkeypatch.setattr(cls, "_compiled", lambda self, kind, *a:
                        ran.append(kind) or compiled(self, kind, *a))
    svc.list_docs(pats[:3], max_df=coll.d + 1, max_buf=MAX_BUF)
    svc.topk(pats[:3], k=3, max_buf=MAX_BUF)
    assert ran == ["list", "topk"]
    assert svc.compile_counts == {"list": 1, "topk": 1}


def test_empty_batch():
    coll = generate(
        SyntheticSpec("version", n_base=2, n_variants=4, base_len=60,
                      mutation_rate=0.01, seed=3)
    )
    svc = RetrievalService.build(coll, block_size=16, beta=8.0)
    assert svc.list_docs([]) == []
    assert svc.topk([]) == []
    assert svc.tfidf([]) == []
    assert svc.count([]).shape == (0,)


def test_list_kernel_service_parity_oob_and_compile():
    """A ``use_list_kernel=True`` service answers bit-identically to the
    reference path — including patterns with out-of-alphabet symbols,
    which must stay empty through the fused listing kernel — and keeps
    the one-compile-per-bucket discipline."""
    import numpy as np

    coll = generate(SPECS["version"])
    svc = RetrievalService.build(
        coll, block_size=16, beta=8.0, brute_window=MAX_BUF,
        use_list_kernel=True,
    )
    assert svc.use_list_kernel is True
    pats = random_substring_patterns(coll, 200, 5, 12)
    pats_oob = pats[:6] + [np.asarray([coll.sigma + 3, 1, 2], np.int32)]
    for eng in ("auto", "ilcp", "brute", "pdl"):
        got = svc.list_docs(pats_oob, max_df=32, engine=eng, max_buf=MAX_BUF)
        want = svc.list_docs(
            pats_oob, max_df=32, max_buf=MAX_BUF,
            engine="reference" if eng == "auto" else f"reference:{eng}",
        )
        assert got == want, eng
        assert got[-1] == [], "OOB symbol must produce an empty answer"

    before = svc.compile_counts["list"]
    svc.list_docs(pats[:5], max_df=32, max_buf=MAX_BUF)
    svc.list_docs(pats[:7], max_df=32, max_buf=MAX_BUF)
    assert svc.compile_counts["list"] == before, \
        "same bucket must not recompile on the listing-kernel backend"
