"""Docs-mesh sharded serving: cross-shard merge parity + kernel restoration.

Every endpoint of ``ShardedRetrievalService`` must be bit-identical to the
single-device reference oracle — the merges (psum counting, offset+sort
listing, (tf desc, id asc) top-k, global-df tf-idf scoring) are exact
algebra over document-disjoint shards, not approximations.  The suite also
proves the tentpole perf claim: an index whose wavelet matrix is over the
fused kernel's VMEM budget (and therefore falls back to the XLA pair
descent unsharded) serves through the Pallas kernel again once sharded,
one launch per shard.

Host devices are virtualized by conftest (XLA_FLAGS
``--xla_force_host_platform_device_count=8``), so the docs mesh is real:
the merge stages run as shard_map programs over 4 devices, not a
single-device simulation.
"""

import numpy as np
import pytest

import jax

from repro.core.suffix import concat_documents
from repro.data.collections import SyntheticSpec, generate, random_substring_patterns
from repro.dist.sharding import doc_shard_bounds, make_docs_mesh
from repro.errors import IndexIntegrityError
from repro.kernels import ops
from repro.serve import faults
from repro.serve.faults import FaultSpec
from repro.serve.retrieval import RetrievalService, kernel_selection
from repro.serve.runtime import RuntimeConfig, ServeRuntime
from repro.serve.sharded import ShardedRetrievalService

pytestmark = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="docs-mesh tests need >= 4 (virtual) devices",
)

N_SHARDS = 4
GENEROUS = 300.0


def _resident_bytes(csa):
    return ops.backward_search_resident_bytes(csa.wm.words, csa.wm.ones_prefix)


@pytest.fixture(scope="module")
def setup():
    coll = generate(SyntheticSpec(
        "version", n_base=3, n_variants=7, base_len=90,
        mutation_rate=0.01, seed=5,
    ))
    base = RetrievalService.build(coll, block_size=16, beta=8.0,
                                  validate=False)
    mesh = make_docs_mesh(N_SHARDS)
    # mesh= routes RetrievalService.build through the sharded builder;
    # validate=True covers the shard-keyed fingerprint path
    svc = RetrievalService.build(coll, mesh=mesh, block_size=16, beta=8.0,
                                 validate=True)
    assert isinstance(svc, ShardedRetrievalService)
    pats = random_substring_patterns(coll, 24, 3, 14)
    assert pats
    return coll, base, svc, pats


# ---------------------------------------------------------------------------
# Parity: every endpoint bit-identical to the single-device oracle
# ---------------------------------------------------------------------------
# non-truncating regime: max_df covers every document, buffers cover every
# occurrence, so sharded/unsharded differ only if the merge algebra is wrong


def _maxdf(coll):
    return coll.d + 1


def test_count_parity(setup):
    coll, base, svc, pats = setup
    got = svc.count(pats)
    want = np.asarray(base.count(pats, engine="reference"))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(svc.count(pats, engine="reference"), want)


def test_list_parity(setup):
    coll, base, svc, pats = setup
    want = base.list_docs(pats, max_df=_maxdf(coll), engine="reference",
                          max_buf=4096)
    assert svc.list_docs(pats, max_df=_maxdf(coll), max_buf=4096) == want
    assert svc.list_docs(pats, max_df=_maxdf(coll), engine="reference",
                         max_buf=4096) == want


def test_topk_parity(setup):
    coll, base, svc, pats = setup
    for k in (1, 3, coll.d):
        want = base.topk(pats, k=k, engine="reference", max_buf=4096)
        assert svc.topk(pats, k=k, max_buf=4096) == want
        assert svc.topk(pats, k=k, engine="reference", max_buf=4096) == want


@pytest.mark.parametrize("conjunctive", [False, True])
def test_tfidf_parity_exact_floats(setup, conjunctive):
    coll, base, svc, pats = setup
    queries = [pats[i:i + 2] for i in range(0, 12, 2)]
    want = base.tfidf(queries, k=coll.d, conjunctive=conjunctive,
                      max_buf=4096, engine="reference")
    got = svc.tfidf(queries, k=coll.d, conjunctive=conjunctive, max_buf=4096)
    # exact float equality: per-document scores are computed with the
    # global df/N weights inside the owning shard, so no reassociation
    assert got == want
    assert svc.tfidf(queries, k=coll.d, conjunctive=conjunctive,
                     max_buf=4096, engine="reference") == want


def test_plan_merges_global_occ_df(setup):
    coll, base, svc, pats = setup
    plan = svc.plan(pats)
    want = base.plan(pats)
    assert plan["lo"].shape == (N_SHARDS, len(pats))
    np.testing.assert_array_equal(plan["occ"], want["occ"])
    np.testing.assert_array_equal(plan["df"], want["df"])
    # shard-local occ sums to the global count
    np.testing.assert_array_equal(
        (plan["hi"] - plan["lo"]).sum(axis=0), plan["occ"]
    )


# ---------------------------------------------------------------------------
# Degenerate shards
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def skewed():
    """8 tiny documents built so shard behaviour is adversarial under a
    4-way split (bounds (0,2)(2,4)(4,6)(6,8)):

    * ``common`` occurs once in every document — df = 8 exceeds any single
      shard's document count, so top-k with k = 8 must merge across all
      shards;
    * ``only0`` occurs only in document 0 — every other shard contributes
      an empty answer to the merge;
    * ``absent`` occurs nowhere — every shard's answer is empty.
    """
    docs = [[1, 2, 3] + [4] * (i + 1) for i in range(8)]
    docs[0] = [1, 2, 3, 7, 7, 7]
    coll = concat_documents(docs)
    base = RetrievalService.build(coll, block_size=8, beta=4.0,
                                  validate=False)
    svc = ShardedRetrievalService.build(
        coll, make_docs_mesh(N_SHARDS), block_size=8, beta=4.0,
    )
    text = np.asarray(coll.text)
    common = text[0:3]                       # [1,2,3] shifted
    only0 = text[3:5]                        # [7,7] shifted, doc 0 only
    absent = np.asarray([text[3], text[0], text[3]])  # 7,1,7: nowhere
    return coll, base, svc, common, only0, absent


def test_all_hits_in_one_shard(skewed):
    coll, base, svc, common, only0, absent = skewed
    want = base.list_docs([only0], max_df=_maxdf(coll), engine="reference",
                          max_buf=1024)
    got = svc.list_docs([only0], max_df=_maxdf(coll), max_buf=1024)
    assert got == want
    lo, hi = svc.shard_doc_range(0)
    assert got[0] and all(lo <= d < hi for d in got[0])


def test_empty_answer_every_shard(skewed):
    coll, base, svc, common, only0, absent = skewed
    assert int(svc.count([absent])[0]) == 0
    assert svc.list_docs([absent], max_df=_maxdf(coll), max_buf=1024) == [[]]
    assert svc.topk([absent], k=4, max_buf=1024) == [[]]


def test_k_exceeds_any_single_shards_hits(skewed):
    coll, base, svc, common, only0, absent = skewed
    k = coll.d  # every shard holds only 2 documents
    want = base.topk([common, only0], k=k, engine="reference", max_buf=1024)
    got = svc.topk([common, only0], k=k, max_buf=1024)
    assert got == want
    assert len(got[0]) == coll.d  # the union spans all shards


def test_more_shards_than_documents_rejected():
    coll = concat_documents([[1, 2], [2, 1]])
    with pytest.raises(ValueError):
        doc_shard_bounds(coll.d, 4)


# ---------------------------------------------------------------------------
# validate=True over a sharded index pytree
# ---------------------------------------------------------------------------


def test_validate_populates_per_shard_fingerprints(setup):
    coll, base, svc, pats = setup
    for s in range(svc.n_shards):
        assert any(k.startswith(f"shard{s}:") for k in svc.fingerprints)
    # partition bookkeeping covers the whole collection
    assert sum(sh.coll.d for sh in svc.shards) == coll.d


def test_validate_rejects_tampered_shard(skewed):
    coll, *_ = skewed
    from repro.serve.validate import validate_sharded_service

    svc = ShardedRetrievalService.build(
        coll, make_docs_mesh(N_SHARDS), block_size=8, beta=4.0,
        validate=False,
    )
    svc.shards[1].da = np.full_like(np.asarray(svc.shards[1].da), coll.d + 9)
    with pytest.raises(IndexIntegrityError):
        validate_sharded_service(svc)


def test_validate_rejects_bad_partition(skewed):
    coll, *_ = skewed
    from repro.serve.validate import validate_sharded_service

    svc = ShardedRetrievalService.build(
        coll, make_docs_mesh(N_SHARDS), block_size=8, beta=4.0,
        validate=False,
    )
    svc.doc_bases = np.asarray([0, 2, 4, 7], np.int32)  # misaligned split
    with pytest.raises(IndexIntegrityError):
        validate_sharded_service(svc)


# ---------------------------------------------------------------------------
# Tentpole: kernel path restored for an over-budget index
# ---------------------------------------------------------------------------


def test_kernel_restored_when_sharded(setup, monkeypatch):
    """With the VMEM budget pinched between the per-shard and the global
    wavelet-matrix footprint, the build's TPU selection puts the unsharded
    index on the XLA pair descent (zero pallas_calls) and the sharded one
    on the fused kernel, once per shard — which still answers
    bit-identically."""
    coll, base, svc, pats = setup
    from repro.analysis.jaxpr import count_primitive

    # resident tables pad to whole (8, 128) tiles, so the wavelet matrix
    # must span several tiles for a quarter of it to need fewer
    big = generate(SyntheticSpec("version", n_base=4, n_variants=6,
                                 base_len=500, mutation_rate=0.01, seed=5))
    big_flat = RetrievalService.build(big, block_size=16, beta=8.0,
                                      validate=False)
    big_sharded = RetrievalService.build(big, mesh=svc.mesh, block_size=16,
                                         beta=8.0, validate=False)
    global_bytes = _resident_bytes(big_flat.csa)
    shard_bytes = max(_resident_bytes(sh.csa) for sh in big_sharded.shards)
    assert shard_bytes < global_bytes
    budget = (shard_bytes + global_bytes) // 2
    monkeypatch.setattr(ops, "BACKWARD_SEARCH_VMEM_BUDGET", budget)

    flat_search, _ = kernel_selection([big_flat], "tpu")
    shard_search, _ = kernel_selection(big_sharded.shards, "tpu")
    assert not flat_search and shard_search  # over budget only unsharded
    unsharded = big_flat.trace_endpoint("plan", use_kernel=flat_search)
    assert count_primitive(unsharded, "pallas_call") == 0
    sharded = big_sharded.trace_endpoint("plan", use_kernel=shard_search)
    assert count_primitive(sharded, "pallas_call") == big_sharded.n_shards

    # end to end through the kernel (interpret mode off-TPU): same answers
    svc_k = ShardedRetrievalService.build(
        coll, svc.mesh, block_size=16, beta=8.0,
        use_search_kernel=True, validate=False,
    )
    few = pats[:4]
    want = base.list_docs(few, max_df=_maxdf(coll), engine="reference",
                          max_buf=4096)
    assert svc_k.list_docs(few, max_df=_maxdf(coll), max_buf=4096) == want
    np.testing.assert_array_equal(
        svc_k.count(few), np.asarray(base.count(few, engine="reference"))
    )


# ---------------------------------------------------------------------------
# Compile discipline: one program per endpoint x shape bucket
# ---------------------------------------------------------------------------


def test_one_compile_per_endpoint_bucket(setup):
    coll, base, svc, pats = setup
    before = dict(svc.compile_counts)
    # same shape bucket every time -> the cache must not recompile
    for _ in range(3):
        svc.list_docs(pats, max_df=_maxdf(coll), max_buf=4096)
        svc.topk(pats, k=3, max_buf=4096)
        svc.count(pats)
    assert svc.compile_counts == before
    # a new batch bucket is exactly one more lowering of that endpoint
    svc.list_docs(pats[:2], max_df=_maxdf(coll), max_buf=4096)
    assert svc.compile_counts["list"] == before["list"] + 1


# ---------------------------------------------------------------------------
# ServeRuntime rides the sharded service unchanged
# ---------------------------------------------------------------------------


def test_runtime_over_sharded_service(setup):
    coll, base, svc, pats = setup
    rt = ServeRuntime(svc, RuntimeConfig(
        default_deadline_s=GENEROUS, backoff_base_s=0.0,
    ))
    answers = rt.serve([
        ("list", pats[0]), ("count", pats[1]),
        ("topk", pats[2]), ("tfidf", pats[3:5]),
    ])
    assert not any(a.degraded for a in answers)
    assert answers[0].result == svc.list_docs(
        [pats[0]], max_df=rt.config.max_df, engine="reference",
        max_buf=rt.config.max_buf,
    )[0]
    assert answers[1].result == int(svc.count([pats[1]],
                                              engine="reference")[0])


def test_runtime_fault_injection_degrades_to_sharded_reference(setup):
    coll, base, svc, pats = setup
    rt = ServeRuntime(svc, RuntimeConfig(
        default_deadline_s=GENEROUS, backoff_base_s=0.0, max_retries=1,
    ))
    ref = svc.list_docs(pats[:3], max_df=rt.config.max_df,
                        engine="reference", max_buf=rt.config.max_buf)
    with faults.inject(FaultSpec("executor", "error", rate=1.0)):
        answers = rt.serve([("list", p) for p in pats[:3]])
    assert all(a.degraded for a in answers)
    assert [a.result for a in answers] == ref


def test_list_kernel_restored_when_sharded(setup, monkeypatch):
    """Listing-kernel counterpart of the restoration contract: with the
    listing VMEM budget pinched between the per-shard and the global
    resident tables (plus the V scratch), the build's TPU selection takes
    the listing launch from the unsharded list program and keeps one fused
    listing launch per shard — and both kernels together make the
    per-shard launch count 2S."""
    coll, base, svc, pats = setup
    from repro.analysis.jaxpr import count_primitive

    def list_bytes(s):
        return ops.ilcp_list_resident_bytes(
            s.ilcp.vilcp, s.ilcp.rmq.table, s.ilcp.run_starts, s.da,
        ) + ops.ilcp_list_scratch_bytes(s.ilcp.d)

    global_bytes = list_bytes(base)
    shard_bytes = max(list_bytes(sh) for sh in svc.shards)
    assert shard_bytes < global_bytes
    budget = (shard_bytes + global_bytes) // 2
    monkeypatch.setattr(ops, "ILCP_LIST_VMEM_BUDGET", budget)

    _, flat_list = kernel_selection([base], "tpu")
    _, shard_list = kernel_selection(svc.shards, "tpu")
    assert not flat_list and shard_list  # over budget only unsharded
    unsharded = base.trace_endpoint(
        "list", use_kernel=False, use_list_kernel=flat_list
    )
    assert count_primitive(unsharded, "pallas_call") == 0
    sharded = svc.trace_endpoint(
        "list", use_kernel=False, use_list_kernel=shard_list
    )
    assert count_primitive(sharded, "pallas_call") == svc.n_shards
    both = svc.trace_endpoint("list", use_kernel=True, use_list_kernel=True)
    assert count_primitive(both, "pallas_call") == 2 * svc.n_shards

    # end to end through both kernels: same answers as the reference
    svc_k = ShardedRetrievalService.build(
        coll, svc.mesh, block_size=16, beta=8.0,
        use_search_kernel=True, use_list_kernel=True, validate=False,
    )
    few = pats[:4]
    want = base.list_docs(few, max_df=_maxdf(coll), engine="reference",
                          max_buf=4096)
    assert svc_k.list_docs(few, max_df=_maxdf(coll), max_buf=4096) == want
