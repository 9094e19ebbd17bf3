"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.analysis.jaxpr import count_primitive
from repro.kernels import ops, ref
from repro.kernels.backward_search import backward_search_pallas
from repro.kernels.embedding_bag import csr_to_padded, embedding_bag_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rank import rank_pallas
from repro.kernels.rmq import rmq_pallas
from repro.succinct.bitvector import plain_from_bits
from repro.succinct.rmq import rmq_build
from repro.succinct.wavelet import wm_build

RNG = np.random.default_rng(53)


# ---------------------------------------------------------------------------
# rank
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [64, 257, 4096])
@pytest.mark.parametrize("density", [0.02, 0.5, 0.97])
@pytest.mark.parametrize("block_q", [64, 256])
def test_rank_kernel(n, density, block_q):
    bits = (RNG.random(n) < density).astype(np.uint8)
    bv = plain_from_bits(bits)
    idx = jnp.asarray(RNG.integers(0, n + 1, 333), jnp.int32)
    got = rank_pallas(bv.words, bv.ones_prefix, idx, block_q=block_q, interpret=True)
    exp = ref.rank_ref(bv.words, bv.ones_prefix, idx)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))
    # and against the ground truth
    truth = np.concatenate([[0], np.cumsum(bits)])[np.asarray(idx)]
    np.testing.assert_array_equal(np.asarray(got), truth)


# ---------------------------------------------------------------------------
# rmq
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [16, 100, 1000])
@pytest.mark.parametrize("vrange", [3, 1000])
def test_rmq_kernel(n, vrange):
    values = RNG.integers(-vrange, vrange, n).astype(np.int32)
    st = rmq_build(values)
    q = 257
    lo = RNG.integers(0, n, q)
    hi = np.minimum(lo + RNG.integers(0, n, q), n - 1)
    lo = np.minimum(lo, hi)
    got = rmq_pallas(
        st.values, st.table, jnp.asarray(lo, jnp.int32), jnp.asarray(hi, jnp.int32),
        block_q=128, interpret=True,
    )
    exp = ref.rmq_ref(st.values, st.table, jnp.asarray(lo), jnp.asarray(hi))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))
    for g, a, b in zip(np.asarray(got)[:50], lo[:50], hi[:50]):
        assert g == a + int(np.argmin(values[a : b + 1]))


@pytest.mark.parametrize("n", [1, 64, 100])
def test_rmq_kernel_degenerate_spans(n):
    """Kernel parity on the spans that stress the two-probe trick:
    hi == lo (span 1), the full array (top-level k when n is a power of
    two), and spans where the second probe's start ``hi - 2^k + 1``
    coincides with ``lo``."""
    values = RNG.integers(0, 4, n).astype(np.int32)
    st = rmq_build(values)
    lo = [i for i in range(n)] + [0]
    hi = [i for i in range(n)] + [n - 1]
    k = 1
    while (1 << k) <= n:
        span = 1 << k
        lo += [0, n - span]
        hi += [span - 1, n - 1]
        k += 1
    got = rmq_pallas(
        st.values, st.table, jnp.asarray(lo, jnp.int32),
        jnp.asarray(hi, jnp.int32), block_q=64, interpret=True,
    )
    for g, a, b in zip(np.asarray(got), lo, hi):
        assert g == a + int(np.argmin(values[a : b + 1])), (a, b)


# ---------------------------------------------------------------------------
# embedding bag
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mode", ["sum", "mean"])
@pytest.mark.parametrize("V,D,B,L", [(100, 16, 37, 4), (1000, 64, 128, 1), (50, 8, 5, 7)])
def test_embedding_bag_kernel(dtype, mode, V, D, B, L):
    table = jnp.asarray(RNG.standard_normal((V, D)), dtype)
    lens = RNG.integers(1, L + 1, B)
    indices = np.concatenate([RNG.integers(0, V, l) for l in lens]).astype(np.int32)
    offsets = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
    padded = csr_to_padded(indices, offsets, L)
    got = embedding_bag_pallas(
        table, jnp.asarray(padded), mode=mode, block_b=32, interpret=True
    )
    exp = ref.embedding_bag_ref(
        table.astype(jnp.float32), jnp.asarray(indices), jnp.asarray(offsets), mode
    )
    tol = 1e-6 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(exp, np.float32), rtol=tol, atol=tol
    )


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("B,H,S,Dh", [(2, 2, 128, 32), (1, 4, 256, 64)])
def test_flash_attention_self(dtype, causal, B, H, S, Dh):
    q = jnp.asarray(RNG.standard_normal((B, H, S, Dh)) * 0.5, dtype)
    k = jnp.asarray(RNG.standard_normal((B, H, S, Dh)) * 0.5, dtype)
    v = jnp.asarray(RNG.standard_normal((B, H, S, Dh)) * 0.5, dtype)
    got = flash_attention_pallas(
        q, k, v, causal=causal, block_q=64, block_k=64, interpret=True
    )
    exp = ref.flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(exp, np.float32), rtol=tol, atol=tol
    )


def test_flash_attention_decode_window():
    """S_kv > S_q (decode with KV cache): query i sees <= offset + i."""
    B, H, Sq, Skv, Dh = 1, 2, 64, 256, 32
    q = jnp.asarray(RNG.standard_normal((B, H, Sq, Dh)) * 0.5, jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, H, Skv, Dh)) * 0.5, jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, H, Skv, Dh)) * 0.5, jnp.float32)
    got = flash_attention_pallas(
        q, k, v, causal=True, block_q=32, block_k=64, interpret=True
    )
    exp = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), rtol=2e-5, atol=2e-5)


def test_flash_attention_grad():
    """Kernel must be differentiable (training path)."""
    B, H, S, Dh = 1, 2, 128, 32
    q = jnp.asarray(RNG.standard_normal((B, H, S, Dh)) * 0.3, jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, H, S, Dh)) * 0.3, jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, H, S, Dh)) * 0.3, jnp.float32)

    def loss_kernel(q, k, v):
        return flash_attention_pallas(
            q, k, v, causal=True, block_q=64, block_k=64, interpret=True
        ).sum()

    def loss_ref(q, k, v):
        return ref.flash_attention_ref(q, k, v, causal=True).sum()

    g1 = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4)


# ---------------------------------------------------------------------------
# backward search (fused CSA range search)
# ---------------------------------------------------------------------------


def _bws_index(n, sigma, seed):
    """Wavelet matrix over a random sequence + the FM-index base array
    (C[c] - sym_starts[c]); returns the raw sequence for ground truth."""
    rng = np.random.default_rng(seed)
    seq = rng.integers(0, sigma, n)
    wm = wm_build(seq, sigma)
    counts = np.concatenate([[0], np.cumsum(np.bincount(seq, minlength=sigma))])
    base = jnp.asarray(counts[:sigma], jnp.int32) - wm.sym_starts
    return seq, wm, base, counts


def _bws_truth(seq, counts, n, sigma, pat):
    """Textbook per-symbol backward search with the serving layer's
    conventions: empty pattern -> (0, n); out-of-alphabet symbol collapses
    to the empty range at its lexicographic insertion point."""
    lo, hi = 0, n
    for c in map(int, reversed(pat)):
        if lo >= hi:
            break
        if c < 0 or c >= sigma:
            lo = hi = 0 if c < 0 else n
            break
        lo = int(counts[c]) + int(np.sum(seq[:lo] == c))
        hi = int(counts[c]) + int(np.sum(seq[:hi] == c))
    return lo, max(lo, hi)


def _bws_patterns(seq, sigma, Q, max_m, seed, oob=True):
    rng = np.random.default_rng(seed)
    pats = np.zeros((Q, max_m), np.int32)
    lens = rng.integers(0, max_m + 1, Q).astype(np.int32)
    for qi in range(Q):
        m = int(lens[qi])
        if m == 0:
            continue
        if rng.random() < 0.5 and m <= len(seq):
            start = rng.integers(0, len(seq) - m + 1)
            pats[qi, :m] = seq[start : start + m]  # guaranteed hits
        else:
            pats[qi, :m] = rng.integers(0, sigma, m)
        if oob and rng.random() < 0.25:
            pats[qi, rng.integers(0, m)] = rng.choice(
                [-3, -1, sigma, sigma + 5]
            )
    return jnp.asarray(pats), jnp.asarray(lens)


def _reversed_pats(pats, lens):
    """Right-to-left symbol order, as ops.backward_search materialises it."""
    B, max_m = pats.shape
    j = jnp.clip(
        lens[:, None] - 1 - jnp.arange(max_m, dtype=jnp.int32)[None, :],
        0, max(max_m - 1, 0),
    )
    return jnp.take_along_axis(pats, j, axis=1)


@pytest.mark.parametrize("sigma", [2, 5, 37])
@pytest.mark.parametrize("Q,block_q", [(1, 256), (33, 8), (64, 16)])
def test_backward_search_kernel(sigma, Q, block_q):
    """Interpret-mode kernel == ref oracle == ground truth, including Q not
    a multiple of block_q and out-of-alphabet symbols."""
    n, max_m = 500, 9
    seq, wm, base, counts = _bws_index(n, sigma, seed=sigma)
    pats, lens = _bws_patterns(seq, sigma, Q, max_m, seed=Q * 31 + sigma)

    lo_k, hi_k = ops.backward_search(
        wm.words, wm.ones_prefix, wm.zcount, base, pats, lens,
        n=n, sigma=sigma, block_q=block_q, interpret=True,
    )
    rev = _reversed_pats(pats, lens)
    lo_r, hi_r = ref.backward_search_ref(
        wm.words, wm.ones_prefix, wm.zcount, base, rev, lens, n=n, sigma=sigma
    )
    np.testing.assert_array_equal(np.asarray(lo_k), np.asarray(lo_r))
    np.testing.assert_array_equal(np.asarray(hi_k), np.asarray(hi_r))
    # and the raw kernel entry point (wrapper-materialised reversal aside)
    lo_p, hi_p = backward_search_pallas(
        wm.words, wm.ones_prefix, wm.zcount, base, rev, lens,
        n=n, sigma=sigma, block_q=block_q, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(lo_p), np.asarray(lo_r))
    np.testing.assert_array_equal(np.asarray(hi_p), np.asarray(hi_r))
    for qi in range(Q):
        lo_t, hi_t = _bws_truth(
            seq, counts, n, sigma, np.asarray(pats[qi, : lens[qi]])
        )
        assert (int(lo_k[qi]), int(hi_k[qi])) == (lo_t, hi_t), f"query {qi}"


def test_backward_search_oob_stays_empty():
    """Any out-of-alphabet symbol must collapse the range to empty and keep
    it empty through the remaining (earlier) symbols."""
    n, sigma, max_m = 300, 6, 7
    seq, wm, base, _ = _bws_index(n, sigma, seed=2)
    rng = np.random.default_rng(7)
    pats = rng.integers(0, sigma, (32, max_m)).astype(np.int32)
    lens = np.full(32, max_m, np.int32)
    pats[:, 3] = np.where(np.arange(32) % 2 == 0, sigma + 4, -2)
    lo, hi = ops.backward_search(
        wm.words, wm.ones_prefix, wm.zcount, base,
        jnp.asarray(pats), jnp.asarray(lens),
        n=n, sigma=sigma, block_q=8, interpret=True,
    )
    assert np.array_equal(np.asarray(lo), np.asarray(hi))


def test_backward_search_odd_shape_fallback(monkeypatch):
    """Empty batch / zero-width patterns have closed forms: correct
    results, zero pallas_call in the jaxpr.  An over-budget index is a
    build-time decision (``backward_search_fits``); the wrapper itself
    never swaps the kernel for the oracle."""
    n, sigma, max_m = 200, 5, 6
    seq, wm, base, counts = _bws_index(n, sigma, seed=4)

    def launches(pats, lens):
        fn = lambda p, l: ops.backward_search(  # noqa: E731
            wm.words, wm.ones_prefix, wm.zcount, base, p, l,
            n=n, sigma=sigma, interpret=True,
        )
        return count_primitive(jax.make_jaxpr(fn)(pats, lens).jaxpr, "pallas_call")

    # B == 0
    e_pats = jnp.zeros((0, max_m), jnp.int32)
    e_lens = jnp.zeros(0, jnp.int32)
    assert launches(e_pats, e_lens) == 0
    lo, hi = ops.backward_search(
        wm.words, wm.ones_prefix, wm.zcount, base, e_pats, e_lens,
        n=n, sigma=sigma, interpret=True,
    )
    assert lo.shape == (0,) and hi.shape == (0,)

    # max_m == 0: every row is the empty pattern -> full range (0, n)
    z_pats = jnp.zeros((4, 0), jnp.int32)
    z_lens = jnp.zeros(4, jnp.int32)
    assert launches(z_pats, z_lens) == 0
    lo, hi = ops.backward_search(
        wm.words, wm.ones_prefix, wm.zcount, base, z_pats, z_lens,
        n=n, sigma=sigma, interpret=True,
    )
    assert np.all(np.asarray(lo) == 0) and np.all(np.asarray(hi) == n)

    # over the VMEM budget: the index no longer fits, but a launch asked
    # for is a launch made — with the oracle's integers
    pats, lens = _bws_patterns(seq, sigma, 16, max_m, seed=11)
    want = ref.backward_search_ref(
        wm.words, wm.ones_prefix, wm.zcount, base,
        _reversed_pats(pats, lens), lens, n=n, sigma=sigma,
    )
    assert ops.backward_search_fits(wm.words, wm.ones_prefix)
    monkeypatch.setattr(ops, "BACKWARD_SEARCH_VMEM_BUDGET", 1)
    assert not ops.backward_search_fits(wm.words, wm.ones_prefix)
    assert launches(pats, lens) == 1
    got = ops.backward_search(
        wm.words, wm.ones_prefix, wm.zcount, base, pats, lens,
        n=n, sigma=sigma, interpret=True,
    )
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_backward_search_single_launch():
    """The launch-count contract: the whole planned range search for a
    padded batch is exactly ONE pallas_call (down from 2*m*levels rank
    calls); the XLA fallback is zero launches and bit-identical."""
    from repro.core.csa import build_csa, csa_search_planned
    from repro.core.suffix import build_suffix_data
    from repro.data.collections import SyntheticSpec, generate

    coll = generate(
        SyntheticSpec("version", n_base=2, n_variants=4, base_len=60,
                      mutation_rate=0.01, seed=7)
    )
    csa = build_csa(build_suffix_data(coll))
    pats = jnp.asarray(RNG.integers(0, coll.sigma, (8, 16)), jnp.int32)
    lens = jnp.asarray(RNG.integers(0, 17, 8), jnp.int32)

    kern = lambda p, l: csa_search_planned(  # noqa: E731
        csa, p, l, use_kernel=True, interpret=True
    )
    fall = lambda p, l: csa_search_planned(csa, p, l, use_kernel=False)  # noqa: E731
    assert count_primitive(jax.make_jaxpr(kern)(pats, lens).jaxpr, "pallas_call") == 1
    assert count_primitive(jax.make_jaxpr(fall)(pats, lens).jaxpr, "pallas_call") == 0

    lo_k, hi_k = kern(pats, lens)
    lo_f, hi_f = fall(pats, lens)
    np.testing.assert_array_equal(np.asarray(lo_k), np.asarray(lo_f))
    np.testing.assert_array_equal(np.asarray(hi_k), np.asarray(hi_f))


def test_pair_descent_halves_gathers():
    """The XLA fallback contract: a fused (lo, hi) pair descent issues half
    the per-level rank gathers of two independent wm_rank_batch descents."""
    from repro.succinct.wavelet import wm_rank_batch, wm_rank_pair_batch

    _, wm, _, _ = _bws_index(600, 13, seed=3)
    c = jnp.asarray(RNG.integers(0, 13, 64), jnp.int32)
    lo = jnp.asarray(RNG.integers(0, 300, 64), jnp.int32)
    hi = jnp.asarray(RNG.integers(300, 601, 64), jnp.int32)

    pair = jax.make_jaxpr(lambda c, a, b: wm_rank_pair_batch(wm, c, a, b))(
        c, lo, hi
    )
    dual = jax.make_jaxpr(
        lambda c, a, b: (wm_rank_batch(wm, c, a), wm_rank_batch(wm, c, b))
    )(c, lo, hi)
    g_pair = count_primitive(pair.jaxpr, "gather")
    g_dual = count_primitive(dual.jaxpr, "gather")
    # pair: 2 rank gathers/level + one sym_starts lookup outside the loop;
    # dual: 4 rank gathers/level (each wm_rank carries a (start, end) pair)
    assert g_pair * 2 <= g_dual + 2, (g_pair, g_dual)

    # and the integers agree with the classic descent
    rl_p, rh_p = wm_rank_pair_batch(wm, c, lo, hi)
    np.testing.assert_array_equal(
        np.asarray(rl_p), np.asarray(wm_rank_batch(wm, c, lo))
    )
    np.testing.assert_array_equal(
        np.asarray(rh_p), np.asarray(wm_rank_batch(wm, c, hi))
    )


# ---------------------------------------------------------------------------
# fused ILCP document listing
# ---------------------------------------------------------------------------


def _ilcp_fixture(seed=13):
    """A repetitive versioned collection with pattern-derived SA ranges —
    the ILCP recursion's completeness (Lemma 3) holds on pattern ranges,
    so ground-truth checks must use real ones, not random intervals."""
    from repro.core.ilcp import build_ilcp
    from repro.core.suffix import build_suffix_data, sa_range_for_pattern
    from repro.data.collections import (
        SyntheticSpec, generate, random_substring_patterns,
    )

    coll = generate(SyntheticSpec(
        "version", n_base=2, n_variants=6, base_len=80,
        mutation_rate=0.02, seed=seed,
    ))
    data = build_suffix_data(coll)
    index = build_ilcp(data)
    pats = random_substring_patterns(coll, 300, 5, 32)
    ranges = [sa_range_for_pattern(data, p) for p in pats]
    ranges += [(0, 0), (5, 5), (7, 3)]  # empty + inverted ranges
    lo = jnp.asarray([r[0] for r in ranges], jnp.int32)
    hi = jnp.asarray([r[1] for r in ranges], jnp.int32)
    return coll, data, index, jnp.asarray(data.da), lo, hi


def _list_launches(fn, *args):
    # fresh wrapper per call: make_jaxpr caches on (fn identity, avals),
    # and these tests re-trace the same fn after flipping a module global
    fresh = lambda *a: fn(*a)  # noqa: E731
    return count_primitive(jax.make_jaxpr(fresh)(*args).jaxpr, "pallas_call")


@pytest.mark.parametrize("max_df,block_q", [(2, 128), (8, 4), (64, 128)])
def test_ilcp_list_kernel_parity(max_df, block_q):
    """Kernel vs lockstep oracle vs the vmapped Fig-1 recursion: all three
    bit-identical (same documents in the same discovery order), and the
    distinct-document sets match numpy ground truth on pattern SA ranges —
    including df > max_df truncation at small max_df and odd batch shapes
    (B not a multiple of block_q)."""
    from repro.core.ilcp import ilcp_list_docs_da_batch

    coll, data, index, da, lo, hi = _ilcp_fixture()
    kw = dict(d=coll.d, max_df=max_df)
    docs_k, cnt_k = ops.ilcp_list(
        index.vilcp, index.rmq.table, index.run_starts, da, lo, hi,
        block_q=block_q, interpret=True, **kw,
    )
    lo_run = ops.runs_of(index.run_starts, lo)
    hi_run = ops.runs_of(index.run_starts, hi - 1)
    docs_o, cnt_o = ref.ilcp_list_ref(
        index.vilcp, index.rmq.table, index.run_starts, da, lo, hi,
        lo_run, hi_run, **kw,
    )
    docs_v, cnt_v = ilcp_list_docs_da_batch(index, da, lo, hi, max_df)
    np.testing.assert_array_equal(np.asarray(cnt_k), np.asarray(cnt_o))
    np.testing.assert_array_equal(np.asarray(docs_k), np.asarray(docs_o))
    np.testing.assert_array_equal(np.asarray(cnt_k), np.asarray(cnt_v))
    np.testing.assert_array_equal(
        np.asarray(docs_k), np.asarray(docs_v)[:, :max_df]
    )

    danp = np.asarray(data.da)
    for i in range(lo.shape[0]):
        a, b = int(lo[i]), int(hi[i])
        truth = sorted(set(danp[a:b].tolist())) if a < b else []
        got = np.asarray(docs_k)[i, : int(cnt_k[i])].tolist()
        assert len(set(got)) == len(got), "duplicate docs reported"
        if len(truth) <= max_df:
            assert sorted(got) == truth, (a, b)
        else:
            assert int(cnt_k[i]) == max_df
            assert set(got) <= set(truth), (a, b)


def test_ilcp_list_launch_and_fallbacks(monkeypatch):
    """Launch-count + fallback contract of the ``ops.ilcp_list`` wrapper:
    ONE pallas_call on the kernel path; zero for B == 0 and max_df == 0
    (closed forms).  A pinched VMEM budget flips the build-time fit test
    (``ilcp_list_fits``) but never the wrapper: it still launches."""
    coll, data, index, da, lo, hi = _ilcp_fixture()

    def run(l, h, max_df=8):
        return ops.ilcp_list(
            index.vilcp, index.rmq.table, index.run_starts, da, l, h,
            d=coll.d, max_df=max_df, interpret=True,
        )

    assert _list_launches(run, lo, hi) == 1
    want = run(lo, hi)

    # B == 0: no launch, empty outputs
    e = jnp.zeros(0, jnp.int32)
    assert _list_launches(run, e, e) == 0
    docs0, cnt0 = run(e, e)
    assert docs0.shape == (0, 8) and cnt0.shape == (0,)

    # max_df == 0 has the closed form: no documents
    assert _list_launches(lambda a, b: run(a, b, max_df=0), lo, hi) == 0
    docs0, cnt0 = run(lo, hi, max_df=0)
    assert docs0.shape == (lo.shape[0], 0) and not np.asarray(cnt0).any()

    # over the VMEM budget: the fit test says no, the wrapper still runs
    # the kernel it was asked for, with the same integers
    tables = (index.vilcp, index.rmq.table, index.run_starts, da)
    assert ops.ilcp_list_fits(*tables, d=coll.d)
    monkeypatch.setattr(ops, "ILCP_LIST_VMEM_BUDGET", 1)
    assert not ops.ilcp_list_fits(*tables, d=coll.d)
    assert _list_launches(run, lo, hi) == 1
    got = run(lo, hi)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_ilcp_list_rmq_kernel_fallback():
    """Satellite wiring: the XLA fallback recursion can batch its RMQ
    probes through the orphaned Pallas RMQ kernel (one launch — the RMQ
    inside the loop body) and stays bit-identical to the plain path."""
    from repro.core.ilcp import ilcp_list_docs_da_batch

    coll, data, index, da, lo, hi = _ilcp_fixture()
    plain = ilcp_list_docs_da_batch(index, da, lo, hi, 8)
    rmqk = ilcp_list_docs_da_batch(index, da, lo, hi, 8, use_rmq_kernel=True)
    for g, w in zip(rmqk, plain):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    n = _list_launches(
        lambda a, b: ilcp_list_docs_da_batch(
            index, da, a, b, 8, use_rmq_kernel=True
        ),
        lo, hi,
    )
    assert n == 1


def test_ilcp_list_oob_range_stays_empty():
    """Degenerate SA bounds past the array ends must not fabricate
    documents — the kernel clips its gathers, so cnt stays 0 for empty
    and inverted ranges even at the extremes."""
    coll, data, index, da, _, _ = _ilcp_fixture()
    n = int(da.shape[0])
    lo = jnp.asarray([0, n, n - 1, 17], jnp.int32)
    hi = jnp.asarray([0, n, n - 1, 2], jnp.int32)
    docs, cnt = ops.ilcp_list(
        index.vilcp, index.rmq.table, index.run_starts, da, lo, hi,
        d=coll.d, max_df=8, interpret=True,
    )
    assert np.asarray(cnt).tolist() == [0, 0, 0, 0]
    assert np.all(np.asarray(docs) == -1)


def test_list_endpoint_two_launches():
    """The list endpoint's launch-count contract at the program level:
    kernel path = exactly TWO pallas_calls (fused backward search + fused
    listing), XLA path = zero, and the two programs agree end to end."""
    from repro.data.collections import SyntheticSpec, generate
    from repro.serve.retrieval import RetrievalService

    coll = generate(SyntheticSpec(
        "version", n_base=2, n_variants=4, base_len=60,
        mutation_rate=0.01, seed=7,
    ))
    svc = RetrievalService.build(coll, validate=False)
    on = svc.trace_endpoint("list", use_kernel=True, use_list_kernel=True)
    off = svc.trace_endpoint("list", use_kernel=False, use_list_kernel=False)
    assert count_primitive(on.jaxpr, "pallas_call") == 2
    assert count_primitive(off.jaxpr, "pallas_call") == 0
