"""Compressed suffix array: FM-index with run-length (RLCSA) accounting.

The paper's experiments all sit on top of the RLCSA (Makinen et al 2010):
``search(m)`` finds the SA range of a pattern by backward search, and
``lookup(n)`` retrieves SA[i] by LF-walking to a sampled position.  We
implement the same functional interface:

* backward search over a wavelet matrix of the BWT — a fixed-length
  ``lax.scan`` over pattern symbols (masked for padding), so a *batch* of
  patterns is one vectorized program;
* locate via LF-walk with text-position sampling; every document start is
  additionally sampled, which bounds the walk by the sample rate and stops
  it at document boundaries.  Under the shared-$ plain-suffix-array
  semantics (see repro.core.suffix) SA is the suffix array of the single
  string T, so the LF identity is exact — terminators are ordinary symbols.

Space accounting: the working set is the plain wavelet matrix (TPU layout);
``modeled_bits_rlcsa`` reports the run-length compressed size the paper's
RLCSA would use (rho_bwt runs), which is what the space axes of Figures
6-10 show for the CSA component.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.common import IDX, as_i32, ceil_log2, pytree_dataclass
from repro.core.suffix import SuffixData
from repro.succinct.bitvector import SparseBitvector, sparse_from_positions
from repro.succinct.wavelet import WaveletMatrix, wm_access, wm_build, wm_rank


@pytree_dataclass(meta=("n", "d", "sigma", "sample_rate", "bwt_runs"))
class CSA:
    wm: WaveletMatrix          # wavelet matrix over the BWT
    counts: jnp.ndarray        # int32[sigma+1]: symbols strictly < c
    sampled: SparseBitvector   # SA positions i whose SA[i] is sampled
    samples: jnp.ndarray       # int32[s]: SA[i] for sampled i, in SA order
    doc_bv: SparseBitvector    # text positions of document starts (bitvector B)
    n: int
    d: int
    sigma: int
    sample_rate: int
    bwt_runs: int

    # -- space accounting ---------------------------------------------------

    def modeled_bits_rlcsa(self) -> int:
        """rho(lg sigma + 2 lg(n/rho)) + samples — the RLCSA model."""
        rho = max(1, self.bwt_runs)
        per_run = ceil_log2(self.sigma) + 2 * max(1, ceil_log2(max(2, self.n // rho)))
        sample_bits = int(self.samples.shape[0]) * ceil_log2(max(2, self.n))
        return rho * per_run + sample_bits

    def modeled_bits_plain_fm(self) -> int:
        return self.n * ceil_log2(self.sigma) + int(self.samples.shape[0]) * ceil_log2(
            max(2, self.n)
        )


def build_csa(data: SuffixData, sample_rate: int = 16) -> CSA:
    coll = data.coll
    n, d = coll.n, coll.d
    sa = data.sa
    bwt = coll.text[(sa - 1) % n]

    wm = wm_build(bwt, coll.sigma)

    # counts[c] = number of symbols strictly smaller than c
    hist = np.bincount(coll.text, minlength=coll.sigma + 1)
    counts = np.zeros(coll.sigma + 1, dtype=np.int32)
    counts[1:] = np.cumsum(hist)[:-1].astype(np.int32)

    # sampling: SA[i] % rate == 0, plus every document start
    text_sampled = (sa % sample_rate == 0) | np.isin(sa, coll.doc_starts)
    marked_sa_positions = np.flatnonzero(text_sampled)
    samples = sa[marked_sa_positions].astype(np.int32)

    runs = int(1 + np.count_nonzero(np.diff(bwt))) if n else 0

    return CSA(
        wm=wm,
        counts=jnp.asarray(counts),
        sampled=sparse_from_positions(marked_sa_positions, n),
        samples=jnp.asarray(samples),
        doc_bv=sparse_from_positions(coll.doc_starts, n),
        n=n,
        d=d,
        sigma=coll.sigma,
        sample_rate=sample_rate,
        bwt_runs=runs,
    )


# ---------------------------------------------------------------------------
# search(m): backward search (batched)
# ---------------------------------------------------------------------------


def csa_symbol_bounds(csa: CSA, c):
    """Input hardening for one backward-search step (shared by every search
    path — the scalar scan, the batched pair descent, and the reference
    loop all route through this one validator).

    A symbol outside ``[0, sigma)`` cannot occur: the range collapses to
    the empty range at the symbol's lexicographic insertion point (0 below
    the alphabet, n above it), matching the host binary search's
    convention, and the clamped symbol ``cc`` keeps every downstream gather
    in bounds.  Returns ``(cc, c_ok, oob)``: the clamped symbol, the
    validity mask, and the collapse point.
    """
    c = as_i32(c)
    c_ok = (c >= 0) & (c < csa.sigma)
    cc = jnp.clip(c, 0, csa.sigma - 1)
    oob = jnp.where(c < 0, 0, csa.n).astype(IDX)
    return cc, c_ok, oob


def csa_search(csa: CSA, pattern, length):
    """SA range [lo, hi) of suffixes prefixed by ``pattern[:length]``.

    pattern: int32[max_m] (padded), length: scalar.  Fully traced: suitable
    for vmap over a batch of padded patterns.
    """
    pattern = as_i32(pattern)
    max_m = pattern.shape[0]
    length = as_i32(length)

    def body(carry, t):
        lo, hi = carry
        # process symbols right-to-left; slot t handles pattern[length-1-t]
        j = length - 1 - t
        active = (t < length) & (lo < hi)
        c = pattern[jnp.clip(j, 0, max_m - 1)]
        cc, c_ok, oob = csa_symbol_bounds(csa, c)
        nlo = jnp.where(c_ok, csa.counts[cc] + wm_rank(csa.wm, cc, lo), oob)
        nhi = jnp.where(c_ok, csa.counts[cc] + wm_rank(csa.wm, cc, hi), oob)
        lo = jnp.where(active, nlo, lo)
        hi = jnp.where(active, nhi, hi)
        return (lo, hi), None

    (lo, hi), _ = jax.lax.scan(
        body, (as_i32(0), as_i32(csa.n)), jnp.arange(max_m, dtype=IDX)
    )
    return lo, jnp.maximum(lo, hi)


def csa_search_batch(csa: CSA, patterns, lengths):
    """patterns: int32[Q, max_m]; lengths: int32[Q] -> (lo[Q], hi[Q])."""
    return jax.vmap(lambda p, l: csa_search(csa, p, l))(
        as_i32(patterns), as_i32(lengths)
    )


def csa_search_planned(csa: CSA, patterns, lengths, *, use_kernel: bool = False,
                       block_q: int = 256, interpret: bool | None = None):
    """Backward search written batch-first for the serving planner.

    Same integers as ``csa_search_batch``, but computed over [B] range
    arrays with both SA-range boundaries riding ONE wavelet descent per
    symbol step (``wm_rank_pair_batch``) — half the per-level rank gathers
    of two independent ``wm_rank_batch`` descents.

    ``use_kernel`` selects the execution path (the serving layer decides it
    once, at build time):
      * ``True``  — the fused kernel (``repro.kernels.backward_search``; one
        ``pallas_call`` for the whole batched search, interpreted when the
        program is lowered for CPU unless ``interpret`` says otherwise);
      * ``False`` — the XLA pair-descent path.
    """
    patterns = as_i32(patterns)
    lengths = as_i32(lengths)
    B, max_m = patterns.shape

    if use_kernel:
        from repro.kernels.ops import backward_search

        return backward_search(
            csa.wm.words, csa.wm.ones_prefix, csa.wm.zcount,
            csa.counts[: csa.sigma] - csa.wm.sym_starts,
            patterns, lengths,
            n=csa.n, sigma=csa.sigma, block_q=block_q, interpret=interpret,
        )

    from repro.succinct.wavelet import wm_rank_pair_batch

    rows = jnp.arange(B, dtype=IDX)

    def body(carry, t):
        lo, hi = carry
        j = lengths - 1 - t
        active = (t < lengths) & (lo < hi)
        c = patterns[rows, jnp.clip(j, 0, max_m - 1)]
        cc, c_ok, oob = csa_symbol_bounds(csa, c)
        rlo, rhi = wm_rank_pair_batch(csa.wm, cc, lo, hi)
        lo = jnp.where(active, jnp.where(c_ok, csa.counts[cc] + rlo, oob), lo)
        hi = jnp.where(active, jnp.where(c_ok, csa.counts[cc] + rhi, oob), hi)
        return (lo, hi), None

    (lo, hi), _ = jax.lax.scan(
        body,
        (jnp.zeros(B, IDX), jnp.full(B, csa.n, IDX)),
        jnp.arange(max_m, dtype=IDX),
    )
    return lo, jnp.maximum(lo, hi)


# ---------------------------------------------------------------------------
# lookup(n): locate SA[i] by LF-walk to a sample (batched)
# ---------------------------------------------------------------------------


def _lf(csa: CSA, j):
    c = wm_access(csa.wm, j)
    return csa.counts[c] + wm_rank(csa.wm, c, j)


def csa_lookup(csa: CSA, i):
    """SA[i] for a single (traced) index; O(sample_rate) LF steps."""

    def cond(carry):
        j, steps, done = carry
        return ~done

    def body(carry):
        j, steps, _ = carry
        is_sampled = csa.sampled.get(j) == 1
        nj = jnp.where(is_sampled, j, _lf(csa, j))
        nsteps = jnp.where(is_sampled, steps, steps + 1)
        return (nj, nsteps, is_sampled)

    j, steps, _ = jax.lax.while_loop(cond, body, (as_i32(i), as_i32(0), jnp.bool_(False)))
    base = csa.samples[csa.sampled.rank1(j)]
    return (base + steps).astype(IDX)


def csa_lookup_batch(csa: CSA, idx):
    return jax.vmap(lambda i: csa_lookup(csa, i))(as_i32(idx))


def csa_doc_of(csa: CSA, text_pos):
    """DA[i] given SA[i]: rank over the document-start bitvector B."""
    return csa.doc_bv.rank1(as_i32(text_pos) + 1) - 1


def csa_da_at(csa: CSA, i):
    """DA[i] = rank_B(SA[i]) — the Sadakane replacement for a stored DA."""
    return csa_doc_of(csa, csa_lookup(csa, i))


def csa_locate_range(csa: CSA, lo, max_out: int):
    """Locate SA[lo : lo + max_out] (masked by caller against hi)."""
    idx = as_i32(lo) + jnp.arange(max_out, dtype=IDX)
    idx = jnp.minimum(idx, csa.n - 1)
    return csa_lookup_batch(csa, idx)
