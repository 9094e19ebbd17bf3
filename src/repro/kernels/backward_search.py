"""Pallas TPU kernel: fused CSA backward search — one launch per batch.

Every query the serving engine answers starts with the backward search of
the pattern over the BWT wavelet matrix (paper Sections 2.2 / 6.2.2).  This
kernel runs the ENTIRE search for a padded batch in one ``pallas_call``:

  * the wavelet matrix's per-level ``words`` / ``ones_prefix`` arrays are
    flattened with a level stride and stay VMEM-resident across the whole
    search, each as one int32 ``(rows, 128)`` table (``repro.kernels.rows``);
  * the query batch streams through the grid in ``block_q`` tiles: the
    pattern tile in VMEM, lengths and the (lo, hi) results in SMEM;
  * inside one grid step each query runs as a scalar loop over its symbols
    (stopping at its length or at an empty range), with the level descent
    unrolled inside, carrying the (lo, hi) boundary pair so both ranks of
    a step share one descent;
  * a rank probe is a dynamic row load of the words and prefix tables, a
    vector popcount, and a lane select — Mosaic has no VMEM vector gather;
  * the per-symbol block start of the classic wavelet-matrix rank is
    precomputed at build time (``WaveletMatrix.sym_starts``), folded with
    the C-array into ``base[c] = counts[c] - sym_starts[c]`` (SMEM), so each
    boundary costs ONE rank probe per level.

Patterns arrive right-to-left (processing order) — callers reverse the
padded rows once up front (``repro.kernels.ops.backward_search`` does).
Out-of-alphabet symbols collapse the range to the empty range at the
symbol's lexicographic insertion point (0 below the alphabet, n above),
matching the host binary search's convention; rows padded beyond the true
batch get length 0 and return the untouched (0, n) seed, which callers trim.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.rows import as_rows, load_row, pick


def tile_rows(*, batch: int, block_q: int) -> int:
    """Queries per grid step: the whole batch when it fits one tile, else
    ``block_q`` rounded up to the int32 sublane tile (8)."""
    if batch <= block_q:
        return max(batch, 1)
    return -(-block_q // 8) * 8


def _backward_search_kernel(
    len_ref, zcount_ref, base_ref, pat_ref, words_ref, prefix_ref,
    lo_ref, hi_ref, *, levels: int, stride: int, n: int, sigma: int,
):
    bq, max_m = pat_ref.shape
    slots = jax.lax.broadcasted_iota(jnp.int32, (1, max_m), 1)

    def rank1(lvl, pos):
        w = lvl * stride + (pos >> 5)
        words, hit = load_row(words_ref, w)
        prefix, _ = load_row(prefix_ref, w)
        mask = (jnp.int32(1) << (pos & 31)) - 1
        return pick(prefix + jax.lax.population_count(words & mask), hit)

    def query(q, carry):
        pat = pat_ref[pl.ds(q, 1), :]          # right-to-left symbols
        length = len_ref[0, q]

        def more(s):
            t, lo, hi = s
            return (t < length) & (lo < hi)

        def sym_step(s):
            t, lo, hi = s
            c = pick(pat, slots == t)
            c_ok = (c >= 0) & (c < sigma)
            cc = jnp.clip(c, 0, sigma - 1)
            p, r = lo, hi
            for lvl in range(levels):
                bit = (cc >> (levels - 1 - lvl)) & 1
                z = zcount_ref[lvl]
                r1p = rank1(lvl, p)
                r1r = rank1(lvl, r)
                p = jnp.where(bit == 0, p - r1p, z + r1p)
                r = jnp.where(bit == 0, r - r1r, z + r1r)
            b = base_ref[cc]
            oob = jnp.where(c < 0, 0, n)
            return (t + 1, jnp.where(c_ok, b + p, oob),
                    jnp.where(c_ok, b + r, oob))

        _, lo, hi = jax.lax.while_loop(
            more, sym_step, (jnp.int32(0), jnp.int32(0), jnp.int32(n))
        )
        lo_ref[0, q] = lo
        hi_ref[0, q] = jnp.maximum(lo, hi)
        return carry

    jax.lax.fori_loop(0, bq, query, 0)


@functools.partial(
    jax.jit,
    static_argnames=("n", "sigma", "block_q", "interpret"),
)
def backward_search_pallas(
    words: jnp.ndarray,        # uint32[levels, W+1] wavelet-matrix words
    ones_prefix: jnp.ndarray,  # int32[levels, W+1]
    zcount: jnp.ndarray,       # int32[levels]
    base: jnp.ndarray,         # int32[sigma]: counts[c] - sym_starts[c]
    rev_patterns: jnp.ndarray, # int32[B, max_m], right-to-left symbol order
    lengths: jnp.ndarray,      # int32[B]
    *,
    n: int,
    sigma: int,
    block_q: int = 256,
    interpret: bool = True,
):
    """Fused batched backward search: (lo int32[B], hi int32[B]).

    ONE ``pallas_call`` regardless of batch size, pattern length, or level
    count — the launch-count contract the serving planner's tests assert.
    """
    levels, stride = words.shape
    B, max_m = rev_patterns.shape
    bq = tile_rows(batch=B, block_q=block_q)
    tiles = -(-B // bq)
    bpad = tiles * bq
    pat_p = jnp.zeros((bpad, max_m), jnp.int32).at[:B].set(rev_patterns)
    len_p = jnp.zeros(bpad, jnp.int32).at[:B].set(lengths).reshape(tiles, 1, bq)
    kernel = functools.partial(
        _backward_search_kernel,
        levels=levels, stride=stride, n=n, sigma=sigma,
    )
    per_query = pl.BlockSpec(
        (None, 1, bq), lambda i: (i, 0, 0), memory_space=pltpu.SMEM
    )
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    lo, hi = pl.pallas_call(
        kernel,
        grid=(tiles,),
        in_specs=[
            per_query,                                    # lengths
            smem,                                         # zcount
            smem,                                         # base
            pl.BlockSpec((bq, max_m), lambda i: (i, 0)),  # pattern tile
            vmem,                                         # words (resident)
            vmem,                                         # prefix (resident)
        ],
        out_specs=[per_query, per_query],
        out_shape=[
            jax.ShapeDtypeStruct((tiles, 1, bq), jnp.int32),
            jax.ShapeDtypeStruct((tiles, 1, bq), jnp.int32),
        ],
        interpret=interpret,
    )(len_p, zcount, base, pat_p, as_rows(words), as_rows(ones_prefix))
    return lo.reshape(bpad)[:B], hi.reshape(bpad)[:B]
