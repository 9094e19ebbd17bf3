"""Jitted public wrappers for the Pallas kernels.

``interpret=None`` (the default) lets the lowering platform decide: the
kernel is compiled by Mosaic when the program is lowered for TPU and runs
in the Pallas interpreter when it is lowered for CPU
(``jax.lax.platform_dependent``).  One traced program is therefore right on
both, and an AOT compile for a described TPU exercises the real kernel.

The serving wrappers never swap a kernel for its reference.  Whether the
fused kernels run at all is decided once, at build time, from the platform
and the VMEM budgets below (``backward_search_fits`` / ``ilcp_list_fits``
feed ``RetrievalService.build``), so the service's ``use_search_kernel`` /
``use_list_kernel`` flags say what runs.  Only degenerate shapes with a
closed-form answer skip the launch.

VMEM layout, as compiled for v5e: the resident tables are whole-array VMEM
operands that XLA places in VMEM once per launch (one copy); the per-tile
blocks are double-buffered by the Pallas pipeline; the listing kernel's
stacks live in SMEM.  The ``*_block_meta`` helpers list the VMEM entries
with their buffer counts.
"""

from __future__ import annotations

import functools
import math

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.backward_search import backward_search_pallas, tile_rows
from repro.kernels.embedding_bag import embedding_bag_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ilcp_list import ilcp_list_pallas, v_rows_shape
from repro.kernels.rank import rank_pallas
from repro.kernels.rmq import rmq_pallas
from repro.kernels.rows import rows_shape

#: VMEM the backward-search kernel may keep resident for one wavelet
#: matrix; a larger index is served by the XLA pair descent (chosen at
#: build time), or sharded over a docs mesh so each shard fits.
BACKWARD_SEARCH_VMEM_BUDGET = 12 * 2**20

#: VMEM the fused listing kernel may keep resident: the RMQ table, vilcp,
#: the run boundaries, the document array and the bit-packed V marker.
ILCP_LIST_VMEM_BUDGET = 12 * 2**20


def _rows_bytes(size: int) -> int:
    return math.prod(rows_shape(size)) * 4


def backward_search_resident_bytes(words, ones_prefix) -> int:
    """VMEM the fused kernel keeps resident across the whole search: the
    flattened wavelet levels as two int32 (rows, 128) tables."""
    return _rows_bytes(int(words.size)) + _rows_bytes(int(ones_prefix.size))


def backward_search_fits(words, ones_prefix) -> bool:
    """Whether the wavelet matrix fits ``BACKWARD_SEARCH_VMEM_BUDGET`` —
    the build-time half of the kernel selection."""
    return (backward_search_resident_bytes(words, ones_prefix)
            <= BACKWARD_SEARCH_VMEM_BUDGET)


def shards_to_fit(resident_bytes: int,
                  budget: int | None = None) -> int:
    """Smallest docs-mesh shard count that brings a wavelet matrix of
    ``resident_bytes`` under the kernel's VMEM budget, assuming the
    balanced contiguous document split of ``doc_shard_bounds`` (each
    shard's matrix is ~1/S of the whole: same levels, 1/S of the text).

    Sizing hint for ``RetrievalService.build(mesh=...)`` — see
    docs/SHARDING.md."""
    if budget is None:
        budget = BACKWARD_SEARCH_VMEM_BUDGET
    if budget <= 0:
        raise ValueError("budget must be positive")
    return max(1, -(-resident_bytes // budget))


def backward_search_block_meta(words, ones_prefix, batch: int, max_m: int,
                               *, block_q: int = 256) -> list:
    """VMEM the fused kernel claims, as (shape, dtype, buffers) entries
    mirroring ``backward_search_pallas``: the double-buffered pattern tile
    and the two resident tables.  Lengths, zcount, base and the results
    live in SMEM."""
    return [
        ((tile_rows(batch=batch, block_q=block_q), max_m), "int32", 2),   # pattern tile
        (rows_shape(int(words.size)), "int32", 1),          # words
        (rows_shape(int(ones_prefix.size)), "int32", 1),    # ones_prefix
    ]


def block_meta_bytes(meta) -> int:
    """Total VMEM bytes of a ``*_block_meta`` layout."""
    return sum(
        int(math.prod(shape)) * np.dtype(dtype).itemsize * buffers
        for shape, dtype, buffers in meta
    )


def _launch(kernel, *args, interpret, **kw):
    """Run a jitted Pallas kernel.  ``interpret=None`` compiles it with
    Mosaic for TPU and interprets it for CPU, per lowering platform."""
    if interpret is not None:
        return kernel(*args, interpret=interpret, **kw)
    return jax.lax.platform_dependent(
        *args,
        cpu=functools.partial(kernel, interpret=True, **kw),
        tpu=functools.partial(kernel, interpret=False, **kw),
    )


def rank(words, ones_prefix, idx, *, block_q=1024, interpret=None):
    return _launch(rank_pallas, words, ones_prefix, idx,
                   interpret=interpret, block_q=block_q)


def backward_search(words, ones_prefix, zcount, base, patterns, lengths, *,
                    n, sigma, block_q=256, interpret=None):
    """Fused batched CSA backward search (see repro.kernels.backward_search).

    Takes natural left-to-right padded patterns; the right-to-left
    processing order the kernel wants is materialised here with one gather.
    Degenerate shapes have closed forms and launch nothing: an empty batch,
    zero-width patterns (every row is the empty pattern: (0, n)), and an
    empty alphabet (every non-empty pattern is out of alphabet).
    """
    patterns = jnp.asarray(patterns, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    B, max_m = patterns.shape
    if B == 0 or max_m == 0:
        return jnp.zeros(B, jnp.int32), jnp.full(B, n, jnp.int32)
    j = jnp.clip(
        lengths[:, None] - 1 - jnp.arange(max_m, dtype=jnp.int32)[None, :],
        0, max_m - 1,
    )
    rev = jnp.take_along_axis(patterns, j, axis=1)
    if base.shape[0] == 0:
        lo = jnp.where((lengths > 0) & (rev[:, 0] >= 0), n, 0).astype(jnp.int32)
        return lo, jnp.where(lengths > 0, lo, n).astype(jnp.int32)
    return _launch(
        backward_search_pallas,
        words, ones_prefix, zcount, base, rev, lengths,
        interpret=interpret, n=n, sigma=sigma, block_q=block_q,
    )


def rmq(values, table, lo, hi, *, block_q=1024, interpret=None):
    return _launch(rmq_pallas, values, table, lo, hi,
                   interpret=interpret, block_q=block_q)


def ilcp_list_resident_bytes(vilcp, table, run_starts, da) -> int:
    """VMEM the fused listing kernel keeps resident across the recursion:
    the flattened RMQ table, the run head values, the run boundaries and
    the document array, each an int32 (rows, 128) table."""
    return sum(_rows_bytes(int(x.size)) for x in (table, vilcp, run_starts, da))


def ilcp_list_scratch_bytes(d: int) -> int:
    """VMEM scratch of the listing kernel: the bit-packed distinct-document
    marker V (ceil(d / 32) int32 words, padded to (rows, 128)).  The
    interval stacks are SMEM."""
    return math.prod(v_rows_shape(d)) * 4


def ilcp_list_fits(vilcp, table, run_starts, da, *, d: int) -> bool:
    """Whether the listing kernel's resident tables plus V fit
    ``ILCP_LIST_VMEM_BUDGET`` — the build-time half of the selection."""
    return (ilcp_list_resident_bytes(vilcp, table, run_starts, da)
            + ilcp_list_scratch_bytes(d)) <= ILCP_LIST_VMEM_BUDGET


def ilcp_list_block_meta(vilcp, table, run_starts, da,
                         batch: int, *, d: int, max_df: int,
                         block_q: int = 128) -> list:
    """VMEM the fused listing kernel claims, as (shape, dtype, buffers)
    entries mirroring ``ilcp_list_pallas``: the double-buffered docs tile,
    the four resident tables and the V scratch.  Query bounds, counts and
    the interval stacks live in SMEM."""
    return [
        ((tile_rows(batch=batch, block_q=block_q), max_df), "int32", 2),  # docs out tile
        (rows_shape(int(table.size)), "int32", 1),           # RMQ table
        (rows_shape(int(vilcp.size)), "int32", 1),           # vilcp
        (rows_shape(int(run_starts.size)), "int32", 1),      # run boundaries
        (rows_shape(int(da.size)), "int32", 1),              # document array
        (v_rows_shape(d), "int32", 1),                       # scratch: V
    ]


def runs_of(run_starts, pos):
    """Run index containing ILCP position ``pos`` (vectorised ``_run_of``:
    rank1 over the run-start bitvector = searchsorted over the starts).
    ``pos = -1`` (empty range roots) maps to run -1."""
    starts = run_starts[: run_starts.shape[0] - 1]
    return (
        jnp.searchsorted(starts, jnp.asarray(pos, jnp.int32), side="right")
        .astype(jnp.int32) - 1
    )


def ilcp_list(vilcp, table, run_starts, da, lo, hi, *,
              d, max_df, block_q=128, interpret=None):
    """Fused batched ILCP document listing (see repro.kernels.ilcp_list).

    Takes SA ranges; the run indices of the range endpoints the kernel
    wants are materialised here with one searchsorted per boundary — the
    backward-search wrapper's pattern-reversal move.  Degenerate shapes
    (empty batch, zero ``max_df``, no documents) have a closed-form answer
    (no documents) and launch nothing.
    """
    lo = jnp.asarray(lo, jnp.int32)
    hi = jnp.asarray(hi, jnp.int32)
    B = lo.shape[0]
    if B == 0 or max_df <= 0 or d <= 0:
        return (jnp.full((B, max(max_df, 0)), -1, jnp.int32),
                jnp.zeros((B,), jnp.int32))
    return _launch(
        ilcp_list_pallas,
        vilcp, table, run_starts, da, lo, hi,
        runs_of(run_starts, lo), runs_of(run_starts, hi - 1),
        interpret=interpret, d=d, max_df=max_df, block_q=block_q,
    )


def embedding_bag(table, padded_idx, *, mode="sum", block_b=128, interpret=None):
    return _launch(embedding_bag_pallas, table, padded_idx,
                   interpret=interpret, mode=mode, block_b=block_b)


def flash_attention(
    q, k, v, *, causal=True, block_q=128, block_k=128, interpret=None
):
    Sq, Skv = q.shape[2], k.shape[2]
    bq = min(block_q, Sq)
    bk = min(block_k, Skv)
    if Sq % bq or Skv % bk:
        return ref.flash_attention_ref(q, k, v, causal=causal)
    return _launch(flash_attention_pallas, q, k, v,
                   interpret=interpret, causal=causal, block_q=bq, block_k=bk)
