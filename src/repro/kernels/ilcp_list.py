"""Pallas TPU kernel: fused ILCP document listing — one launch per batch.

The Fig-1 recursion (paper Section 3.3) is the listing hot path.  This
kernel runs the ENTIRE recursion — bounded explicit stack, leftmost-min
sparse-table RMQ, run->position resolution, document lookup, distinct-doc
dedup up to ``max_df`` — inside ONE ``pallas_call`` per padded batch:

  * the flattened RMQ table, the run head values ``vilcp``, the run
    boundaries and the document array stay VMEM-resident across the whole
    recursion, each as one int32 ``(rows, 128)`` table
    (``repro.kernels.rows``: a lookup is a dynamic row load plus a lane
    select — Mosaic has no VMEM vector gather);
  * the query batch streams through the grid in ``block_q`` tiles; each
    query's bounds come from SMEM and its count goes back to SMEM;
  * inside one grid step the queries of the tile run one after another as
    scalar loops that replay ``ilcp_list_docs`` exactly: the interval stack
    lives in SMEM scratch, the bit-packed ``V`` marker in VMEM scratch
    (cleared per query), and the query's document row is carried as one
    vector and stored once.  Documents come out in discovery order,
    bit-identical to the vmap'd while_loop path and the lockstep oracle.

Callers resolve the query bounds to run indices (``lo_run``/``hi_run``)
up front with one ``searchsorted`` over the run starts — the same
"materialise the access order outside the kernel" move the backward-search
wrapper makes for pattern reversal.  Rows padded past the true batch get
``hi_run = -1``: their root interval is invalid, so they pop once and
retire without touching the tables.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backward_search import tile_rows
from repro.kernels.rows import as_rows, load_row, lookup, pick, rows_shape

#: stack capacity / pop budget as functions of max_df — shared with the
#: while_loop reference (``ilcp_list_docs``) so trajectories stay aligned.
def stack_cap(max_df: int) -> int:
    return max_df + 4


def pop_cap(max_df: int) -> int:
    return 2 * max_df + 8


def v_rows_shape(d: int) -> tuple[int, int]:
    """(rows, 128) shape of the bit-packed distinct-document marker V:
    ceil(d / 32) int32 words."""
    return rows_shape(-(-max(d, 1) // 32))


def _ilcp_list_kernel(
    lo_ref, hi_ref, lor_ref, hir_ref, table_ref, vilcp_ref, rs_ref, da_ref,
    docs_ref, cnt_ref, stka_ref, stkb_ref, v_ref, *,
    levels: int, rho: int, n: int, d: int, max_df: int,
):
    bq = docs_ref.shape[0]
    cap = stack_cap(max_df)
    max_pops = pop_cap(max_df)
    slots = jax.lax.broadcasted_iota(jnp.int32, (1, max_df), 1)

    def rmq(a, b):
        # leftmost argmin of vilcp[a..b] over the flattened sparse table;
        # k = min(floor(lg(span)), levels - 1) without a scalar clz
        span = jnp.maximum(b - a + 1, 1)
        k = jnp.int32(0)
        for i in range(1, levels):
            k = k + (span >= (1 << i)).astype(jnp.int32)
        right = jnp.maximum(b - (jnp.int32(1) << k) + 1, a)
        ia = lookup(table_ref, k * rho + a)
        ib = lookup(table_ref, k * rho + right)
        va = lookup(vilcp_ref, ia)
        vb = lookup(vilcp_ref, ib)
        pick_b = (vb < va) | ((vb == va) & (ib < ia))
        return jnp.where(pick_b, ib, ia)

    def query(q, carry):
        lo = lo_ref[0, q]
        hi = hi_ref[0, q]
        stka_ref[0] = lor_ref[0, q]
        stkb_ref[0] = hir_ref[0, q]

        @pl.when(lo < hi)
        def _():
            v_ref[...] = jnp.zeros(v_ref.shape, jnp.int32)

        def more_pops(s):
            sp, cnt, pops, _ = s
            return (sp > 0) & (cnt < max_df) & (pops < max_pops)

        def pop(s):
            sp, cnt, pops, docs = s
            sp = sp - 1
            a = stka_ref[sp]
            b = stkb_ref[sp]
            valid = (a <= b) & (lo < hi)
            r = rmq(jnp.clip(a, 0, rho - 1), jnp.clip(b, 0, rho - 1))
            k0 = jnp.maximum(lo, lookup(rs_ref, jnp.clip(r, 0, rho - 1)))
            j = jnp.minimum(hi, lookup(rs_ref, jnp.clip(r + 1, 0, rho)))
            j = jnp.where(valid, j, k0)          # an invalid pop scans nothing

            def more_scan(c):
                k, cnt, aborted, _ = c
                return (k < j) & (aborted == 0) & (cnt < max_df)

            def visit(c):
                # one DA position of the run: report a new document, or
                # abort the whole subrange on a repeat (Lemma 3)
                k, cnt, _, docs = c
                g = lookup(da_ref, jnp.clip(k, 0, n - 1))
                gc = jnp.clip(g, 0, max(d - 1, 0))
                vrow, hit = load_row(v_ref, gc >> 5)
                bit = jnp.int32(1) << (gc & 31)
                seen = pick(vrow & bit, hit) != 0
                v_ref[pl.ds(gc >> 12, 1), :] = jnp.where(hit, vrow | bit, vrow)
                docs = jnp.where((slots == cnt) & ~seen, g, docs)
                return (k + 1, jnp.where(seen, cnt, cnt + 1),
                        seen.astype(jnp.int32), docs)

            _, cnt, aborted, docs = jax.lax.while_loop(
                more_scan, visit, (k0, cnt, jnp.int32(0), docs)
            )
            # push right subrange first, then left (left popped first —
            # Lemma 3 with the leftmost RMQ); aborts kill the whole subrange
            push = valid & (aborted == 0)
            do1 = push & (r + 1 <= b) & (sp < cap)

            @pl.when(do1)
            def _():
                stka_ref[sp] = r + 1
                stkb_ref[sp] = b

            sp = sp + do1.astype(jnp.int32)
            do2 = push & (a <= r - 1) & (sp < cap)

            @pl.when(do2)
            def _():
                stka_ref[sp] = a
                stkb_ref[sp] = r - 1

            return sp + do2.astype(jnp.int32), cnt, pops + 1, docs

        _, cnt, _, docs = jax.lax.while_loop(
            more_pops, pop,
            (jnp.int32(1), jnp.int32(0), jnp.int32(0),
             jnp.full((1, max_df), -1, jnp.int32)),
        )
        docs_ref[pl.ds(q, 1), :] = docs
        cnt_ref[0, q] = cnt
        return carry

    jax.lax.fori_loop(0, bq, query, 0)


@functools.partial(
    jax.jit,
    static_argnames=("d", "max_df", "block_q", "interpret"),
)
def ilcp_list_pallas(
    vilcp: jnp.ndarray,       # int32[rho] run head values (RMQ values)
    table: jnp.ndarray,       # int32[levels, rho] sparse-table argmins
    run_starts: jnp.ndarray,  # int32[rho + 1] run boundaries (last = n)
    da: jnp.ndarray,          # int32[n] document array
    lo: jnp.ndarray,          # int32[B] SA-range starts
    hi: jnp.ndarray,          # int32[B] SA-range ends (exclusive)
    lo_run: jnp.ndarray,      # int32[B] run of lo
    hi_run: jnp.ndarray,      # int32[B] run of hi - 1
    *,
    d: int,
    max_df: int,
    block_q: int = 128,
    interpret: bool = True,
):
    """Fused batched ILCP listing: (docs int32[B, max_df] padded -1, cnt[B]).

    ONE ``pallas_call`` regardless of batch size, df, or recursion depth —
    the launch-count contract the listing tests assert.  Documents are in
    discovery order, bit-identical to ``ilcp_list_docs_da_batch``.
    """
    levels, rho = table.shape
    n = da.shape[0]
    B = lo.shape[0]
    bq = tile_rows(batch=B, block_q=block_q)
    tiles = -(-B // bq)
    bpad = tiles * bq

    def per_query(x, fill):
        return jnp.full(bpad, fill, jnp.int32).at[:B].set(x).reshape(tiles, 1, bq)

    kernel = functools.partial(
        _ilcp_list_kernel,
        levels=levels, rho=rho, n=n, d=d, max_df=max_df,
    )
    query_spec = pl.BlockSpec(
        (None, 1, bq), lambda i: (i, 0, 0), memory_space=pltpu.SMEM
    )
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    docs, cnt = pl.pallas_call(
        kernel,
        grid=(tiles,),
        in_specs=[query_spec] * 4 + [vmem] * 4,
        out_specs=[
            pl.BlockSpec((bq, max_df), lambda i: (i, 0)),
            query_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bpad, max_df), jnp.int32),
            jax.ShapeDtypeStruct((tiles, 1, bq), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.SMEM((stack_cap(max_df),), jnp.int32),     # stack a
            pltpu.SMEM((stack_cap(max_df),), jnp.int32),     # stack b
            pltpu.VMEM(v_rows_shape(d), jnp.int32),          # V (bit-packed)
        ],
        interpret=interpret,
        name="ilcp_list",
    )(
        per_query(lo, 0), per_query(hi, 0),
        per_query(lo_run, 0), per_query(hi_run, -1),
        as_rows(table), as_rows(vilcp), as_rows(run_starts), as_rows(da),
    )
    return docs[:B], cnt.reshape(bpad)[:B]
