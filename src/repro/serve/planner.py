"""Query planner — stage 1 of the batched on-device query engine.

The paper's experimental recommendation (Section 6.2.2) is a *dispatch
policy*: document frequency df is cheap to compute first (Sada), occ =
hi - lo falls out of the CSA range search, and the listing engine is chosen
by their ratio — Brute-L when occ/df is below a threshold (~4 on the
paper's hardware), the precomputed machinery (PDL) otherwise.

This module turns that policy into a fully traced program: one fused pass
over a padded pattern batch computes ``(lo, hi)`` (CSA backward search),
``df`` (Sadakane counting), ``occ``, and a per-query **engine assignment**
as an int32 array — no host branching anywhere.  The masked batch executors
(stage 2, ``repro.core.*``) then run every engine over its sub-batch under
``jnp.where`` masking, and the serving layer (stage 3,
``repro.serve.retrieval``) compiles planner + executors into a single
program per shape bucket.

Engine codes are part of the serving ABI (they appear in plans returned to
callers): 0 = empty range, 1 = Brute-L, 2 = ILCP (Sada-I-D), 3 = PDL.
``forced_engine`` is a *traced* scalar (-1 = auto), so switching the engine
mode does not recompile the program.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.common import IDX, as_i32, pytree_dataclass
from repro.core.csa import CSA, csa_search_planned
from repro.core.sada import SadaCount, sada_count_batch

ENGINE_EMPTY = 0
ENGINE_BRUTE = 1
ENGINE_ILCP = 2
ENGINE_PDL = 3

#: public engine names -> forced-engine codes (-1 lets the planner decide)
ENGINE_CODES = {
    "auto": -1,
    "brute": ENGINE_BRUTE,
    "ilcp": ENGINE_ILCP,
    "pdl": ENGINE_PDL,
}


@pytree_dataclass
class QueryPlan:
    """Per-query execution plan (all int32[B] device arrays)."""

    lo: jnp.ndarray
    hi: jnp.ndarray
    occ: jnp.ndarray
    df: jnp.ndarray
    engine: jnp.ndarray


def plan_queries(
    csa: CSA,
    sada: SadaCount,
    patterns: jnp.ndarray,     # int32[B, max_m] padded patterns
    lengths: jnp.ndarray,      # int32[B] true lengths (0 = padding row)
    occ_df_threshold,          # traced f32 scalar
    forced_engine,             # traced i32 scalar; -1 = auto dispatch
    *,
    use_kernel: bool = False,
) -> QueryPlan:
    """One fused pass: ranges + df + occ + engine assignment.

    Rows with length 0 (batch padding) and patterns with no occurrences get
    ``ENGINE_EMPTY``; executors skip them under masking and the serving
    layer reports them as empty results.  ``use_kernel`` selects the range
    search's execution path: the fused Pallas backward-search kernel (one
    launch per batch — the TPU hot path) or the XLA pair descent.
    """
    lengths = as_i32(lengths)
    lo, hi = csa_search_planned(
        csa, as_i32(patterns), lengths, use_kernel=use_kernel
    )
    hi = jnp.where(lengths > 0, hi, lo)  # padding rows: empty range
    occ = hi - lo
    df = sada_count_batch(sada, lo, hi)

    thresh = jnp.asarray(occ_df_threshold, jnp.float32)
    auto = jnp.where(
        occ.astype(jnp.float32) < thresh * jnp.maximum(df, 1).astype(jnp.float32),
        ENGINE_BRUTE,
        ENGINE_PDL,
    ).astype(IDX)
    forced = as_i32(forced_engine)
    engine = jnp.where(forced >= 0, forced, auto)
    engine = jnp.where(occ > 0, engine, ENGINE_EMPTY).astype(IDX)
    return QueryPlan(lo=lo, hi=hi, occ=occ, df=df, engine=engine)


def masked_ranges(plan: QueryPlan, engine_code: int):
    """(lo, hi) with every query not assigned to ``engine_code`` collapsed
    to the empty range (0, 0) — the masking contract of the batch
    executors: an empty range costs one loop iteration and reports
    nothing."""
    sel = plan.engine == engine_code
    return jnp.where(sel, plan.lo, 0), jnp.where(sel, plan.hi, 0)
