"""Interleaved LCP (ILCP) index — Section 3 of the paper.

Structure (Section 3.3): the ILCP array is stored *run-length encoded*:
  * ``L``     — sparse bitvector with a 1 at the start of each of the rho runs
  * ``vilcp`` — the run head values (stored once; also the RMQ's value array)
  * RMQ over VILCP (leftmost minimum — required by Lemma 3)
and for counting (Section 3.4):
  * a wavelet matrix over VILCP (the skewed wavelet tree's rank role;
    see repro.succinct.wavelet docstring for the equivalence note)
  * ``clens`` — cumulative lengths of the runs re-ordered by (value, pos):
    this is the paper's L' bitmap, stored as its select-prefix-sum, which
    weights run-head occurrences by their run lengths.

Query model (TPU adaptation): document listing is the Fig-1 recursion
realised as a bounded explicit stack inside ``lax.while_loop`` — each query
is O(df) iterations (every non-aborting pop reports >= 1 new document, every
aborting pop kills its whole subrange by Lemma 3).  A batch of queries is
``vmap`` over the same program.  Counting is the Fig-3 computation with the
value loop of the skewed tree replaced by a rank descent per value
(O(m lg lambda) instead of O(m); DESIGN.md Section 6).
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.common import IDX, as_i32, ceil_log2, elias_fano_bits, pytree_dataclass
from repro.core.csa import CSA, csa_da_at
from repro.core.suffix import SuffixData
from repro.succinct.bitvector import SparseBitvector, sparse_from_positions
from repro.succinct.rmq import SparseTableRMQ, rmq_build, rmq_query
from repro.succinct.wavelet import WaveletMatrix, wm_build, wm_rank_pair


@pytree_dataclass(meta=("n", "d", "nruns", "max_value"))
class ILCPIndex:
    L: SparseBitvector          # run starts (rho ones over n)
    rmq: SparseTableRMQ         # over VILCP (leftmost-min)
    wm: WaveletMatrix           # over VILCP values
    vilcp: jnp.ndarray          # int32[rho] run head values
    run_starts: jnp.ndarray     # int32[rho + 1] run boundaries (last = n)
    clens: jnp.ndarray          # int32[rho + 1] cum lengths, (value, pos) order
    value_run_offset: jnp.ndarray  # int32[max_value + 2] first sorted run per value
    n: int
    d: int
    nruns: int
    max_value: int

    # -- space accounting (Theorems 1 and 2) --------------------------------

    def modeled_bits_listing(self) -> int:
        """rho lg(n/rho) + O(rho) [L] + 2 rho [RMQ] + d lg(n/d) + O(d) [B]."""
        rho, n, d = self.nruns, self.n, self.d
        return (
            elias_fano_bits(rho, max(n, 1))
            + 2 * rho + max(1, rho // 4)
            + elias_fano_bits(d, max(n, 1))
        )

    def modeled_bits_counting(self) -> int:
        """rho(lg lambda + 2 lg(n/rho) + O(1)) — Theorem 2."""
        rho, n = self.nruns, self.n
        lam = max(2, self.max_value + 1)
        return rho * ceil_log2(lam) + 2 * elias_fano_bits(rho, max(n, 1)) + 2 * rho


def build_ilcp(data: SuffixData) -> ILCPIndex:
    ilcp = np.asarray(data.ilcp, dtype=np.int32)
    n = len(ilcp)
    d = data.d
    if n == 0:
        raise ValueError("empty collection")
    change = np.flatnonzero(np.diff(ilcp)) + 1
    run_starts = np.concatenate([[0], change]).astype(np.int32)
    rho = len(run_starts)
    vilcp = ilcp[run_starts]
    run_bounds = np.concatenate([run_starts, [n]]).astype(np.int32)
    lengths = np.diff(run_bounds)

    # value-sorted run lengths (the L' reordering of Section 3.4)
    order = np.lexsort((np.arange(rho), vilcp))
    clens = np.concatenate([[0], np.cumsum(lengths[order])]).astype(np.int32)
    sorted_vals = vilcp[order]
    max_value = int(vilcp.max()) if rho else 0
    value_run_offset = np.searchsorted(
        sorted_vals, np.arange(max_value + 2), side="left"
    ).astype(np.int32)

    return ILCPIndex(
        L=sparse_from_positions(run_starts, n),
        rmq=rmq_build(vilcp),
        wm=wm_build(vilcp, max_value + 1),
        vilcp=jnp.asarray(vilcp),
        run_starts=jnp.asarray(run_bounds),
        clens=jnp.asarray(clens),
        value_run_offset=jnp.asarray(value_run_offset),
        n=n,
        d=d,
        nruns=rho,
        max_value=max_value,
    )


def ilcp_num_runs(data: SuffixData) -> int:
    """rho, the quantity bounded by Lemma 2."""
    ilcp = np.asarray(data.ilcp)
    return int(1 + np.count_nonzero(np.diff(ilcp))) if len(ilcp) else 0


# ---------------------------------------------------------------------------
# Document listing (Fig 1) — bounded-stack while_loop, vmap-batchable
# ---------------------------------------------------------------------------


def _run_of(index: ILCPIndex, pos):
    return index.L.rank1(as_i32(pos) + 1) - 1


def ilcp_list_docs(index: ILCPIndex, get_da, lo, hi, max_df: int):
    """Distinct documents in DA[lo, hi) via the ILCP recursion.

    get_da: traced k -> document id (either a stored-DA gather, Sada-I-D,
    or a CSA locate + B-rank, Sada-I-L).
    Returns (docs int32[max_df] padded with -1, count).
    """
    lo = as_i32(lo)
    hi = as_i32(hi)
    d = index.d
    cap = max_df + 4
    iter_cap = 2 * max_df + 8

    lo_run = _run_of(index, lo)
    hi_run = _run_of(index, hi - 1)

    stack_a = jnp.zeros(cap, IDX).at[0].set(lo_run)
    stack_b = jnp.zeros(cap, IDX).at[0].set(hi_run)
    init = (
        stack_a,
        stack_b,
        as_i32(1),                       # stack pointer
        jnp.zeros(d, jnp.bool_),         # V
        jnp.full(max_df, -1, IDX),       # results
        as_i32(0),                       # count
        as_i32(0),                       # iterations (safety)
    )

    def cond(state):
        _, _, sp, _, _, cnt, it = state
        return (sp > 0) & (cnt < max_df) & (it < iter_cap)

    def body(state):
        sa_, sb_, sp, V, res, cnt, it = state
        a = sa_[sp - 1]
        b = sb_[sp - 1]
        sp = sp - 1
        valid = a <= b

        def process(V, res, cnt, sa_, sb_, sp):
            i_run = rmq_query(index.rmq, a, b)
            i = jnp.maximum(lo, index.run_starts[i_run])
            j = jnp.minimum(hi, index.run_starts[i_run + 1])

            def scan_cond(c):
                k, V, res, cnt, aborted = c
                return (k < j) & ~aborted & (cnt < max_df)

            def scan_body(c):
                k, V, res, cnt, aborted = c
                g = get_da(k)
                seen = V[g]
                V = V.at[g].set(True)
                res = jnp.where(
                    seen, res, res.at[jnp.minimum(cnt, max_df - 1)].set(g)
                )
                cnt = jnp.where(seen, cnt, cnt + 1)
                return (k + 1, V, res, cnt, seen)

            k, V, res, cnt, aborted = jax.lax.while_loop(
                scan_cond, scan_body, (i, V, res, cnt, jnp.bool_(False))
            )

            # push right subrange first, then left (left processed first —
            # required by Lemma 3 together with leftmost RMQ)
            def push(sa_, sb_, sp, x, y):
                do = (x <= y) & (sp < cap)
                sa_ = jnp.where(do, sa_.at[jnp.minimum(sp, cap - 1)].set(x), sa_)
                sb_ = jnp.where(do, sb_.at[jnp.minimum(sp, cap - 1)].set(y), sb_)
                return sa_, sb_, jnp.where(do, sp + 1, sp)

            def do_push(args):
                sa_, sb_, sp = args
                sa_, sb_, sp = push(sa_, sb_, sp, i_run + 1, b)
                sa_, sb_, sp = push(sa_, sb_, sp, a, i_run - 1)
                return sa_, sb_, sp

            sa_2, sb_2, sp2 = jax.lax.cond(
                aborted, lambda t: t, do_push, (sa_, sb_, sp)
            )
            return V, res, cnt, sa_2, sb_2, sp2

        def skip(V, res, cnt, sa_, sb_, sp):
            return V, res, cnt, sa_, sb_, sp

        V, res, cnt, sa_, sb_, sp = jax.lax.cond(
            valid & (lo < hi),
            lambda _: process(V, res, cnt, sa_, sb_, sp),
            lambda _: skip(V, res, cnt, sa_, sb_, sp),
            None,
        )
        return (sa_, sb_, sp, V, res, cnt, it + 1)

    _, _, _, _, res, cnt, _ = jax.lax.while_loop(cond, body, init)
    return res, cnt


def ilcp_list_docs_da(index: ILCPIndex, da: jnp.ndarray, lo, hi, max_df: int):
    """Sada-I-D: explicit document array (n lg d bits, fastest)."""
    return ilcp_list_docs(index, lambda k: da[k], lo, hi, max_df)


def ilcp_list_docs_csa(index: ILCPIndex, csa: CSA, lo, hi, max_df: int):
    """Sada-I-L: document ids via CSA locate + B-rank (Theorem 1 space)."""
    return ilcp_list_docs(index, lambda k: csa_da_at(csa, k), lo, hi, max_df)


def ilcp_list_docs_da_batch(index: ILCPIndex, da: jnp.ndarray, lo, hi, max_df: int,
                            *, use_rmq_kernel: bool = False):
    """Sada-I-D over a range batch (masked-query contract of
    repro.core.listing): returns (docs int32[B, max_df] padded -1, count[B]).
    Document ids are reported in *discovery* order — callers needing the
    canonical sorted layout sort rows (repro.serve.retrieval does).

    ``use_rmq_kernel=True`` swaps the vmap'd per-query recursion for the
    batch-lockstep oracle with the popped-interval RMQ routed through the
    batched Pallas RMQ kernel (``repro.kernels.ops.rmq``) — one launch per
    lockstep round instead of an XLA gather chain per query.  Answers are
    bit-identical either way; the default keeps the serve XLA path at zero
    ``pallas_call``s."""
    lo = as_i32(lo)
    hi = as_i32(hi)
    if not use_rmq_kernel:
        return jax.vmap(lambda a, b: ilcp_list_docs_da(index, da, a, b, max_df))(
            lo, hi
        )

    from repro.kernels import ops, ref

    def rmq_fn(a, b):
        return ops.rmq(index.vilcp, index.rmq.table, a, b)

    return ref.ilcp_list_ref(
        index.vilcp, index.rmq.table, index.run_starts, da, lo, hi,
        ops.runs_of(index.run_starts, lo),
        ops.runs_of(index.run_starts, hi - 1),
        d=index.d, max_df=max_df, rmq_fn=rmq_fn,
    )


def ilcp_list_docs_da_planned(index: ILCPIndex, da: jnp.ndarray, lo, hi,
                              max_df: int, *, use_kernel: bool = False,
                              block_q: int = 128, interpret: bool | None = None):
    """Sada-I-D listing written batch-first for the serving executor.

    Same integers as ``ilcp_list_docs_da_batch`` — documents in discovery
    order, bit-identical across paths.

    ``use_kernel`` selects the execution path (the serving layer decides it
    once, at build time):
      * ``True``  — the fused kernel (``repro.kernels.ilcp_list``; one
        ``pallas_call`` for the whole batched recursion, interpreted when
        the program is lowered for CPU unless ``interpret`` says otherwise);
      * ``False`` — the XLA vmap'd while_loop path.
    """
    if use_kernel:
        from repro.kernels.ops import ilcp_list

        return ilcp_list(
            index.vilcp, index.rmq.table, index.run_starts, da, lo, hi,
            d=index.d, max_df=max_df, block_q=block_q, interpret=interpret,
        )
    return ilcp_list_docs_da_batch(index, da, lo, hi, max_df)


def ilcp_list_docs_csa_batch(index: ILCPIndex, csa: CSA, lo, hi, max_df: int):
    """Sada-I-L over a range batch; same contract as the -da variant."""
    return jax.vmap(lambda a, b: ilcp_list_docs_csa(index, csa, a, b, max_df))(
        as_i32(lo), as_i32(hi)
    )


# ---------------------------------------------------------------------------
# Document counting (Fig 3)
# ---------------------------------------------------------------------------


def ilcp_count_docs(index: ILCPIndex, lo, hi, m):
    """df = |{distinct docs in DA[lo, hi)}| = #{k in [lo, hi) : ILCP[k] < m}.

    m is the pattern length (Lemma 1).  Runs fully inside the range
    contribute via the L' cumulative lengths; the first/last run overlap is
    corrected exactly as in the paper's countDocuments.
    """
    lo = as_i32(lo)
    hi = as_i32(hi)
    m = as_i32(m)

    lo_run = _run_of(index, lo)
    hi_run = _run_of(index, jnp.maximum(hi - 1, lo))

    def per_value(v, acc):
        # both run boundaries share one wavelet descent (wm_rank_pair):
        # 2 rank gathers per level instead of the 4 of two wm_rank calls
        a, b = wm_rank_pair(index.wm, v, lo_run, hi_run + 1)
        off = index.value_run_offset[jnp.minimum(v, index.max_value + 1)]
        return acc + index.clens[off + b] - index.clens[off + a]

    vmax = jnp.minimum(m, index.max_value + 1)
    total = jax.lax.fori_loop(0, vmax, per_value, as_i32(0))

    # corrections: clip the first/last run to the query range
    v_lo = index.vilcp[lo_run]
    total = total - jnp.where(v_lo < m, lo - index.run_starts[lo_run], 0)
    v_hi = index.vilcp[hi_run]
    total = total - jnp.where(v_hi < m, index.run_starts[hi_run + 1] - hi, 0)

    return jnp.where(lo >= hi, 0, total).astype(IDX)


def ilcp_count_docs_batch(index: ILCPIndex, lo, hi, m):
    return jax.vmap(lambda a, b, c: ilcp_count_docs(index, a, b, c))(
        as_i32(lo), as_i32(hi), as_i32(m)
    )


# ---------------------------------------------------------------------------
# Host-side skewed wavelet tree (paper Fig 2) — reference + space model
# ---------------------------------------------------------------------------


class SkewedWaveletTree:
    """Literal host-side implementation of the Section 3.4 skewed shape:
    leaf for value i at depth 1 + 2*floor(lg(i+1)).  Used as the oracle for
    the jitted counting path and for modeled-space reporting.

    The tree is materialised as nested python nodes over numpy arrays; a
    node is (values_mask_bitvector, left, right).  Spine node S_k covers
    value groups k, k+1, ...; its left child is a balanced subtree over
    group k = values [2^{k-1}-1, 2^k-2].
    """

    def __init__(self, seq: np.ndarray, max_value: int):
        self.seq = np.asarray(seq, dtype=np.int64)
        self.max_value = max_value
        self.total_bits = 0
        self.root = self._build_spine(self.seq, 1)

    def _build_spine(self, seq, group):
        if len(seq) == 0:
            return None
        lo_v = (1 << (group - 1)) - 1
        hi_v = (1 << group) - 2  # inclusive
        if lo_v > self.max_value:
            return None
        go_left = seq <= hi_v
        self.total_bits += len(seq)
        left = self._build_balanced(seq[go_left], lo_v, min(hi_v, self.max_value))
        right = self._build_spine(seq[~go_left], group + 1)
        return ("spine", go_left, left, right)

    def _build_balanced(self, seq, lo_v, hi_v):
        if len(seq) == 0 or lo_v > hi_v:
            return None
        if lo_v == hi_v:
            return ("leaf", lo_v, len(seq))
        mid = (lo_v + hi_v) // 2
        go_left = seq <= mid
        self.total_bits += len(seq)
        return (
            "node",
            go_left,
            self._build_balanced(seq[go_left], lo_v, mid),
            self._build_balanced(seq[~go_left], mid + 1, hi_v),
        )

    def count_less(self, lo: int, hi: int, m: int) -> int:
        """Occurrences of values < m in seq[lo, hi) — O(m) nodes visited."""

        def walk(node, lo, hi):
            if node is None or lo >= hi:
                return 0
            kind = node[0]
            if kind == "leaf":
                _, value, _ = node
                return hi - lo if value < m else 0
            _, go_left, left, right = node
            pref = np.cumsum(go_left)
            nl_lo = int(pref[lo - 1]) if lo > 0 else 0
            nl_hi = int(pref[hi - 1]) if hi > 0 else 0
            total = 0
            # left subtree covers smaller values: descend if any value < m there
            total += walk(left, nl_lo, nl_hi)
            total += walk(right, lo - nl_lo, hi - nl_hi)
            return total

        return walk(self.root, lo, hi)

    def modeled_bits(self) -> int:
        return self.total_bits + max(1, self.total_bits // 8)
