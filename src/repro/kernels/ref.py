"""Pure-jnp oracles for every Pallas kernel in this package.

Each kernel's tests sweep shapes/dtypes and assert_allclose against these.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rank_ref(words: jnp.ndarray, ones_prefix: jnp.ndarray, idx: jnp.ndarray):
    """Batched rank1: ones in bits [0, idx) of the packed bitvector.

    words: uint32[W(+1)], ones_prefix: int32[W+1], idx: int32[Q].
    """
    w = idx >> 5
    off = (idx & 31).astype(jnp.uint32)
    word = words[w]
    mask = (jnp.uint32(1) << off) - jnp.uint32(1)
    return ones_prefix[w] + jax.lax.population_count(word & mask).astype(jnp.int32)


def backward_search_ref(
    words: jnp.ndarray,        # uint32[levels, W+1] wavelet-matrix words
    ones_prefix: jnp.ndarray,  # int32[levels, W+1]
    zcount: jnp.ndarray,       # int32[levels]
    base: jnp.ndarray,         # int32[sigma]: counts[c] - sym_starts[c]
    rev_patterns: jnp.ndarray, # int32[B, max_m], right-to-left symbol order
    lengths: jnp.ndarray,      # int32[B]
    *,
    n: int,
    sigma: int,
):
    """Batched CSA backward search over the BWT wavelet matrix.

    Same operand layout and the same integers as the fused Pallas kernel
    (repro.kernels.backward_search): patterns pre-reversed into processing
    order, both range boundaries sharing one descent per symbol step, one
    rank gather per level per boundary via the precomputed block-start
    ``base``.  Out-of-alphabet symbols collapse to the empty range at the
    symbol's insertion point; length-0 rows return the untouched (0, n).
    """
    levels = words.shape[0]
    B, max_m = rev_patterns.shape
    flat_w = words.reshape(-1)
    flat_p = ones_prefix.reshape(-1)
    stride = words.shape[1]

    def rank1(lvl, pos):
        w = lvl * stride + (pos >> 5)
        off = (pos & 31).astype(jnp.uint32)
        mask = (jnp.uint32(1) << off) - jnp.uint32(1)
        pc = jax.lax.population_count(flat_w[w] & mask).astype(jnp.int32)
        return flat_p[w] + pc

    def sym_step(carry, c):
        lo, hi, t = carry
        active = (t < lengths) & (lo < hi)
        c_ok = (c >= 0) & (c < sigma)
        cc = jnp.clip(c, 0, sigma - 1)

        def level_step(lvl, pq):
            p, q = pq
            bit = (cc >> (levels - 1 - lvl)) & 1
            z = zcount[lvl]
            r1p = rank1(lvl, p)
            r1q = rank1(lvl, q)
            p = jnp.where(bit == 0, p - r1p, z + r1p)
            q = jnp.where(bit == 0, q - r1q, z + r1q)
            return (p, q)

        dlo, dhi = jax.lax.fori_loop(0, levels, level_step, (lo, hi))
        b = base[cc]
        oob = jnp.where(c < 0, 0, n)
        lo = jnp.where(active, jnp.where(c_ok, b + dlo, oob), lo)
        hi = jnp.where(active, jnp.where(c_ok, b + dhi, oob), hi)
        return (lo, hi, t + 1), None

    (lo, hi, _), _ = jax.lax.scan(
        sym_step,
        (jnp.zeros(B, jnp.int32), jnp.full(B, n, jnp.int32), jnp.int32(0)),
        rev_patterns.T,
    )
    return lo, jnp.maximum(lo, hi)


def rmq_ref(values: jnp.ndarray, table: jnp.ndarray, lo: jnp.ndarray, hi: jnp.ndarray):
    """Batched leftmost-argmin over values[lo..hi] via the sparse table.

    table: int32[Lv, n] (argmin of 2^k windows), lo/hi: int32[Q] inclusive.
    """
    span = jnp.maximum(hi - lo + 1, 1)
    k = 31 - jax.lax.clz(span)
    k = jnp.clip(k, 0, table.shape[0] - 1)
    a = table[k, lo]
    b = table[k, jnp.maximum(hi - (jnp.int32(1) << k) + 1, lo)]
    va = values[a]
    vb = values[b]
    pick_b = (vb < va) | ((vb == va) & (b < a))
    return jnp.where(pick_b, b, a).astype(jnp.int32)


def lockstep_iteration_cap(max_df: int) -> int:
    """Safety ceiling on the lockstep iterations of ``ilcp_list_ref``.
    Each pop costs one iteration (<= pop_cap) and each visited DA position
    one more; a visited position either reports a new document (<= max_df)
    or aborts its pop (<= pop_cap), so the trajectory of any single query
    is bounded by ``pop_cap + max_df + pop_cap`` iterations plus the final
    retire step.  The loop normally exits far earlier on the all-done
    predicate."""
    return 5 * max_df + 36


def ilcp_list_ref(
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
    rmq_fn=None,
):
    """Batched ILCP document listing over the Fig-1 recursion.

    Same operand layout and the same integers as the fused Pallas kernel
    (repro.kernels.ilcp_list): the per-query recursion is flattened into a
    POP/SCAN state machine and the whole batch advances in lockstep through
    one ``lax.while_loop``, replaying ``ilcp_list_docs`` trajectories
    exactly — documents come out in discovery order, bit-identical to the
    vmap'd while_loop path and to the kernel.

    ``rmq_fn(a, b) -> leftmost argmin of vilcp[a..b]`` may be injected to
    route the popped-interval RMQ through the batched Pallas RMQ kernel
    (``repro.kernels.ops.rmq``); default is the inline two-gather chain.
    """
    from repro.kernels.ilcp_list import pop_cap, stack_cap

    levels, rho = table.shape
    n = da.shape[0]
    B = lo.shape[0]
    cap = stack_cap(max_df)
    iter_cap = pop_cap(max_df)
    rows = jnp.arange(B, dtype=jnp.int32)
    flat = table.reshape(-1)

    if rmq_fn is None:
        def rmq_fn(a, b):
            span = jnp.maximum(b - a + 1, 1)
            k = jnp.clip(31 - jax.lax.clz(span), 0, levels - 1)
            right = jnp.maximum(b - (jnp.int32(1) << k) + 1, a)
            ia = flat[k * rho + a]
            ib = flat[k * rho + right]
            va = vilcp[ia]
            vb = vilcp[ib]
            pick_b = (vb < va) | ((vb == va) & (ib < ia))
            return jnp.where(pick_b, ib, ia)

    zeros = jnp.zeros(B, jnp.int32)
    init = (
        jnp.int32(0),
        jnp.zeros(B, jnp.bool_),                          # done
        zeros, zeros, zeros, zeros, zeros, zeros,         # mode,a,b,i_run,k,j
        jnp.ones(B, jnp.int32),                           # sp
        zeros, zeros,                                     # cnt, pops
        jnp.zeros((B, cap), jnp.int32).at[:, 0].set(lo_run),
        jnp.zeros((B, cap), jnp.int32).at[:, 0].set(hi_run),
        jnp.zeros((B, d), jnp.bool_),                     # V
        jnp.full((B, max_df), -1, jnp.int32),             # docs
    )

    def cond(c):
        it, done = c[0], c[1]
        return jnp.any(~done) & (it < lockstep_iteration_cap(max_df))

    def body(c):
        (it, done, mode, a, b, i_run, k, j, sp, cnt, pops,
         sa, sb, V, docs) = c

        in_pop = ~done & (mode == 0)
        can_pop = in_pop & (sp > 0) & (cnt < max_df) & (pops < iter_cap)
        done = done | (in_pop & ~can_pop)

        top = jnp.maximum(sp - 1, 0)
        a = jnp.where(can_pop, sa[rows, top], a)
        b = jnp.where(can_pop, sb[rows, top], b)
        sp = jnp.where(can_pop, sp - 1, sp)
        pops = jnp.where(can_pop, pops + 1, pops)

        valid = can_pop & (a <= b) & (lo < hi)
        r = rmq_fn(jnp.clip(a, 0, rho - 1), jnp.clip(b, 0, rho - 1))
        i_run = jnp.where(valid, r, i_run)
        k = jnp.where(
            valid, jnp.maximum(lo, run_starts[jnp.clip(r, 0, rho - 1)]), k
        )
        j = jnp.where(
            valid, jnp.minimum(hi, run_starts[jnp.clip(r + 1, 0, rho)]), j
        )
        mode = jnp.where(valid, 1, mode)

        scanning = ~done & (mode == 1)
        proc = scanning & (k < j) & (cnt < max_df)
        g = da[jnp.clip(k, 0, n - 1)]
        gc = jnp.clip(g, 0, max(d - 1, 0))
        seen = V[rows, gc]
        rep = proc & ~seen
        V = V.at[rows, gc].set(jnp.where(proc, True, seen))
        slot = jnp.minimum(cnt, max_df - 1)
        docs = docs.at[rows, slot].set(jnp.where(rep, g, docs[rows, slot]))
        cnt = jnp.where(rep, cnt + 1, cnt)
        k = jnp.where(proc, k + 1, k)
        aborted = proc & seen
        ended = scanning & (aborted | (k >= j) | (cnt >= max_df))

        push = ended & ~aborted
        slot1 = jnp.minimum(sp, cap - 1)
        do1 = push & (i_run + 1 <= b) & (sp < cap)
        sa = sa.at[rows, slot1].set(jnp.where(do1, i_run + 1, sa[rows, slot1]))
        sb = sb.at[rows, slot1].set(jnp.where(do1, b, sb[rows, slot1]))
        sp = jnp.where(do1, sp + 1, sp)
        slot2 = jnp.minimum(sp, cap - 1)
        do2 = push & (a <= i_run - 1) & (sp < cap)
        sa = sa.at[rows, slot2].set(jnp.where(do2, a, sa[rows, slot2]))
        sb = sb.at[rows, slot2].set(jnp.where(do2, i_run - 1, sb[rows, slot2]))
        sp = jnp.where(do2, sp + 1, sp)
        mode = jnp.where(ended, 0, mode)

        return (it + 1, done, mode, a, b, i_run, k, j, sp, cnt, pops,
                sa, sb, V, docs)

    final = jax.lax.while_loop(cond, body, init)
    return final[14], final[9]


def embedding_bag_ref(
    table: jnp.ndarray, indices: jnp.ndarray, offsets: jnp.ndarray, mode: str = "sum"
):
    """EmbeddingBag: per-bag reduction of gathered rows.

    table: f[V, D]; indices: int32[N]; offsets: int32[B+1] (bag b spans
    indices[offsets[b]:offsets[b+1]]).  Returns f[B, D].
    Implemented with take + segment_sum — the pattern the assignment calls
    out as the system's own responsibility in JAX.
    """
    rows = jnp.take(table, indices, axis=0)
    nbags = offsets.shape[0] - 1
    seg = jnp.repeat(
        jnp.arange(nbags, dtype=jnp.int32),
        offsets[1:] - offsets[:-1],
        total_repeat_length=indices.shape[0],
    )
    summed = jax.ops.segment_sum(rows, seg, num_segments=nbags)
    if mode == "sum":
        return summed
    if mode == "mean":
        counts = (offsets[1:] - offsets[:-1]).astype(summed.dtype)
        return summed / jnp.maximum(counts, 1)[:, None]
    raise ValueError(mode)


def flash_attention_ref(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, causal: bool = True,
    scale: float | None = None,
):
    """Reference attention: q,k,v [B, H, S, Dh] -> [B, H, S, Dh] (f32 math)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * scale
    if causal:
        s = q.shape[2]
        mask = jnp.tril(jnp.ones((s, k.shape[2]), dtype=bool), k.shape[2] - s)
        logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vf)
    return out.astype(q.dtype)
