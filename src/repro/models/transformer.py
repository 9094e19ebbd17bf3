"""Llama-family decoder transformers: dense (Llama 3.x / Mistral / SmolLM)
and MoE with top-1 routing + shared expert and 3:1 chunked-local:global
attention interleave (Llama 4 Scout / Maverick).

Structure: layers are grouped for ``lax.scan``.  A *group* holds ``period``
sub-layer positions with static attention types (llama4: [local, local,
local, global]; dense archs: period=1, [global]); parameters are stacked
[n_groups, ...] per position so one scan step runs one group.  This keeps
the lowered HLO a single while-loop over groups — essential for compiling
88-layer / 400B-parameter configs in the multi-pod dry-run.

Attention: GQA via KV-head grouping; RoPE on local (or all dense) layers,
NoPE on llama4 global layers (iRoPE); chunked local attention reshapes the
sequence into 8k chunks, masking causally within each chunk.  The XLA
einsum path is the default (it is what the dry-run lowers and the SPMD
partitioner shards); ``attention_impl='flash'`` swaps in the Pallas kernel
on TPU.

MoE: top-1 (Switch-style) routed expert + always-on shared expert, dense
dispatch via one-hot einsum over the expert axis so the expert dimension
shards over the ``model`` axis (EP): per-chip each expert's weights live on
E/model chips and the dispatch einsum lowers to an all-to-all-free
reduce-scatter pattern under GSPMD.

Steps exposed (built in repro.launch.steps with pjit shardings):
  forward_train   tokens -> mean xent loss       (train_4k)
  forward_prefill tokens -> last logits + cache  (prefill_32k)
  forward_decode  token + cache + pos -> logits  (decode_32k, long_500k)
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.common import apply_rope, rms_norm, swiglu


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int = 1            # top-1 per the assigned configs
    shared_expert: bool = True
    d_ff_expert: Optional[int] = None  # defaults to d_ff
    capacity_factor: float = 1.25      # Switch-style; overflow tokens drop


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    d_head: Optional[int] = None
    moe: Optional[MoEConfig] = None
    # attention layout: period & which positions are chunked-local
    period: int = 1
    local_positions: tuple = ()          # e.g. (0, 1, 2) for llama4
    local_chunk: int = 8192
    rope_theta: float = 500000.0
    tie_embeddings: bool = False
    param_dtype: jnp.dtype = jnp.bfloat16
    act_dtype: jnp.dtype = jnp.bfloat16
    attention_impl: str = "xla"          # "xla" | "flash"
    # expert parallelism via shard_map (set by the cell registry on
    # production meshes; None = single-device local dispatch)
    ep_mesh: Any = None
    ep_dp_axes: tuple = ()
    ep_fsdp: bool = False                # weights carry a data-axis shard

    @property
    def head_dim(self) -> int:
        return self.d_head or self.d_model // self.n_heads

    @property
    def n_groups(self) -> int:
        assert self.n_layers % self.period == 0
        return self.n_layers // self.period

    def param_count(self) -> int:
        dh = self.head_dim
        attn = self.d_model * dh * (self.n_heads + 2 * self.n_kv_heads) + (
            self.n_heads * dh * self.d_model
        )
        if self.moe:
            dff = self.moe.d_ff_expert or self.d_ff
            ffn = 3 * self.d_model * dff * self.moe.n_experts
            if self.moe.shared_expert:
                ffn += 3 * self.d_model * self.d_ff
            ffn += self.d_model * self.moe.n_experts  # router
        else:
            ffn = 3 * self.d_model * self.d_ff
        per_layer = attn + ffn + 2 * self.d_model
        emb = self.vocab * self.d_model * (1 if self.tie_embeddings else 2)
        return self.n_layers * per_layer + emb + self.d_model

    def active_param_count(self) -> int:
        """6*N_active*D convention for MoE rooflines."""
        if not self.moe:
            return self.param_count()
        dh = self.head_dim
        attn = self.d_model * dh * (self.n_heads + 2 * self.n_kv_heads) + (
            self.n_heads * dh * self.d_model
        )
        dff = self.moe.d_ff_expert or self.d_ff
        ffn = 3 * self.d_model * dff * self.moe.top_k
        if self.moe.shared_expert:
            ffn += 3 * self.d_model * self.d_ff
        ffn += self.d_model * self.moe.n_experts
        per_layer = attn + ffn + 2 * self.d_model
        emb = self.vocab * self.d_model * (1 if self.tie_embeddings else 2)
        return self.n_layers * per_layer + emb + self.d_model


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def _sublayer_params(cfg: LMConfig, key, g: int):
    """One sub-layer position's stacked parameters ([n_groups, ...])."""
    dh = cfg.head_dim
    d = cfg.d_model
    keys = jax.random.split(key, 12)
    dt = cfg.param_dtype
    G = cfg.n_groups
    s = 0.02

    def mk(k, *shape):
        return (jax.random.normal(k, (G, *shape)) * s).astype(dt)

    p = {
        "attn_norm": jnp.ones((G, d), dt),
        # head-structured projections: the head axis shards over `model`
        "wq": mk(keys[0], d, cfg.n_heads, dh),
        "wk": mk(keys[1], d, cfg.n_kv_heads, dh),
        "wv": mk(keys[2], d, cfg.n_kv_heads, dh),
        "wo": mk(keys[3], cfg.n_heads, dh, d),
        "ffn_norm": jnp.ones((G, d), dt),
    }
    if cfg.moe:
        dff = cfg.moe.d_ff_expert or cfg.d_ff
        E = cfg.moe.n_experts
        p["router"] = mk(keys[4], d, E)
        p["we_gate"] = mk(keys[5], E, d, dff)
        p["we_up"] = mk(keys[6], E, d, dff)
        p["we_down"] = mk(keys[7], E, dff, d)
        if cfg.moe.shared_expert:
            p["ws_gate"] = mk(keys[8], d, cfg.d_ff)
            p["ws_up"] = mk(keys[9], d, cfg.d_ff)
            p["ws_down"] = mk(keys[10], cfg.d_ff, d)
    else:
        p["w_gate"] = mk(keys[5], d, cfg.d_ff)
        p["w_up"] = mk(keys[6], d, cfg.d_ff)
        p["w_down"] = mk(keys[7], cfg.d_ff, d)
    return p


def init_params(cfg: LMConfig, key):
    keys = jax.random.split(key, cfg.period + 3)
    params = {
        "embed": (jax.random.normal(keys[0], (cfg.vocab, cfg.d_model)) * 0.02).astype(
            cfg.param_dtype
        ),
        "final_norm": jnp.ones((cfg.d_model,), cfg.param_dtype),
        "blocks": {
            f"pos{p}": _sublayer_params(cfg, keys[p + 1], p)
            for p in range(cfg.period)
        },
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (
            jax.random.normal(keys[-1], (cfg.d_model, cfg.vocab)) * 0.02
        ).astype(cfg.param_dtype)
    return params


def abstract_params(cfg: LMConfig):
    """ShapeDtypeStructs without allocation (dry-run path)."""
    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def _gqa_attention(cfg: LMConfig, q, k, v, causal_offset: int | None = 0,
                   q_block: int = 512):
    """q [B,S,H,Dh], k/v [B,Skv,K,Dh] -> [B,S,H,Dh].

    Blockwise over query chunks: each chunk materializes only a
    [B, H, q_block, Skv] score tile, never the full S x S matrix — this is
    what bounds activation memory for train_4k / prefill_32k on the
    production mesh (XLA-level flash; the Pallas kernel is the TPU fast
    path via attention_impl='flash').  The chunk loop is unrolled so
    cost_analysis sees the true FLOP total (scan bodies undercount).
    """
    B, S, H, Dh = q.shape
    K = k.shape[2]
    rep = H // K
    qg = q.reshape(B, S, K, rep, Dh)
    if cfg.attention_impl == "flash" and causal_offset is not None:
        from repro.kernels import flash_attention

        kr = jnp.repeat(k, rep, axis=2)
        vr = jnp.repeat(v, rep, axis=2)
        out = flash_attention(
            q.transpose(0, 2, 1, 3), kr.transpose(0, 2, 1, 3),
            vr.transpose(0, 2, 1, 3), causal=True,
        )
        return out.transpose(0, 2, 1, 3)

    Skv = k.shape[1]
    qb = min(q_block, S)
    assert S % qb == 0, (S, qb)
    nq = S // qb
    kpos = jnp.arange(Skv)[None, :]

    # context parallelism: when heads don't divide the model axis (e.g.
    # 40 heads on a 16-way axis), shard the KV sequence dimension instead —
    # score tiles become [*, q_block, Skv/model]; GSPMD inserts the softmax
    # max/sum reductions and the PV partial-sum all-reduce.
    if cfg.ep_mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as _P

        dp = tuple(cfg.ep_dp_axes)
        dspec = dp if len(dp) > 1 else dp[0]
        mdl_ok = Skv % cfg.ep_mesh.shape["model"] == 0
        kv_spec = _P(dspec, "model" if mdl_ok else None, None, None)
        cst = lambda a, sp: jax.lax.with_sharding_constraint(
            a, NamedSharding(cfg.ep_mesh, sp)
        )
        if B % int(np.prod([cfg.ep_mesh.shape[a] for a in dp])) == 0:
            k = cst(k, kv_spec)
            v = cst(v, kv_spec)
            qg = cst(qg, _P(dspec, None, None, None, None))

    # scan over query chunks: exactly one [*, q_block, Skv] score tile is
    # live at a time (fwd and — with the checkpoint — bwd).  No collectives
    # exist inside the chunk body, so roofline trip-accounting is unaffected.
    @jax.checkpoint
    def chunk_attn(carry, xs):
        qc, qpos0 = xs
        logits = jnp.einsum("bqkrd,btkd->bkrqt", qc, k).astype(jnp.float32)
        logits = logits * (Dh ** -0.5)
        if causal_offset is not None:
            qpos = qpos0 + jnp.arange(qb)[:, None] + causal_offset
            mask = kpos <= qpos
            logits = jnp.where(mask[None, None, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return carry, jnp.einsum("bkrqt,btkd->bqkrd", probs, v)

    q_chunks = qg.reshape(B, nq, qb, K, rep, Dh).transpose(1, 0, 2, 3, 4, 5)
    starts = jnp.arange(nq, dtype=jnp.int32) * qb
    _, outs = jax.lax.scan(chunk_attn, 0, (q_chunks, starts))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, K, rep, Dh)
    return out.reshape(B, S, H, Dh)


def _chunked_local_attention(cfg: LMConfig, q, k, v):
    """Causal attention within fixed chunks (llama4 local layers)."""
    B, S, H, Dh = q.shape
    C = min(cfg.local_chunk, S)
    assert S % C == 0
    nc = S // C
    K = k.shape[2]

    def resh(x, heads):
        return x.reshape(B * nc, C, heads, Dh)

    qc = q.reshape(B, nc, C, H, Dh).reshape(B * nc, C, H, Dh)
    kc = k.reshape(B, nc, C, K, Dh).reshape(B * nc, C, K, Dh)
    vc = v.reshape(B, nc, C, K, Dh).reshape(B * nc, C, K, Dh)
    out = _gqa_attention(cfg, qc, kc, vc, causal_offset=0)
    return out.reshape(B, nc, C, H, Dh).reshape(B, S, H, Dh)


# ---------------------------------------------------------------------------
# FFN / MoE
# ---------------------------------------------------------------------------


def _moe_ffn(cfg: LMConfig, p, x, capacity_factor: float | None = None):
    """Top-1 routed + shared expert, capacity-based sorted dispatch.

    Tokens are argsorted by expert id; each expert takes its first
    ``capacity`` tokens (Switch-style dropping).  Buffers are
    [E, capacity, D] with E sharded over ``model`` (EP), so memory is
    O(T * D + E * cap * D / ep) — never the dense [E, T, D] blowup.  The
    scatter/gather dispatch lowers to an all-to-all-like exchange under
    GSPMD.  Gradients flow through the gate weight (standard top-1).
    """
    B, S, D = x.shape
    E = cfg.moe.n_experts
    T = B * S
    if capacity_factor is None:
        capacity_factor = cfg.moe.capacity_factor
    cap = max(1, min(T, int(T / E * capacity_factor)))

    xf = x.reshape(T, D)
    scores = jnp.einsum("td,de->te", xf, p["router"]).astype(jnp.float32)
    gate = jax.nn.softmax(scores, axis=-1)
    top = jnp.argmax(gate, axis=-1).astype(jnp.int32)              # [T]
    top_w = jnp.take_along_axis(gate, top[:, None], axis=-1)[:, 0]  # [T]

    if S == 1:
        # decode: no token may be dropped — compute all experts for the few
        # live tokens and select (E x T x F is small at T = batch)
        onehot = jax.nn.one_hot(top, E, dtype=x.dtype)              # [T, E]
        g = jax.nn.silu(jnp.einsum("td,edf->etf", xf, p["we_gate"]))
        u = jnp.einsum("td,edf->etf", xf, p["we_up"])
        ye = jnp.einsum("etf,efd->etd", g * u, p["we_down"])        # [E,T,D]
        y = jnp.einsum("etd,te->td", ye, onehot)
        y = (y * top_w[:, None].astype(x.dtype)).reshape(B, S, D)
        if cfg.moe.shared_expert:
            y = y + swiglu(x, p["ws_gate"], p["ws_up"], p["ws_down"])
        return y, jnp.float32(0)

    # stable sort by expert; slot within expert = sorted pos - expert start
    perm = jnp.argsort(top)                                         # [T]
    top_sorted = top[perm]
    expert_start = jnp.searchsorted(top_sorted, jnp.arange(E, dtype=jnp.int32))
    slot_sorted = jnp.arange(T, dtype=jnp.int32) - expert_start[top_sorted]
    keep = slot_sorted < cap

    # dispatch into [E, cap, D] (overflow tokens dropped)
    xe = jnp.zeros((E, cap, D), x.dtype)
    se = jnp.where(keep, top_sorted, E)            # OOB -> dropped
    ss = jnp.where(keep, slot_sorted, cap)
    xe = xe.at[se, ss].set(xf[perm], mode="drop")

    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["we_gate"]))
    u = jnp.einsum("ecd,edf->ecf", xe, p["we_up"])
    ye = jnp.einsum("ecf,efd->ecd", g * u, p["we_down"])           # [E,cap,D]

    # combine: token at sorted pos s reads ye[expert, slot] (0 if dropped)
    gathered = ye[se, jnp.minimum(ss, cap - 1)]                     # [T, D]
    gathered = jnp.where(keep[:, None], gathered, 0)
    y = jnp.zeros((T, D), x.dtype).at[perm].set(gathered)
    y = (y * top_w[:, None].astype(x.dtype)).reshape(B, S, D)

    if cfg.moe.shared_expert:
        y = y + swiglu(x, p["ws_gate"], p["ws_up"], p["ws_down"])
    # load-balance auxiliary loss (Switch): E * sum_e f_e * P_e
    fe = jnp.zeros(E, jnp.float32).at[top].add(1.0) / T
    pe = jnp.mean(gate, axis=0)
    aux = E * jnp.sum(fe * pe)
    return y, aux


def _dense_ffn(cfg: LMConfig, p, x):
    return swiglu(x, p["w_gate"], p["w_up"], p["w_down"]), jnp.float32(0)


def _moe_ffn_ep(cfg: LMConfig, p, x, capacity_factor: float | None = None):
    """Expert parallelism with explicit collectives (shard_map).

    Routing and capacity dispatch are *local* to each data shard (a global
    token argsort under pjit forces activation replication — the reason
    this path exists); the [E, cap_local, D] buffers are exchanged across
    the `model` axis with all-to-all so each chip runs its E/ep experts,
    and FSDP-sharded expert weights all-gather their data-axis shard just
    before use.  This is the Switch/GShard execution scheme mapped onto
    jax.shard_map (DESIGN.md Section 5).
    """
    mesh = cfg.ep_mesh
    mdl = "model"
    dp = tuple(cfg.ep_dp_axes)
    E = cfg.moe.n_experts
    ep = mesh.shape[mdl]
    assert E % ep == 0, (E, ep)
    cf = capacity_factor or cfg.moe.capacity_factor
    B, S, D = x.shape
    import numpy as _np

    dpn = int(_np.prod([mesh.shape[a] for a in dp]))
    T_loc = (B // dpn) * S
    cap = max(1, min(T_loc, int(T_loc / E * cf)))
    P = jax.sharding.PartitionSpec
    dspec = dp if len(dp) > 1 else dp[0]

    def body(xl, router, wg, wu, wd):
        if cfg.ep_fsdp and dpn > 1:
            wg = jax.lax.all_gather(wg, dp, axis=2, tiled=True)
            wu = jax.lax.all_gather(wu, dp, axis=2, tiled=True)
            wd = jax.lax.all_gather(wd, dp, axis=1, tiled=True)
        Bl = xl.shape[0]
        xf = xl.reshape(Bl * S, D)
        T = xf.shape[0]
        scores = jnp.einsum("td,de->te", xf, router).astype(jnp.float32)
        gate = jax.nn.softmax(scores, axis=-1)
        top = jnp.argmax(gate, axis=-1).astype(jnp.int32)
        top_w = jnp.take_along_axis(gate, top[:, None], axis=-1)[:, 0]

        perm = jnp.argsort(top)
        top_sorted = top[perm]
        expert_start = jnp.searchsorted(top_sorted, jnp.arange(E, dtype=jnp.int32))
        slot_sorted = jnp.arange(T, dtype=jnp.int32) - expert_start[top_sorted]
        keep = slot_sorted < cap
        se = jnp.where(keep, top_sorted, E)
        ss = jnp.where(keep, slot_sorted, cap)
        xe = jnp.zeros((E, cap, D), xl.dtype).at[se, ss].set(xf[perm], mode="drop")

        # exchange: [E, cap, D] -> [E/ep, ep*cap, D]
        xe = jax.lax.all_to_all(xe, mdl, split_axis=0, concat_axis=1, tiled=True)

        # expert FFN, chunked over the token-capacity dim so the [*, F]
        # intermediates stay bounded (~2k tokens per tile); checkpointed so
        # the backward recomputes g/u per chunk instead of saving them
        cp = xe.shape[1]
        nch = max(1, cp // 2048)
        while cp % nch:
            nch -= 1
        cc = cp // nch

        @jax.checkpoint
        def ffn_chunk(carry, xc):
            g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xc, wg))
            u = jnp.einsum("ecd,edf->ecf", xc, wu)
            return carry, jnp.einsum("ecf,efd->ecd", g * u, wd)

        xch = xe.reshape(xe.shape[0], nch, cc, D).transpose(1, 0, 2, 3)
        _, ych = jax.lax.scan(ffn_chunk, 0, xch)
        ye = ych.transpose(1, 0, 2, 3).reshape(xe.shape[0], cp, D)

        ye = jax.lax.all_to_all(ye, mdl, split_axis=1, concat_axis=0, tiled=True)

        gathered = ye[se, jnp.minimum(ss, cap - 1)]
        gathered = jnp.where(keep[:, None], gathered, 0)
        y = jnp.zeros((T, D), xl.dtype).at[perm].set(gathered)
        y = (y * top_w[:, None].astype(xl.dtype)).reshape(Bl, S, D)

        fe = jnp.zeros(E, jnp.float32).at[top].add(1.0) / T
        pe = jnp.mean(gate, axis=0)
        aux = E * jnp.sum(fe * pe)
        aux = jax.lax.pmean(aux, dp + (mdl,))
        return y, aux

    f_dp = dspec if cfg.ep_fsdp else None
    y, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(dspec, None, None),
            P(None, None),
            P(mdl, None, f_dp),
            P(mdl, None, f_dp),
            P(mdl, f_dp, None),
        ),
        out_specs=(P(dspec, None, None), P()),
        check_vma=False,
    )(x, p["router"], p["we_gate"], p["we_up"], p["we_down"])

    if cfg.moe.shared_expert:
        y = y + swiglu(x, p["ws_gate"], p["ws_up"], p["ws_down"])
    return y, aux


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def _sublayer_train(cfg: LMConfig, pos: int, p, x, positions):
    """One decoder layer (training / prefill, full sequence)."""
    B, S, D = x.shape
    dh = cfg.head_dim
    local = pos in cfg.local_positions

    h = rms_norm(x, p["attn_norm"])
    q = jnp.einsum("bsd,dhe->bshe", h, p["wq"])
    k = jnp.einsum("bsd,dhe->bshe", h, p["wk"])
    v = jnp.einsum("bsd,dhe->bshe", h, p["wv"])
    if local or cfg.period == 1:
        # RoPE on local layers (and all layers of dense archs); llama4
        # global layers are NoPE (iRoPE)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    if local:
        attn = _chunked_local_attention(cfg, q, k, v)
    else:
        attn = _gqa_attention(cfg, q, k, v, causal_offset=0)
    x = x + jnp.einsum("bshe,hed->bsd", attn, p["wo"])

    h = rms_norm(x, p["ffn_norm"])
    if cfg.moe:
        ffn = _moe_ffn_ep if cfg.ep_mesh is not None else _moe_ffn
    else:
        ffn = _dense_ffn
    y, aux = ffn(cfg, p, h)
    return x + y, aux, (k, v)


def forward_train(cfg: LMConfig, params, tokens, labels):
    """Mean next-token loss over [B, S] tokens."""
    x = params["embed"][tokens].astype(cfg.act_dtype)

    (x, aux), _ = jax.lax.scan(
        functools.partial(_remat_group, cfg),
        (x, jnp.float32(0)),
        params["blocks"],
    )
    x = rms_norm(x, params["final_norm"])
    head = params.get("lm_head", params["embed"].T if cfg.tie_embeddings else None)
    loss = _chunked_xent(cfg, x[:, :-1], head, labels[:, 1:])
    return loss + 0.01 * aux / cfg.n_groups


def _chunked_xent(cfg: LMConfig, x, head, labels, chunk: int = 512):
    """Cross entropy without materializing [B, S, V] logits: unrolled loop
    over sequence chunks; each step holds one [B, chunk, V] tile (vocab
    additionally sharded over `model` under pjit)."""
    B, S, D = x.shape
    head = head.astype(cfg.act_dtype)
    cb = min(chunk, S)
    nc = -(-S // cb)
    total = jnp.float32(0)
    count = jnp.float32(0)
    for c in range(nc):
        lo = c * cb
        width = min(cb, S - lo)
        xc = jax.lax.dynamic_slice_in_dim(x, lo, width, axis=1)
        yc = jax.lax.dynamic_slice_in_dim(labels, lo, width, axis=1)
        logits = jnp.einsum("bsd,dv->bsv", xc, head).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yc[..., None], axis=-1)[..., 0]
        total = total + jnp.sum(logz - gold)
        count = count + jnp.float32(B * width)
    return total / count


def _remat_group(cfg: LMConfig, carry, block):
    """Scan body with activation checkpointing: only the group inputs are
    saved; everything inside the group recomputes in the backward pass.

    The saved carry (the residual stream) is *sequence-sharded* over the
    model axis (sequence parallelism, Korthikanti et al. 2022): without
    this, an 88-group 12k-wide model saves 88 x [B_loc, S, D] full-width
    residuals per device (~141 GB for mistral-large on the single-pod
    mesh).  Sharded, the per-group checkpoint is D*S/model — the boundary
    resharding lowers to reduce-scatter/all-gather pairs that replace the
    row-parallel all-reduces at the same wire bytes.
    """

    @functools.partial(jax.checkpoint, policy=jax.checkpoint_policies.nothing_saveable)
    def body(x, aux):
        positions = jnp.arange(x.shape[1])[None, :]
        for pos in range(cfg.period):
            x, a, _ = _sublayer_train(cfg, pos, block[f"pos{pos}"], x, positions)
            aux = aux + a
        return x, aux

    x, aux = carry
    x, aux = body(x, aux)
    x = _seq_shard_constraint(cfg, x)
    return (x, aux), None


def _seq_shard_constraint(cfg: LMConfig, x):
    """Pin [B, S, D] activations to (data, model-on-S) sharding when a
    production mesh is attached and S divides the model axis."""
    if cfg.ep_mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as _P

    dp = tuple(cfg.ep_dp_axes)
    dspec = dp if len(dp) > 1 else dp[0]
    import numpy as _np

    dpn = int(_np.prod([cfg.ep_mesh.shape[a] for a in dp]))
    if x.shape[0] % dpn or x.shape[1] % cfg.ep_mesh.shape["model"]:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(cfg.ep_mesh, _P(dspec, "model", None))
    )


# ---------------------------------------------------------------------------
# Prefill / decode with KV cache
# ---------------------------------------------------------------------------


def init_cache(cfg: LMConfig, batch: int, max_seq: int, dtype=None):
    dt = dtype or cfg.act_dtype
    G = cfg.n_groups
    dh = cfg.head_dim
    return {
        f"pos{p}": {
            "k": jnp.zeros((G, batch, max_seq, cfg.n_kv_heads, dh), dt),
            "v": jnp.zeros((G, batch, max_seq, cfg.n_kv_heads, dh), dt),
        }
        for p in range(cfg.period)
    }


def abstract_cache(cfg: LMConfig, batch: int, max_seq: int, dtype=None):
    return jax.eval_shape(lambda: init_cache(cfg, batch, max_seq, dtype))


def forward_prefill(cfg: LMConfig, params, tokens):
    """Full-sequence forward returning (last-token logits, cache)."""
    x = params["embed"][tokens].astype(cfg.act_dtype)
    B, S = tokens.shape
    positions = jnp.arange(S)[None, :]

    def group(x, block):
        kvs = {}
        for pos in range(cfg.period):
            x, _, (k, v) = _sublayer_train(cfg, pos, block[f"pos{pos}"], x, positions)
            kvs[f"pos{pos}"] = {"k": k, "v": v}
        return x, kvs

    x, cache = jax.lax.scan(group, x, params["blocks"])
    x = rms_norm(x, params["final_norm"])
    head = params.get("lm_head", params["embed"].T if cfg.tie_embeddings else None)
    logits = jnp.einsum("bd,dv->bv", x[:, -1], head.astype(cfg.act_dtype))
    return logits, cache


def _sublayer_decode(cfg: LMConfig, pos, p, x, cache_kv, t):
    """One layer, one new token.  x [B, D]; cache k/v [B, Smax, K, Dh];
    t: current position (scalar int32)."""
    B, D = x.shape
    dh = cfg.head_dim
    local = pos in cfg.local_positions

    h = rms_norm(x, p["attn_norm"])
    q = jnp.einsum("bd,dhe->bhe", h, p["wq"])[:, None]
    k = jnp.einsum("bd,dhe->bhe", h, p["wk"])[:, None]
    v = jnp.einsum("bd,dhe->bhe", h, p["wv"])[:, None]
    posn = jnp.full((1, 1), t, jnp.int32)
    if local or cfg.period == 1:
        q = apply_rope(q, posn, cfg.rope_theta)
        k = apply_rope(k, posn, cfg.rope_theta)

    ck = jax.lax.dynamic_update_slice(cache_kv["k"], k.astype(cache_kv["k"].dtype), (0, t, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache_kv["v"], v.astype(cache_kv["v"].dtype), (0, t, 0, 0))

    Smax = ck.shape[1]
    K = cfg.n_kv_heads
    rep = cfg.n_heads // K
    qg = q.reshape(B, K, rep, dh)
    logits = jnp.einsum("bkrd,btkd->bkrt", qg, ck).astype(jnp.float32)
    logits = logits * (dh ** -0.5)
    kpos = jnp.arange(Smax)[None, None, None, :]
    valid = kpos <= t
    if local:
        # chunked-local: only the current chunk attends
        chunk_start = (t // cfg.local_chunk) * cfg.local_chunk
        valid = valid & (kpos >= chunk_start)
    logits = jnp.where(valid, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
    attn = jnp.einsum("bkrt,btkd->bkrd", probs, cv)
    attn = attn.reshape(B, cfg.n_heads, dh)
    x = x + jnp.einsum("bhe,hed->bd", attn, p["wo"])

    h = rms_norm(x, p["ffn_norm"])
    if cfg.moe:
        y, _ = _moe_ffn(cfg, p, h[:, None, :])
        y = y[:, 0]
    else:
        y = swiglu(h, p["w_gate"], p["w_up"], p["w_down"])
    return x + y, {"k": ck, "v": cv}


def forward_decode(cfg: LMConfig, params, token, cache, t):
    """One decode step: token [B] int32, cache pytree, t scalar position.
    Returns (logits [B, V], new cache)."""
    x = params["embed"][token].astype(cfg.act_dtype)

    def group(x, scans):
        block, cache_g = scans
        new_cache = {}
        for pos in range(cfg.period):
            x, kv = _sublayer_decode(
                cfg, pos, block[f"pos{pos}"], x, cache_g[f"pos{pos}"], t
            )
            new_cache[f"pos{pos}"] = kv
        return x, new_cache

    x, new_cache = jax.lax.scan(group, x, (params["blocks"], cache))
    x = rms_norm(x, params["final_norm"])
    head = params.get("lm_head", params["embed"].T if cfg.tie_embeddings else None)
    logits = jnp.einsum("bd,dv->bv", x, head.astype(cfg.act_dtype))
    return logits, new_cache
