"""Sharding rules: every PartitionSpec in the repo is decided here.

Axis policy (see repro.launch.mesh): the last mesh axis is the tensor /
``model`` axis; everything before it is data parallelism (single-pod mesh
``(data, model)``, multi-pod ``(pod, data, model)`` where both leading axes
act as hierarchical DP).  All rules are *divisibility-guarded*: an axis is
used only when the array dimension divides the axis size, so the same rules
are valid on the (1, 1) host mesh, the 16x16 pod, and the 2x16x16 multi-pod
mesh without special cases (GSPMD would pad otherwise — we never rely on
padding for parameters or optimizer state, only activations may).

Rules:

* LM parameters — Megatron-style tensor parallelism over ``model``:
  attention head axes (wq/wk/wv/wo), the FFN hidden dim (w_gate/w_up column,
  w_down row), the MoE expert axis (we_*, matching the shard_map specs in
  repro.models.transformer._moe_ffn_ep), and the vocab dim of embed/lm_head.
  Routers stay replicated (shard_map EP requires it).
* ZeRO (``zero_spec_for``) — add the data axes on the largest
  still-unsharded divisible dimension; applied to optimizer moments always
  (ZeRO-1) and to parameters when the registry enables FSDP.
* KV caches — batch over data, KV-head over model.
* RecSys parameters — large embedding tables row-shard over ``model`` (the
  layout repro.kernels.embedding_bag expects); MLP towers replicate.
"""

from __future__ import annotations

import dataclasses
import math

import jax
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class MeshAxes:
    """Role assignment of mesh axes: ``dp`` (tuple, possibly hierarchical),
    ``mdl`` (the tensor-parallel axis), ``all_axes`` in mesh order."""

    dp: tuple
    mdl: str
    all_axes: tuple


def axes_for_mesh(mesh) -> MeshAxes:
    names = tuple(mesh.axis_names)
    if "model" in names:
        mdl = "model"
    else:
        mdl = names[-1]
    dp = tuple(a for a in names if a != mdl)
    if not dp:
        dp = (mdl,)  # degenerate 1-axis mesh: DP == model axis of size 1
    return MeshAxes(dp=dp, mdl=mdl, all_axes=names)


def dp_size(mesh, axes: MeshAxes) -> int:
    return int(math.prod(mesh.shape[a] for a in axes.dp))


def _norm(spec: P, ndim: int) -> list:
    """PartitionSpec entries padded with None to the array rank."""
    entries = list(spec) if spec is not None else []
    return entries + [None] * (ndim - len(entries))


def _axis_if(mesh, axis: str, dim: int) -> str | None:
    return axis if dim % mesh.shape[axis] == 0 else None


# ---------------------------------------------------------------------------
# ZeRO / FSDP extension
# ---------------------------------------------------------------------------


def zero_spec_for(spec: P, shape: tuple, axes: MeshAxes, dpn: int) -> P:
    """Extend ``spec`` with the data axes on the largest still-unsharded
    dimension divisible by the total DP degree.  Returns ``spec`` unchanged
    when nothing qualifies (dpn == 1, fully sharded, or no divisible dim)."""
    if dpn <= 1:
        return spec
    entries = _norm(spec, len(shape))
    used = {
        ax
        for entry in entries
        if entry is not None
        for ax in (entry if isinstance(entry, tuple) else (entry,))
    }
    if used & set(axes.dp):
        return spec  # a dp axis already shards some dim; adding it again
        # elsewhere would be an invalid duplicate-axis PartitionSpec
    best = -1
    for i, (entry, dim) in enumerate(zip(entries, shape)):
        if entry is not None:
            continue
        if dim % dpn != 0:
            continue
        if best < 0 or dim >= shape[best]:
            best = i  # ties resolve to the last (innermost) candidate
    if best < 0:
        return spec
    entries[best] = tuple(axes.dp) if len(axes.dp) > 1 else axes.dp[0]
    return P(*entries)


# ---------------------------------------------------------------------------
# LM specs
# ---------------------------------------------------------------------------

#: blocks/pos* leaf name -> index of the dimension (in the stacked
#: [n_groups, ...] layout) that shards over the model axis; -1 = replicated.
_LM_BLOCK_TP_DIM = {
    "attn_norm": -1,
    "ffn_norm": -1,
    "wq": 2,        # [G, d, H, dh]   heads
    "wk": 2,        # [G, d, K, dh]   kv heads
    "wv": 2,
    "wo": 1,        # [G, H, dh, d]   heads
    "w_gate": 2,    # [G, d, f]       hidden columns
    "w_up": 2,
    "w_down": 1,    # [G, f, d]       hidden rows
    "ws_gate": 2,   # shared expert: same layout as dense FFN
    "ws_up": 2,
    "ws_down": 1,
    "router": -1,   # replicated (shard_map EP contract)
    "we_gate": 1,   # [G, E, d, f]    expert axis (EP over `model`)
    "we_up": 1,
    "we_down": 1,   # [G, E, f, d]
}


def lm_param_specs(cfg, axes: MeshAxes, mesh, params_abs):
    """PartitionSpecs for repro.models.transformer parameter trees."""
    mdl = axes.mdl

    def block_spec(name: str, ab):
        tp_dim = _LM_BLOCK_TP_DIM.get(name, -1)
        entries = [None] * ab.ndim
        if tp_dim >= 0:
            entries[tp_dim] = _axis_if(mesh, mdl, ab.shape[tp_dim])
        return P(*entries)

    specs = {
        "embed": P(_axis_if(mesh, mdl, params_abs["embed"].shape[0]), None),
        "final_norm": P(),
        "blocks": {
            pos: {name: block_spec(name, ab) for name, ab in leaves.items()}
            for pos, leaves in params_abs["blocks"].items()
        },
    }
    if "lm_head" in params_abs:
        specs["lm_head"] = P(
            None, _axis_if(mesh, mdl, params_abs["lm_head"].shape[1])
        )
    return specs


def lm_batch_specs(axes: MeshAxes):
    dp = tuple(axes.dp) if len(axes.dp) > 1 else axes.dp[0]
    return {"tokens": P(dp, None), "labels": P(dp, None)}


def lm_cache_specs(cfg, axes: MeshAxes, batch: int, mesh):
    """Specs matching repro.models.transformer.abstract_cache:
    {pos*: {k, v}} with arrays [n_groups, B, S, n_kv_heads, head_dim]."""
    dpn = dp_size(mesh, axes)
    dp = (tuple(axes.dp) if len(axes.dp) > 1 else axes.dp[0]) if (
        batch % dpn == 0
    ) else None
    kv = _axis_if(mesh, axes.mdl, cfg.n_kv_heads)
    spec = P(None, dp, None, kv, None)
    return {f"pos{p}": {"k": spec, "v": spec} for p in range(cfg.period)}


# ---------------------------------------------------------------------------
# GNN / RecSys specs
# ---------------------------------------------------------------------------


def nequip_batch_specs(axes: MeshAxes, shard: bool = True):
    """Edge/node sharding over *all* axes (GNN batches have no tensor dim)."""
    if not shard:
        return {
            "node_feat": P(), "edge_index": P(), "edge_vec": P(),
            "graph_id": P(), "energy": P(),
        }
    alla = axes.all_axes if len(axes.all_axes) > 1 else axes.all_axes[0]
    return {
        "node_feat": P(alla, None),
        "edge_index": P(None, alla),
        "edge_vec": P(alla, None),
        "graph_id": P(alla),
        "energy": P(),
    }


def recsys_param_specs(params_abs, axes: MeshAxes, mesh, row_threshold: int = 1 << 16):
    """Row-shard large embedding tables over ``model``; replicate the rest.

    The threshold matches the registry's bf16 serving-copy rule: tables with
    >= 2^16 rows are the memory-dominant state and the ones the
    embedding_bag kernel gathers from.
    """

    def spec(ab):
        if ab.ndim == 2 and ab.shape[0] >= row_threshold:
            return P(_axis_if(mesh, axes.mdl, ab.shape[0]), None)
        return P()

    return jax.tree.map(spec, params_abs)


# ---------------------------------------------------------------------------
# Docs-axis sharding (document-retrieval index stack)
# ---------------------------------------------------------------------------

#: mesh axis name the retrieval index stack shards over
DOCS_AXIS = "docs"


def make_docs_mesh(n_shards: int):
    """1-D ``(docs,)`` mesh of ``n_shards`` devices for the sharded index
    stack.  On a CPU host, virtualize devices first with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before jax
    imports; tests/conftest.py and the CI sharded-smoke step do this)."""
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    avail = jax.device_count()
    if n_shards > avail:
        raise ValueError(
            f"n_shards={n_shards} exceeds available devices ({avail}); "
            "set --xla_force_host_platform_device_count"
        )
    # Auto axes: the sharded programs steer placement with
    # with_sharding_constraint, which refuses Explicit axes (the
    # jax.make_mesh default since JAX 0.7)
    return jax.make_mesh(
        (n_shards,), (DOCS_AXIS,), axis_types=(jax.sharding.AxisType.Auto,)
    )


def docs_mesh_size(mesh) -> int:
    return int(mesh.shape[DOCS_AXIS])


def doc_shard_bounds(d: int, n_shards: int) -> list[tuple[int, int]]:
    """Contiguous document ranges [dlo, dhi) per shard, balanced to within
    one document.  Every shard owns at least one document — build-time
    empty shards are disallowed (an *empty-answer* shard, where a pattern
    has no hits, is the degenerate case the merge handles)."""
    if n_shards > d:
        raise ValueError(
            f"n_shards={n_shards} > d={d}: every shard must own >= 1 document"
        )
    base, extra = divmod(d, n_shards)
    bounds = []
    lo = 0
    for s in range(n_shards):
        hi = lo + base + (1 if s < extra else 0)
        bounds.append((lo, hi))
        lo = hi
    return bounds


def docs_stacked_spec(ndim: int) -> P:
    """Spec for per-shard results stacked on a leading [S, ...] axis: shard
    the leading dim over ``docs``, replicate the rest.  Applied via
    ``jax.lax.with_sharding_constraint`` between the unrolled per-shard
    executors and the shard_map merge stage."""
    return P(DOCS_AXIS, *([None] * (ndim - 1)))


def docs_replicated_spec() -> P:
    """Placement of index pytree leaves and query batches: replicated over
    the docs mesh.  jax.jit rejects mixed single-device placements, so
    per-shard index leaves live replicated; true per-device residency of
    shard s's leaves on device s only is the multi-host follow-up
    (docs/SHARDING.md)."""
    return P()


def docs_index_shardings(mesh, pytree):
    """NamedShardings for device_put of a (per-shard or global) index
    pytree onto the docs mesh — every leaf replicated."""
    sh = jax.NamedSharding(mesh, docs_replicated_spec())
    return jax.tree.map(lambda _: sh, pytree)


# ---------------------------------------------------------------------------
# Optimizer state (ZeRO-1)
# ---------------------------------------------------------------------------


def opt_state_specs(param_specs, params_abs, axes: MeshAxes, dpn: int):
    """Moments: parameter sharding + data axes on the largest free dim
    (ZeRO-1); step counter replicated.  Matches
    repro.train.optimizer.abstract_opt_state's {m, v, step} layout."""
    mspecs = jax.tree.map(
        lambda spec, ab: zero_spec_for(spec, ab.shape, axes, dpn),
        param_specs,
        params_abs,
        is_leaf=lambda x: isinstance(x, P),
    )
    return {"m": mspecs, "v": mspecs, "step": P()}
