"""Distribution tests that need multiple (virtual) devices: run in a
subprocess with --xla_force_host_platform_device_count so the main pytest
process keeps its single-device JAX runtime."""

import os
import subprocess
import sys

import pytest

_SCRIPT_PARTITIONED_GNN = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
import jax, jax.numpy as jnp

from repro.models.nequip import (
    NequIPConfig, init_params, forward_train, build_partition,
    partitioned_train_step_fn,
)

cfg = NequIPConfig(d_feat_in=6, channels=4, n_layers=2, n_rbf=4)
key = jax.random.PRNGKey(0)
params = init_params(cfg, key)
rng = np.random.default_rng(0)
N, E, G = 32, 96, 2
node_feat = rng.standard_normal((N, 6)).astype(np.float32)
ei = rng.integers(0, N, (2, E)).astype(np.int32)
ev = (rng.standard_normal((E, 3)) * 2).astype(np.float32)
gid = np.sort(rng.integers(0, G, N)).astype(np.int32)
energy = rng.standard_normal(G).astype(np.float32)
batch_ref = dict(node_feat=jnp.asarray(node_feat), edge_index=jnp.asarray(ei),
                 edge_vec=jnp.asarray(ev), graph_id=jnp.asarray(gid),
                 energy=jnp.asarray(energy))
ref = float(forward_train(cfg, params, batch_ref, G))

mesh = jax.make_mesh((2, 2), ("data", "model"))
part = build_partition(node_feat, ei, ev, gid, ndev=4)
part["energy"] = energy
loss_fn = partitioned_train_step_fn(cfg, mesh, ("data", "model"), G)
with mesh:
    got = float(jax.jit(loss_fn)(params, {k: jnp.asarray(v) for k, v in part.items()}))
assert abs(got - ref) < 1e-3 * max(1.0, abs(ref)), (got, ref)

# gradients flow through the halo exchange
with mesh:
    g = jax.jit(jax.grad(loss_fn))(params, {k: jnp.asarray(v) for k, v in part.items()})
assert all(np.isfinite(np.asarray(l)).all() for l in jax.tree.leaves(g))
print("PARTITIONED_OK", got, ref)
"""

_SCRIPT_EP_MOE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses
import numpy as np
import jax, jax.numpy as jnp
from repro.models.transformer import LMConfig, MoEConfig, init_params, forward_train

cfg0 = LMConfig(name="t", n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
                d_ff=64, vocab=64, moe=MoEConfig(n_experts=4, capacity_factor=4.0),
                param_dtype=jnp.float32, act_dtype=jnp.float32)
key = jax.random.PRNGKey(0)
params = init_params(cfg0, key)
tokens = jax.random.randint(key, (4, 16), 0, 64)
ref = float(forward_train(cfg0, params, tokens, tokens))

mesh = jax.make_mesh((2, 2), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = dataclasses.replace(cfg0, ep_mesh=mesh, ep_dp_axes=("data",), ep_fsdp=False)
with mesh:
    got = float(jax.jit(lambda p, t: forward_train(cfg, p, t, t))(params, tokens))
# local-capacity dispatch may drop different tokens than global dispatch at
# tight capacity; with capacity_factor=E there are no drops at all
assert abs(got - ref) < 1e-4 * max(1.0, abs(ref)), (got, ref)
print("EP_OK", got, ref)
"""


def _run(script: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=420, env=env,
    )
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


def test_partitioned_gnn_matches_reference():
    out = _run(_SCRIPT_PARTITIONED_GNN)
    assert "PARTITIONED_OK" in out


def test_shard_map_moe_matches_local_dispatch():
    out = _run(_SCRIPT_EP_MOE)
    assert "EP_OK" in out
