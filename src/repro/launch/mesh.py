"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches JAX device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any JAX
initialization, and nothing here may run earlier.

Topology: TPU v5e pods of 256 chips as a (data=16, model=16) torus slice;
the multi-pod mesh adds a leading pod axis (pod=2) for 512 chips, used by
data parallelism's hierarchical gradient reduction (reduce-scatter inside
the pod over ICI, cross-pod all-reduce over DCI, all-gather inside).
"""

from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    # Auto axes: the models steer placement with with_sharding_constraint,
    # which refuses Explicit axes (the jax.make_mesh default since JAX 0.7)
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for smoke tests / examples on this container."""
    return _auto_mesh((1, 1), ("data", "model"))
