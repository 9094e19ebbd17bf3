"""Lookup tables the scalar-loop Pallas kernels keep in VMEM.

Mosaic (the TPU kernel compiler) has no vector gather from VMEM: a read
at a data-dependent address must be a dynamic *row* load.  So the serving
kernels store every lookup table as an int32 ``(rows, 128)`` array (flat
element ``i`` at row ``i >> 7``, lane ``i & 127``), run each query as a
scalar loop, and read one element as a row load plus a lane select
(``load_row`` + ``pick``).  Rows are padded to a multiple of 8, the int32
sublane tile.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
SUBLANES = 8


def rows_shape(size: int) -> tuple[int, int]:
    """(rows, 128) shape of a flat table of ``size`` elements, rows padded
    to a multiple of 8."""
    rows = -(-max(size, 1) // LANES)
    return (-(-rows // SUBLANES) * SUBLANES, LANES)


def as_rows(flat):
    """Flat int32/uint32 table -> zero-padded int32 ``(rows, 128)`` table."""
    flat = jnp.ravel(flat)
    if flat.dtype != jnp.int32:
        flat = jax.lax.bitcast_convert_type(flat, jnp.int32)
    rows, lanes = rows_shape(flat.shape[0])
    return jnp.pad(flat, (0, rows * lanes - flat.shape[0])).reshape(rows, lanes)


def load_row(ref, idx):
    """The (1, 128) row of ``ref`` holding flat element ``idx``, and the
    lane mask that selects the element in it."""
    row = ref[pl.ds(idx >> 7, 1), :]
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return row, lane == (idx & (LANES - 1))


def pick(vec, hit):
    """The scalar of ``vec`` at the single lane where ``hit`` holds."""
    return jnp.sum(jnp.where(hit, vec, 0))


def lookup(ref, idx):
    """Flat element ``idx`` of a ``(rows, 128)`` VMEM table."""
    row, hit = load_row(ref, idx)
    return pick(row, hit)
