"""Common utilities: pytree dataclasses, dtype policy, small helpers.

Every index structure in ``repro`` is an immutable dataclass registered as a
JAX pytree.  Array fields are pytree leaves (so structures can be passed
through ``jit``/``vmap`` unchanged); integer metadata that must be *static*
(used in shapes, loop bounds, branch decisions at trace time) is declared in
``meta`` and becomes part of the pytree treedef, i.e. a hashable aux value.
"""

from __future__ import annotations

import dataclasses
import os
import pathlib
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

#: the persistent compile cache's home when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset: one fixed, git-ignored directory of the checkout (the path is part
#: of the cache key, so a directory that moved would never hit)
COMPILE_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    For entry points only (never called at import).  A set
    ``JAX_COMPILATION_CACHE_DIR`` is where the cache goes, and no other
    directory is set; otherwise ``COMPILE_CACHE_DIR``."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(COMPILE_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path

# ---------------------------------------------------------------------------
# dtype policy
# ---------------------------------------------------------------------------

#: Default integer dtype for index structures.  All supported collection
#: sizes fit in int32 (n < 2^31); construction paths that could overflow use
#: int64 transiently on the host.
IDX = jnp.int32

#: Word width for plain bitvectors.  32-bit words keep popcount cheap on the
#: VPU and keep gathers aligned.
WORD_BITS = 32


def pytree_dataclass(cls=None, *, meta: Sequence[str] = ()):
    """Register a frozen dataclass as a JAX pytree.

    ``meta`` fields are static (hashable, part of the treedef); all other
    fields are array leaves.
    """

    def wrap(c):
        c = dataclasses.dataclass(frozen=True)(c)
        field_names = [f.name for f in dataclasses.fields(c)]
        data_fields = [f for f in field_names if f not in meta]
        jax.tree_util.register_dataclass(
            c, data_fields=data_fields, meta_fields=list(meta)
        )
        return c

    return wrap(cls) if cls is not None else wrap


def replace(obj, **kwargs):
    """dataclasses.replace that works through the pytree registration."""
    return dataclasses.replace(obj, **kwargs)


# ---------------------------------------------------------------------------
# Small math helpers (host-side, used at build time)
# ---------------------------------------------------------------------------

def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def ceil_log2(x: int) -> int:
    """ceil(lg x) for x >= 1; 0 for x <= 1."""
    if x <= 1:
        return 0
    return int(x - 1).bit_length()


def floor_log2(x: int) -> int:
    if x < 1:
        raise ValueError("floor_log2 requires x >= 1")
    return int(x).bit_length() - 1


def round_up(x: int, m: int) -> int:
    return ceil_div(x, m) * m


def delta_code_len(v: int) -> int:
    """Length in bits of the Elias delta code of v >= 1.

    Used only for *modeled* compressed-size accounting (the paper's space
    axis); the working representation is word-aligned.
    """
    if v < 1:
        raise ValueError("delta codes encode positive integers")
    n = floor_log2(v)          # v = 2^n + rest
    nn = floor_log2(n + 1)
    return 2 * nn + 1 + n


def gamma_code_len(v: int) -> int:
    if v < 1:
        raise ValueError("gamma codes encode positive integers")
    return 2 * floor_log2(v) + 1


def elias_fano_bits(m: int, n: int) -> int:
    """Modeled size in bits of an Elias-Fano / sparse bitmap with m ones out
    of n positions (Okanohara & Sadakane 2007): m*ceil(lg(n/m)) + 2m."""
    if m == 0:
        return 0
    low = max(0, ceil_log2(max(1, n // m)))
    return m * low + 2 * m


# ---------------------------------------------------------------------------
# Array helpers
# ---------------------------------------------------------------------------

def as_i32(x) -> jnp.ndarray:
    return jnp.asarray(x, dtype=IDX)


def np_as_i32(x) -> np.ndarray:
    return np.asarray(x, dtype=np.int32)


def popcount(x: jnp.ndarray) -> jnp.ndarray:
    """Population count of each element (works on any integer dtype)."""
    return jax.lax.population_count(x)


def device_nbytes(tree: Any) -> int:
    """Total bytes of all array leaves in a pytree (the *working set*)."""
    leaves = jax.tree_util.tree_leaves(tree)
    total = 0
    for leaf in leaves:
        if hasattr(leaf, "nbytes"):
            total += int(leaf.nbytes)
        elif isinstance(leaf, (int, float, bool)):
            total += 8
    return total


def tree_map_with_doc(fn: Callable, tree: Any) -> Any:
    return jax.tree_util.tree_map(fn, tree)


def bits_per_char(bits: float, n: int) -> float:
    """Space accounting in the paper's unit (bits per collection symbol)."""
    return bits / max(1, n)
