"""Jaxpr walking primitives for the static contract auditor.

The serving stack's performance contracts (one ``pallas_call`` per planned
batch, bounded gather counts on the XLA fallback, no host callbacks, no
silent 64-bit widening) are all visible in the jaxpr of a traced endpoint
— *before* anything runs.  This module is the walker those audits share.

``jax.core.subjaxprs`` only yields the jaxprs it can see in an eqn's
params and does not descend recursively, so a counter built directly on it
misses primitives nested two levels deep (a ``pallas_call`` inside a
``pjit`` inside a ``scan``, or the branches of a ``cond`` inside a
``custom_vjp`` residual).  ``iter_eqns`` here does its own recursive
descent over every ``Jaxpr``/``ClosedJaxpr`` reachable through eqn params
— including params that hold them inside tuples, lists, or dicts (``cond``
branches, ``custom_vjp`` fun/fwd jaxprs, ``pjit``'s ``jaxpr`` param) — so
every count is a whole-program count.
"""

from __future__ import annotations

import math

import numpy as np
from jax.extend import core as jex_core

#: primitives that re-enter the host mid-program; forbidden in any serving
#: jaxpr (a host round-trip inside a batched endpoint defeats the entire
#: on-device engine and is invisible to wall-clock tests at small scale)
HOST_CALLBACK_PRIMITIVES = (
    "pure_callback",
    "io_callback",
    "debug_callback",
    "host_callback_call",
)

#: dtypes that indicate silent widening downstream of the int32/float32
#: serving ABI (x64 mode leaking in, or a Python float folded as f64)
WIDE_DTYPES = ("int64", "uint64", "float64", "complex128")


def _as_jaxpr(obj):
    """Accept ``Jaxpr``, ``ClosedJaxpr``, or anything with ``.jaxpr``."""
    while hasattr(obj, "jaxpr"):
        obj = obj.jaxpr
    return obj


def _jaxprs_in(value):
    """Yield every jaxpr held (possibly nested in containers) in a param
    value — ``cond`` stores a tuple of ClosedJaxprs, ``pjit`` a single
    ClosedJaxpr, pallas a raw Jaxpr."""
    if isinstance(value, (jex_core.Jaxpr, jex_core.ClosedJaxpr)):
        yield _as_jaxpr(value)
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _jaxprs_in(v)
    elif isinstance(value, dict):
        for v in value.values():
            yield from _jaxprs_in(v)


def iter_eqns(jaxpr):
    """Every eqn in ``jaxpr`` and in every jaxpr nested in eqn params, at
    any depth (pjit / scan / while / cond / custom_vjp / pallas_call)."""
    jaxpr = _as_jaxpr(jaxpr)
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _jaxprs_in(eqn.params):
            yield from iter_eqns(sub)


def count_primitive(jaxpr, name: str) -> int:
    """Whole-program occurrence count of a primitive by name: nested
    jaxprs are descended at any depth and every ``cond`` branch counts,
    except that a per-platform ``cond`` (``lax.platform_dependent``, whose
    eqn carries ``branches_platforms``) counts its costliest branch — one
    platform's branch is lowered.  The kernel wrappers stage each Pallas
    launch that way (an interpreted CPU branch and a compiled TPU branch):
    one launch, not two."""
    jaxpr = _as_jaxpr(jaxpr)
    total = 0
    for eqn in jaxpr.eqns:
        total += int(eqn.primitive.name == name)
        subs = [count_primitive(sub, name) for sub in _jaxprs_in(eqn.params)]
        if eqn.params.get("branches_platforms") is not None:
            total += max(subs, default=0)
        else:
            total += sum(subs)
    return total


def find_primitives(jaxpr, names) -> list:
    """All eqns (any depth) whose primitive name is in ``names``."""
    names = set(names)
    return [eqn for eqn in iter_eqns(jaxpr) if eqn.primitive.name in names]


def find_host_callbacks(jaxpr) -> list:
    return find_primitives(jaxpr, HOST_CALLBACK_PRIMITIVES)


def gather_count(jaxpr) -> int:
    """Static ``gather`` eqn count (loop bodies count once — this is a
    program-structure metric, not a per-element op count)."""
    return count_primitive(jaxpr, "gather")


#: cross-device communication primitives.  The sharded serving programs
#: allowlist ``psum`` / ``all_gather`` in their merge stages; anything else
#: (or any collective in a single-device program) is a contract violation —
#: an accidental ``all_to_all`` or ``ppermute`` in a merge is a silent
#: bandwidth regression no correctness test notices.
COLLECTIVE_PRIMITIVES = (
    "psum",
    "pmax",
    "pmin",
    "all_gather",
    "all_to_all",
    "ppermute",
    "pshuffle",
    "reduce_scatter",
    "pbroadcast",
    "pgather",
)


def collective_eqns(jaxpr) -> list:
    """All cross-device collective eqns at any depth (shard_map bodies
    included — ``iter_eqns`` descends through the shard_map eqn's jaxpr
    param)."""
    return find_primitives(jaxpr, COLLECTIVE_PRIMITIVES)


def wide_dtype_eqns(jaxpr) -> list:
    """(eqn, dtype) for every eqn producing a 64-bit output.

    The serving ABI is int32 indexes and float32 scores end to end; any
    f64/i64 aval in a serving jaxpr is silent widening (x64 leak, a
    ``np.float64`` scalar folded into a traced expression, or an unpinned
    host-side accumulator crossing into the program)."""
    out = []
    for eqn in iter_eqns(jaxpr):
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            dtype = getattr(aval, "dtype", None)
            if dtype is not None and str(dtype) in WIDE_DTYPES:
                out.append((eqn, str(dtype)))
                break
    return out


# ---------------------------------------------------------------------------
# pallas_call inspection
# ---------------------------------------------------------------------------


def pallas_eqns(jaxpr) -> list:
    return find_primitives(jaxpr, ("pallas_call",))


def pallas_scratch_bytes(eqn) -> int:
    """Bytes of every ``scratch_shapes`` operand of one ``pallas_call``
    eqn.  ``grid_mapping.block_mappings`` covers only in/out operands, so
    scratch is invisible to a block-shape walk — but the kernel jaxpr's
    invars carry the scratch refs as its trailing parameters, and their
    MemRef avals keep the allocated shape/dtype.  ``num_scratch_operands``
    on the grid mapping says how many of the tail to take."""
    gm = eqn.params.get("grid_mapping")
    kernel = eqn.params.get("jaxpr")
    n_scratch = getattr(gm, "num_scratch_operands", 0) if gm else 0
    if not n_scratch or kernel is None:
        return 0
    total = 0
    for var in _as_jaxpr(kernel).invars[-n_scratch:]:
        aval = var.aval
        total += (
            int(math.prod(aval.shape)) * np.dtype(aval.dtype).itemsize
        )
    return total


def pallas_block_bytes(eqn) -> int:
    """Static VMEM estimate for one ``pallas_call`` eqn: the bytes of every
    operand/result *block* (the per-grid-step resident set), read from the
    eqn's ``grid_mapping`` block shapes, PLUS the kernel's scratch
    allocations (``pallas_scratch_bytes`` — the fused listing kernel's
    interval stacks and distinct-document bitmap live there, and leaving
    them out would undercount its grid step by the whole working set).

    This is the lowering-time counterpart of the runtime budget checks in
    ``repro.kernels.ops``: if this estimate exceeds the relevant budget the
    kernel was launched on an index the wrapper should have routed to the
    XLA fallback."""
    gm = eqn.params.get("grid_mapping")
    if gm is None:
        return 0
    total = 0
    for bm in gm.block_mappings:
        shape = [d for d in bm.block_shape if isinstance(d, (int, np.integer))]
        sds = getattr(bm, "array_shape_dtype", None)
        itemsize = np.dtype(sds.dtype).itemsize if sds is not None else 4
        total += int(math.prod(shape)) * itemsize
    return total + pallas_scratch_bytes(eqn)
