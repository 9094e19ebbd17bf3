"""Backward-search microbenchmark: launch counts + planner-stage latency.

Compares the three execution paths of the planned CSA range search at
batch sizes {1, 16, 128}:

  legacy-dual-descent  csa_search_batch — vmapped per-query scan, two
                       independent wavelet descents per symbol step
                       (4 rank gathers per level)
  xla-pair-descent     csa_search_planned(use_kernel=False) — batch-first
                       scan, both SA-range boundaries on ONE descent
                       (2 rank gathers per level)
  pallas-kernel        csa_search_planned(use_kernel=True) — the fused
                       kernel: the whole batched search in ONE pallas_call
                       (interpret mode on this CPU container)

Beyond wall time, the bench *counts* the structural contract in each
variant's jaxpr: pallas_call launches per batch (1 on the kernel path,
0 elsewhere — down from the 2*m*levels rank calls a per-rank kernel would
issue) and gather equations (the pair descent halves the legacy count).
The planner stage (plan_queries: search + df + occ + dispatch) is timed on
both the kernel and fallback paths, since that is the serving-layer stage
the fusion targets.

A docs-mesh sweep (``--shards``, default {1, 2, 4, 8} where the host has
the devices) times the *sharded* planner program: per-shard CSA stacks,
one kernel launch per shard, psum-merged occ/df.  Every result row carries
a ``mesh_shape`` field and the per-launch resident wavelet-matrix bytes,
so the artifact shows the VMEM footprint dropping with the shard count —
the restoration mechanism for over-budget indexes.  The JSON is written to
``--out`` and mirrored at a repo-root ``BENCH_backward_search.json``.

    PYTHONPATH=src python -m benchmarks.backward_search_bench \
        [--out experiments/BENCH_backward_search.json] [--shards 1 2 4 8] \
        [--smoke]
"""

from __future__ import annotations

import argparse
import functools

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import (
    SCALE, bench_collections, emit, time_batched, write_json,
)
from repro.analysis.jaxpr import count_primitive
from repro.common import enable_compile_cache
from repro.core.csa import build_csa, csa_search_batch, csa_search_planned
from repro.core.sada import build_sada
from repro.core.suffix import build_suffix_data, subcollection
from repro.data.collections import pad_patterns, random_substring_patterns
from repro.kernels import ops
from repro.serve.planner import plan_queries

BATCH_SIZES = (1, 16, 128)
SHARD_COUNTS = (1, 2, 4, 8)


def _workload(coll, B: int, rng):
    pats = random_substring_patterns(coll, max(2 * B, 16), 4, 24)
    idx = rng.integers(0, len(pats), B)
    arr, lens = pad_patterns([pats[i] for i in idx])
    return jnp.asarray(arr), jnp.asarray(lens)


def _resident_bytes(csa):
    return ops.backward_search_resident_bytes(csa.wm.words, csa.wm.ones_prefix)


def _sharded_plan_variants(coll, n_shards: int):
    """Jitted sharded planner programs (kernel + fallback) over per-shard
    CSA/Sada stacks, plus the max per-launch resident bytes.

    Only the structures ``plan_queries`` touches are built — the docs-mesh
    plan program ignores the ILCP/PDL slots of each shard tuple, so the
    sweep does not pay for listing/top-k index construction."""
    from repro.dist.sharding import doc_shard_bounds, make_docs_mesh
    from repro.serve.sharded import _sharded_plan_program

    mesh = make_docs_mesh(n_shards)
    bounds = doc_shard_bounds(coll.d, n_shards)
    shard_idx, resident = [], 0
    for dlo, dhi in bounds:
        sub = subcollection(coll, dlo, dhi)
        data = build_suffix_data(sub)
        csa = build_csa(data)
        sada = build_sada(data, "sparse")
        shard_idx.append((csa, None, None, None, sada, None))
        resident = max(resident, _resident_bytes(csa))
    shard_idx = tuple(shard_idx)
    bases = tuple(b[0] for b in bounds)

    def fn(use_kernel, p, l):
        return _sharded_plan_program(
            mesh, bases, use_kernel, shard_idx, p, l,
            jnp.float32(4.0), jnp.int32(-1),
        )

    return {
        f"plan-sharded{n_shards}-fallback": jax.jit(functools.partial(fn, False)),
        f"plan-sharded{n_shards}-kernel": jax.jit(functools.partial(fn, True)),
    }, resident


def run(collections=("version-p001", "dna-p03"), batch_sizes=BATCH_SIZES,
        iters: int = 5, out: str | None = None, shard_counts=SHARD_COUNTS):
    rows, results = [], []
    feasible = [s for s in shard_counts if 1 < s <= jax.device_count()]
    skipped = [s for s in shard_counts if s > jax.device_count()]
    if skipped:
        print(f"shard sweep: skipping {skipped} "
              f"(only {jax.device_count()} devices)")
    for name in collections:
        coll = bench_collections()[name]
        data = build_suffix_data(coll)
        csa = build_csa(data)
        sada = build_sada(data, "sparse")
        rng = np.random.default_rng(0)

        search_variants = {
            "legacy-dual-descent": jax.jit(
                lambda p, l, csa=csa: csa_search_batch(csa, p, l)
            ),
            "xla-pair-descent": jax.jit(
                lambda p, l, csa=csa: csa_search_planned(csa, p, l, use_kernel=False)
            ),
            "pallas-kernel": jax.jit(
                lambda p, l, csa=csa: csa_search_planned(csa, p, l, use_kernel=True)
            ),
        }
        plan_variants = {
            "plan-fallback": jax.jit(
                lambda p, l, csa=csa, sada=sada: plan_queries(
                    csa, sada, p, l, 4.0, -1, use_kernel=False)
            ),
            "plan-kernel": jax.jit(
                lambda p, l, csa=csa, sada=sada: plan_queries(
                    csa, sada, p, l, 4.0, -1, use_kernel=True)
            ),
        }
        global_resident = _resident_bytes(csa)
        # variant -> (fn, mesh_shape, max resident bytes per kernel launch)
        meta = {v: (fn, [1], global_resident)
                for v, fn in {**search_variants, **plan_variants}.items()}
        # sharded planner sweep on the first collection only: per-shard
        # index build cost is real, and one collection shows the scaling
        if name == collections[0]:
            for n_shards in feasible:
                sharded, resident = _sharded_plan_variants(coll, n_shards)
                meta.update({v: (fn, [n_shards], resident)
                             for v, fn in sharded.items()})

        for B in batch_sizes:
            pats, lens = _workload(coll, B, rng)
            ref_lo, ref_hi = search_variants["legacy-dual-descent"](pats, lens)
            for variant, (fn, mesh_shape, resident) in meta.items():
                closed = jax.make_jaxpr(fn)(pats, lens)
                launches = count_primitive(closed.jaxpr, "pallas_call")
                gathers = count_primitive(closed.jaxpr, "gather")
                med, got = time_batched(fn, pats, lens, iters=iters)
                # every variant must agree on the integers
                if variant in search_variants:
                    lo, hi = got
                    assert np.array_equal(np.asarray(lo), np.asarray(ref_lo))
                    assert np.array_equal(np.asarray(hi), np.asarray(ref_hi))
                elif variant in plan_variants:
                    assert np.array_equal(np.asarray(got.lo), np.asarray(ref_lo))
                else:
                    # sharded plan: shard-local occ sums psum to global occ
                    occ = np.asarray(got[3])
                    assert np.array_equal(
                        occ, np.asarray(ref_hi) - np.asarray(ref_lo)
                    )
                ms = med * 1e3
                rows.append([name, variant, B, mesh_shape[0],
                             round(ms, 3), launches, gathers])
                results.append(
                    {
                        "collection": name,
                        "variant": variant,
                        "batch": B,
                        "mesh_shape": mesh_shape,
                        "scale": SCALE,
                        "median_ms": round(ms, 4),
                        "pallas_launches_per_batch": launches,
                        "gather_eqns": gathers,
                        "max_resident_bytes_per_launch": int(resident),
                        "vmem_budget_bytes": int(ops.BACKWARD_SEARCH_VMEM_BUDGET),
                    }
                )
    emit(rows, ["collection", "variant", "batch", "shards", "median_ms",
                "pallas_launches", "gather_eqns"])
    payload = {
        "results": results,
        "device_count": jax.device_count(),
        "failures": [],
    }
    write_json(out, payload, "BENCH_backward_search.json")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="experiments/BENCH_backward_search.json")
    ap.add_argument("--batches", type=int, nargs="*", default=list(BATCH_SIZES))
    ap.add_argument("--shards", type=int, nargs="*", default=list(SHARD_COUNTS),
                    help="docs-mesh shard counts for the sharded planner "
                         "sweep (1 = unsharded; counts past the device "
                         "count are skipped)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: one collection, tiny batches, 2 iters")
    args = ap.parse_args()
    enable_compile_cache()
    if args.smoke:
        run(collections=("version-p001",), batch_sizes=(1, 16), iters=2,
            out=args.out, shard_counts=tuple(args.shards))
    else:
        run(batch_sizes=tuple(args.batches), out=args.out,
            shard_counts=tuple(args.shards))


if __name__ == "__main__":
    main()
