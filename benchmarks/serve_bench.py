"""End-to-end batched serving benchmark: QPS and latency percentiles.

Measures the planner stage plus the three planned endpoints (listing,
top-k, tf-idf) of ``RetrievalService`` at batch sizes {1, 16, 128} — each
batch is ONE compiled program per shape bucket, so after the first (warmup)
call per bucket the loop below is pure execution.  The ``plan`` endpoint
isolates the stage the fused backward-search kernel targets; it is timed
on whatever search path the service was built with (kernel on TPU, XLA
pair descent elsewhere — see benchmarks.backward_search_bench for the
per-path comparison).  Emits the usual CSV rows plus a dry-run-shaped JSON
({"results": [...], "failures": []}) at experiments/BENCH_serve.json so
the perf trajectory can track serving throughput next to the roofline
numbers.

A second section exercises the *resilient runtime* (``repro.serve.runtime``)
under deterministic fault injection: a 512-query workload is pushed through
``ServeRuntime`` while executor failures, hangs, and compile errors fire at
a seeded 10% rate, and the run must answer 100% of valid requests (degraded
answers flagged) with no deadline missed by more than one batch interval.
The JSON gains a ``"resilience"`` block with ``degraded_fraction`` and
``deadline_miss_rate``.

A third section sweeps the docs-mesh sharded service over shard counts
(``--shards``, default {1, 2, 4, 8} on a virtualized host mesh): each
result row carries a ``mesh_shape`` field, so the artifact records the
per-shard-count serving cost next to the single-device numbers.  The JSON
is written both to ``--out`` and to a repo-root ``BENCH_serve.json`` so
the perf trajectory is visible without digging into experiments/.

    PYTHONPATH=src python -m benchmarks.serve_bench \
        [--out experiments/BENCH_serve.json] \
        [--shards 1 2 4 8] \
        [--inject executor_fail,slow_pdl,compile_error]
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np
import jax

from benchmarks.common import SCALE, bench_collections, emit, write_json
from repro.analysis.jaxpr import count_primitive
from repro.common import enable_compile_cache
from repro.data.collections import random_substring_patterns
from repro.kernels import ops
from repro.serve import faults
from repro.serve.retrieval import RetrievalService
from repro.serve.runtime import RuntimeConfig, ServeRuntime

BATCH_SIZES = (1, 16, 128)
SHARD_COUNTS = (1, 2, 4, 8)
ITERS = 20
RESILIENCE_QUERIES = 512
DEFAULT_INJECT = "executor_fail,slow_pdl,compile_error"
#: fixed batch sizes for the kernel-vs-XLA listing comparison — NOT scaled
#: down in smoke runs, so the committed mirror's comparison rows stay
#: directly diffable across CI configurations
LIST_COMPARE_BATCHES = (16, 128)


def _build_service(coll, n_shards: int, **kw):
    """The service under test: plain at 1 shard, docs-mesh sharded above.

    Returns (service, mesh_shape) — ``mesh_shape`` goes verbatim into the
    result rows so the artifact distinguishes sweep points."""
    if n_shards <= 1:
        return RetrievalService.build(coll, **kw), [1]
    from repro.dist.sharding import make_docs_mesh

    mesh = make_docs_mesh(n_shards)
    return RetrievalService.build(coll, mesh=mesh, **kw), [n_shards]


def _timed(fn, iters: int = ITERS, warmup: int = 1):
    # warmup: compiles the bucket's program, so the timed loop below is
    # pure execution
    for _ in range(warmup):
        fn()
    lat = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        lat.append(time.perf_counter() - t0)
    ms = np.asarray(lat) * 1e3
    return float(np.percentile(ms, 50)), float(np.percentile(ms, 99)), float(ms.mean())


def run_resilience(collection: str = "version-p001",
                   inject: str = DEFAULT_INJECT, rate: float = 0.1,
                   n_queries: int = RESILIENCE_QUERIES, batch: int = 8,
                   deadline_s: float = 0.5, seed: int = 0,
                   n_shards: int = 1) -> dict:
    """Push ``n_queries`` through ServeRuntime with faults firing at
    ``rate`` and report the resilience contract's metrics.  With
    ``n_shards > 1`` the runtime fronts the docs-mesh sharded service —
    the degradation ladder (retry, floor, host reference merge) must hold
    there too."""
    coll = bench_collections()[collection]
    # the Brute-L window stays pinned at 512, the window of the recorded
    # resilience runs, so that their numbers stay comparable
    svc, mesh_shape = _build_service(coll, n_shards, block_size=32, beta=8.0,
                                     brute_window=512)
    workload = random_substring_patterns(coll, max(n_queries, 64), 6, 64)
    rng = np.random.default_rng(seed)
    rt = ServeRuntime(svc, RuntimeConfig(max_batch=batch,
                                         default_deadline_s=deadline_s))
    kinds = ("count", "list", "topk")
    rt.warmup(kinds=kinds, batch_sizes=(batch,))
    # a realistic warm wave per kind: compiles the workload's pattern-length
    # buckets and seeds the steady-state EMA, so the measured run sees no
    # in-flight compiles
    for kind in kinds:
        for _ in range(2):
            rt.serve([(kind, workload[int(i)])
                      for i in rng.integers(0, len(workload), size=batch)],
                     deadline_s=1e9)
    specs = faults.parse_fault_specs(inject, rate=rate, seed=seed)
    # workload-only baselines: warmup traffic above must not dilute the
    # resilience metrics
    m = rt.metrics
    base_submitted, base_answered = m.submitted, m.answered
    base_degraded, base_misses = m.degraded, m.deadline_misses
    served = 0
    batch_lat = []
    with faults.inject(*specs) as inj:
        while served < n_queries:
            # one kind per submission wave, so batches cut at the warmed
            # power-of-two bucket instead of fragmenting across kinds
            kind = kinds[(served // batch) % len(kinds)]
            take = min(batch, n_queries - served)
            t0 = time.perf_counter()
            for i in rng.integers(0, len(workload), size=take):
                rt.submit(kind, workload[int(i)])
                served += 1
            rt.run_until_idle()
            batch_lat.append(time.perf_counter() - t0)
    answered = m.answered - base_answered
    submitted = m.submitted - base_submitted
    interval_s = float(np.percentile(np.asarray(batch_lat), 99))
    res = {
        "collection": collection,
        "mesh_shape": mesh_shape,
        "inject": inject,
        "fault_rate": rate,
        "faults_fired": len(inj.fired),
        "queries": n_queries,
        "answered": answered,
        "answered_fraction": round(answered / submitted, 4),
        "degraded_fraction": round((m.degraded - base_degraded) / answered, 4),
        "deadline_miss_rate": round(
            (m.deadline_misses - base_misses) / answered, 4),
        "max_overrun_s": round(m.max_overrun_s, 4),
        "batch_interval_s": round(interval_s, 4),
        "overrun_within_one_interval": bool(m.max_overrun_s <= interval_s),
        "retries": m.retries,
        "breaker_trips": m.breaker_trips,
        "degrade_reasons": dict(m.degrade_reasons),
        "compile_s": m.as_dict()["compile_s"],
        "steady_ema_s": m.as_dict()["steady_ema_s"],
    }
    print("resilience:", json.dumps(res, indent=1))
    assert res["answered_fraction"] == 1.0, "runtime dropped valid requests"
    assert res["overrun_within_one_interval"], (
        f"deadline missed by {m.max_overrun_s:.3f}s > one batch interval "
        f"{interval_s:.3f}s"
    )
    return res


def _bench_endpoints(svc, name, mesh_shape, workload, batch_sizes,
                     k, max_df, max_buf, iters, rows, results):
    rng = np.random.default_rng(0)
    for B in batch_sizes:
        idx = rng.integers(0, len(workload), size=(iters + 1, B))
        batches = [[workload[i] for i in row] for row in idx]
        it = iter(range(10_000))

        def batch(batches=batches, it=it):
            return batches[next(it) % len(batches)]

        def pairs(b):
            return [b[i : i + 2] for i in range(0, len(b), 2)] or [b[:1]]

        endpoints = {
            "plan": lambda svc=svc, batch=batch: svc.plan(batch()),
            "list": lambda svc=svc, batch=batch: svc.list_docs(
                batch(), max_df=max_df, max_buf=max_buf),
            "topk": lambda svc=svc, batch=batch: svc.topk(batch(), k=k, max_buf=max_buf),
            "tfidf": lambda svc=svc, batch=batch, pairs=pairs: svc.tfidf(
                pairs(batch()), k=k, max_buf=max_buf),
        }
        for ep, fn in endpoints.items():
            p50, p99, mean = _timed(fn, iters=iters, warmup=iters + 1)
            nq = B if ep != "tfidf" else max(1, B // 2)
            qps = nq / (mean / 1e3)
            rows.append(
                [name, ep, B, mesh_shape[0],
                 round(p50, 2), round(p99, 2), round(qps, 0)]
            )
            results.append(
                {
                    "collection": name,
                    "endpoint": ep,
                    "batch": B,
                    "mesh_shape": mesh_shape,
                    "scale": SCALE,
                    "list_kernel":
                        "on" if getattr(svc, "use_list_kernel", False)
                        else "off",
                    "p50_ms": round(p50, 3),
                    "p99_ms": round(p99, 3),
                    "qps": round(qps, 1),
                    "compiles": dict(svc.compile_counts),
                }
            )


def run_list_kernel_comparison(collection: str, max_df: int = 128,
                               max_buf: int = 1024, iters: int = ITERS,
                               batches=LIST_COMPARE_BATCHES) -> tuple:
    """Kernel-vs-XLA listing rows at fixed batch sizes.

    The auto planner routes most patterns to Brute/PDL, so the default
    ``list`` rows barely exercise the ILCP executor — the honest kernel
    measurement also forces the ILCP engine (endpoint label
    ``list_ilcp``).  Every row carries the whole-program launch count and
    the per-launch resident + scratch VMEM bytes, so the artifact records
    the kernel's cost model next to its wall clock."""
    coll = bench_collections()[collection]
    workload = random_substring_patterns(coll, 1500, 6, 256)
    rows, results = [], []
    if not workload:
        return rows, results
    rng = np.random.default_rng(0)
    for mode, use_k in (("off", False), ("on", True)):
        svc = RetrievalService.build(
            coll, block_size=32, beta=8.0, use_list_kernel=use_k,
        )
        ilcp = svc.ilcp
        resident = ops.ilcp_list_resident_bytes(
            ilcp.vilcp, ilcp.rmq.table, ilcp.run_starts, svc.da
        )
        for B in batches:
            launches = count_primitive(
                svc.trace_endpoint("list", B=B, max_df=max_df).jaxpr,
                "pallas_call",
            )
            scratch = ops.ilcp_list_scratch_bytes(ilcp.d)
            idx = rng.integers(0, len(workload), size=(iters + 1, B))
            batches_q = [[workload[i] for i in row] for row in idx]
            it = iter(range(10_000))

            def batch(batches_q=batches_q, it=it):
                return batches_q[next(it) % len(batches_q)]

            for ep, eng in (("list", "auto"), ("list_ilcp", "ilcp")):
                p50, p99, mean = _timed(
                    lambda: svc.list_docs(batch(), max_df=max_df,
                                          engine=eng, max_buf=max_buf),
                    iters=iters, warmup=iters + 1,
                )
                qps = B / (mean / 1e3)
                rows.append([collection, ep, B, mode, launches,
                             round(p50, 2), round(p99, 2), round(qps, 0)])
                results.append({
                    "collection": collection,
                    "endpoint": ep,
                    "batch": B,
                    "mesh_shape": [1],
                    "scale": SCALE,
                    "list_kernel": mode,
                    "p50_ms": round(p50, 3),
                    "p99_ms": round(p99, 3),
                    "qps": round(qps, 1),
                    "list_launches": launches,
                    "list_resident_bytes": resident,
                    "list_scratch_bytes": scratch,
                })
    emit(rows, ["collection", "endpoint", "batch", "list_kernel",
                "launches", "p50_ms", "p99_ms", "qps"])
    return rows, results


def run(collections=("version-p001", "dna-p03"), batch_sizes=BATCH_SIZES,
        k: int = 10, max_df: int = 128, max_buf: int = 1024,
        out: str | None = None, iters: int = ITERS,
        inject: str = DEFAULT_INJECT, resilience_queries: int = RESILIENCE_QUERIES,
        shard_counts=SHARD_COUNTS, use_list_kernel: bool | None = None):
    rows, results = [], []
    for name in collections:
        coll = bench_collections()[name]
        svc = RetrievalService.build(coll, block_size=32, beta=8.0,
                                     use_list_kernel=use_list_kernel)
        workload = random_substring_patterns(coll, 1500, 6, 256)
        if not workload:
            continue
        _bench_endpoints(svc, name, [1], workload, batch_sizes,
                         k, max_df, max_buf, iters, rows, results)

    # shard-count sweep on the first collection: the same endpoints through
    # the docs-mesh service, one row per (endpoint, batch, mesh shape).
    # Shard counts past the (virtualized) device count are skipped loudly —
    # the artifact's mesh_shape column shows exactly what ran.
    feasible = [s for s in shard_counts if 1 < s <= jax.device_count()]
    skipped = [s for s in shard_counts if s > jax.device_count()]
    if skipped:
        print(f"shard sweep: skipping {skipped} "
              f"(only {jax.device_count()} devices)")
    sweep_coll = bench_collections()[collections[0]]
    sweep_load = random_substring_patterns(sweep_coll, 1500, 6, 256)
    for n_shards in feasible:
        svc, mesh_shape = _build_service(
            sweep_coll, n_shards, block_size=32, beta=8.0, brute_window=512,
            use_list_kernel=use_list_kernel,
        )
        _bench_endpoints(svc, collections[0], mesh_shape, sweep_load,
                         batch_sizes, k, max_df, max_buf, iters, rows, results)

    emit(rows, ["collection", "endpoint", "batch", "shards",
                "p50_ms", "p99_ms", "qps"])
    # kernel-vs-XLA listing comparison at fixed batches (see the function's
    # docstring); its rows join the artifact so the perf trajectory can
    # diff the kernel path against the committed mirror
    _, cmp_results = run_list_kernel_comparison(
        collections[0], max_df=max_df, max_buf=max_buf, iters=iters,
    )
    results.extend(cmp_results)
    # resilience: unsharded, plus through the widest sharded service built
    resilience = run_resilience(collection=collections[0], inject=inject,
                                n_queries=resilience_queries)
    resilience_sharded = None
    if feasible:
        resilience_sharded = run_resilience(
            collection=collections[0], inject=inject,
            n_queries=resilience_queries, n_shards=max(feasible),
        )
    payload = {
        "results": results,
        "resilience": resilience,
        "resilience_sharded": resilience_sharded,
        "device_count": jax.device_count(),
        "scale": SCALE,
        "list_kernel_batches": list(LIST_COMPARE_BATCHES),
        "failures": [],
    }
    write_json(out, payload, "BENCH_serve.json")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="experiments/BENCH_serve.json")
    ap.add_argument("--batches", type=int, nargs="*", default=list(BATCH_SIZES))
    ap.add_argument("--shards", type=int, nargs="*", default=list(SHARD_COUNTS),
                    help="docs-mesh shard counts to sweep (1 = unsharded; "
                         "counts past the device count are skipped)")
    ap.add_argument("--inject", default=DEFAULT_INJECT,
                    help="fault specs for the resilience section "
                         "(repro.serve.faults names, 'name[:rate]' comma list)")
    ap.add_argument("--list-kernel", choices=("auto", "on", "off"),
                    default="auto",
                    help="listing backend for the main endpoint rows: "
                         "'auto' follows the platform (kernel on TPU), "
                         "'on'/'off' force it; the kernel-vs-XLA comparison "
                         "block always benches both")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: one collection, tiny batches, 3 iters")
    args = ap.parse_args()
    enable_compile_cache()
    lk = {"auto": None, "on": True, "off": False}[args.list_kernel]
    if args.smoke:
        run(collections=("version-p001",), batch_sizes=(1, 16), iters=3,
            out=args.out, inject=args.inject, resilience_queries=128,
            shard_counts=tuple(args.shards), use_list_kernel=lk)
    else:
        run(batch_sizes=tuple(args.batches), out=args.out, inject=args.inject,
            shard_counts=tuple(args.shards), use_list_kernel=lk)


if __name__ == "__main__":
    main()
