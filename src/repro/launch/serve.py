"""Retrieval serving launcher.

    PYTHONPATH=src python -m repro.launch.serve [--corpus version-p001]
        [--queries 256] [--k 10] [--mode topk|list|count|tfidf]
        [--deadline-ms 500] [--inject executor_fail:0.1,slow_pdl]

Builds the full paper index stack over a synthetic corpus (see
repro.data.collections for the families) and serves batched queries
through the resilient runtime (``repro.serve.runtime``: deadlines,
retry/breaker, graceful degradation) — the single-host analogue of the
production retrieval tier (the index structures are per-shard state in a
real deployment; the query engine is identical).

Latency accounting is split honestly: the first execution of each
(endpoint, shape bucket) pays the AOT compile and is reported on its own
line; the percentiles below cover steady-state batches only.  Earlier
versions of this launcher mixed the two, which made p99 a compile
benchmark.
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from repro.common import enable_compile_cache
from repro.data.collections import (
    generate,
    paperlike_collections,
    random_substring_patterns,
)
from repro.serve import faults
from repro.serve.retrieval import RetrievalService
from repro.serve.runtime import RuntimeConfig, ServeRuntime


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--corpus", default="version-p001",
                    choices=list(paperlike_collections()))
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--mode", default="topk",
                    choices=["topk", "list", "count", "tfidf"])
    ap.add_argument("--deadline-ms", type=float, default=500.0,
                    help="per-request deadline enforced by the runtime")
    ap.add_argument("--inject", default=None,
                    help="fault specs, e.g. 'executor_fail:0.1,slow_pdl' "
                         "(see repro.serve.faults.NAMED_FAULTS)")
    args = ap.parse_args()
    enable_compile_cache()

    spec = paperlike_collections()[args.corpus]
    coll = generate(spec)
    t0 = time.time()
    svc = RetrievalService.build(coll, block_size=64, beta=16.0)
    print(f"corpus {args.corpus}: n={coll.n} d={coll.d}; "
          f"index built in {time.time()-t0:.1f}s (integrity validated: "
          f"{', '.join(sorted(svc.fingerprints))})")
    for k, v in svc.space_report().items():
        print(f"  {k:22s} {v if isinstance(v, int) else round(v, 3)}")

    workload = random_substring_patterns(coll, 2000, 6, 128)
    rng = np.random.default_rng(0)
    rt = ServeRuntime(svc, RuntimeConfig(
        max_batch=args.batch, k=args.k,
        max_df=min(256, coll.d + 1),
        default_deadline_s=args.deadline_ms / 1e3,
    ))

    def payload(i: int):
        if args.mode == "tfidf":
            j = rng.integers(0, len(workload))
            return [workload[i], workload[int(j)]]
        return workload[i]

    # warm pass: compiles the (mode, bucket) program outside the timed
    # (and deadlined) loop
    for _ in range(2):
        rt.serve([(args.mode, payload(int(i)))
                  for i in rng.integers(0, len(workload), args.batch)],
                 deadline_s=1e9)

    specs = faults.parse_fault_specs(args.inject) if args.inject else []
    lat = []
    served = 0
    with faults.inject(*specs):
        while served < args.queries:
            idx = rng.integers(0, len(workload), args.batch)
            t0 = time.perf_counter()
            rt.serve([(args.mode, payload(int(i))) for i in idx])
            lat.append(time.perf_counter() - t0)
            served += len(idx)
    m = rt.metrics
    ms = np.asarray(lat) * 1e3
    compiles = ", ".join(f"{k}={v}s" for k, v in m.as_dict()["compile_s"].items())
    print(f"compile (first batch per bucket, excluded below): {compiles}")
    print(f"{args.mode}: {served} queries, batch={args.batch}: "
          f"steady p50={np.percentile(ms,50):.1f}ms "
          f"p99={np.percentile(ms,99):.1f}ms ({served/ms.sum()*1e3:.0f} q/s)")
    print(f"resilience: degraded_fraction={m.degraded_fraction:.3f} "
          f"deadline_miss_rate={m.deadline_miss_rate:.3f} "
          f"retries={m.retries} breaker_trips={m.breaker_trips} "
          f"reasons={dict(m.degrade_reasons)}")


if __name__ == "__main__":
    main()
