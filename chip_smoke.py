"""Chip smoke: the served retrieval path, end to end, on TPU.

    python chip_smoke.py               # one chip: all four endpoints
    python chip_smoke.py --chips 4     # docs mesh over four chips vs flat
    JAX_PLATFORMS=cpu PYTHONPATH=src python chip_smoke.py --scale 0.1
                                       # CPU rehearsal: every phase, exits 1

One chip: builds ``RetrievalService`` over the paper-like ``version-p001``
collection (``--scale 2`` gives 800,400 symbols and 400 documents, made
from its seed), then serves a few batches of ``count``, ``list``, ``topk``
and ``tfidf`` through ``ServeRuntime`` on ``random_substring_patterns``
traffic, as ``repro.launch.serve`` does.  It passes when every answer came
from the full device path, undegraded, equal to ``engine="reference"`` bit
for bit, and when every compiled endpoint program holds the Mosaic kernel
launches (``tpu_custom_call``) its build-time selection predicts.

``--chips 4`` runs only the docs-mesh path: the sharded service over four
devices, each endpoint's answers compared with the flat service's, and
``bytes_in_use`` per device.

Phase lines come first.  Only on a TPU with every check passed does the
last line read ``{"ok": true, "device": {...}}`` and the exit code 0;
otherwise the last line names what failed and the exit code is 1.  Uses
one process and touches JAX only inside ``main``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

SRC = pathlib.Path(__file__).resolve().parent / "src"
KINDS = ("count", "list", "topk", "tfidf")
#: compiled endpoint kind -> kernels in it: (search launches, list launches)
KERNELS_PER_KIND = {"plan": (1, 0), "list": (1, 1), "topk": (1, 0),
                    "tfidf": (1, 0)}
MOSAIC = 'custom_call_target="tpu_custom_call"'


def _log(*parts) -> None:
    print(*parts, flush=True)


def _bytes_in_use(devices) -> list:
    out = []
    for dev in devices:
        stats = dev.memory_stats()
        out.append(None if stats is None else stats.get("bytes_in_use"))
    return out


def _serve(rt, workload, kind: str, batches: int, batch: int, rng):
    """``batches`` batches of ``kind`` through the runtime: the request
    payloads and their answers."""
    payloads, answers = [], []
    for _ in range(batches):
        idx = rng.integers(0, len(workload), batch)
        if kind == "tfidf":
            other = rng.integers(0, len(workload), batch)
            reqs = [[workload[int(i)], workload[int(j)]]
                    for i, j in zip(idx, other)]
        else:
            reqs = [workload[int(i)] for i in idx]
        payloads.extend(reqs)
        answers.extend(rt.serve([(kind, p) for p in reqs], deadline_s=1e9))
    return payloads, answers


def _reference(svc, cfg, kind: str, payloads):
    """The same requests through ``engine="reference"``, as the runtime's
    last rung would answer them."""
    if kind == "count":
        return [int(x) for x in svc.count(payloads, engine="reference")]
    if kind == "list":
        return svc.list_docs(payloads, max_df=cfg.max_df, engine="reference",
                             max_buf=cfg.max_buf)
    if kind == "topk":
        return svc.topk(payloads, k=cfg.k, engine="reference",
                        max_buf=cfg.max_buf)
    return svc.tfidf(payloads, k=cfg.k, conjunctive=cfg.tfidf_conjunctive,
                     max_buf=cfg.max_buf, engine="reference")


def _kernel_launches(svc, shards: int, failures: list) -> None:
    """Each compiled endpoint program holds the Mosaic launches its flags
    predict (``shards`` launches per kernel on a docs mesh)."""
    for (kind, statics), exe in sorted(svc.compiled_executables().items(),
                                       key=lambda kv: repr(kv[0])):
        search, listing = KERNELS_PER_KIND[kind]
        want = shards * (search * svc.use_search_kernel
                         + listing * svc.use_list_kernel)
        got = exe.as_text().count(MOSAIC)
        _log(f"program {kind} {statics}: tpu_custom_call={got} expected={want}")
        if got != want:
            failures.append(f"{kind} program: {got} tpu_custom_call, "
                            f"expected {want}")


def _check_answers(kind, answers, want, failures, label) -> None:
    bad_path = sum(a.path != "full" or a.degraded for a in answers)
    got = [a.result for a in answers]
    mismatched = sum(g != w for g, w in zip(got, want))
    _log(f"{kind}: {len(answers)} answers, not full/degraded={bad_path}, "
         f"differ from {label}={mismatched}")
    if bad_path:
        failures.append(f"{kind}: {bad_path} answers off the full path")
    if mismatched or len(got) != len(want):
        failures.append(f"{kind}: {mismatched} answers differ from {label}")


def _build(RetrievalService, coll, **kw):
    svc = RetrievalService.build(coll, block_size=64, beta=16.0, **kw)
    _log(f"selected paths: search={'kernel' if svc.use_search_kernel else 'xla'} "
         f"list={'kernel' if svc.use_list_kernel else 'xla'}")
    return svc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--scale", type=float, default=2.0,
                    help="paperlike_collections scale of version-p001")
    ap.add_argument("--batches", type=int, default=3)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(SRC))
    import jax
    import numpy as np

    from repro.common import enable_compile_cache
    from repro.data.collections import (
        generate, paperlike_collections, random_substring_patterns,
    )
    from repro.dist.sharding import make_docs_mesh
    from repro.serve.retrieval import RetrievalService
    from repro.serve.runtime import RuntimeConfig, ServeRuntime

    _log(f"compile cache: {enable_compile_cache()}")
    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    _log(f"device: {json.dumps(device)}")
    failures = []
    if dev.platform != "tpu":
        failures.append(f"platform is {dev.platform}, not tpu")
    if len(devices) < args.chips:
        _log(f"smoke failed: {len(devices)} devices, --chips {args.chips}")
        return 1

    spec = paperlike_collections(scale=args.scale,
                                 seed=args.seed)["version-p001"]
    coll = generate(spec)
    t0 = time.perf_counter()
    flat = _build(RetrievalService, coll)
    _log(f"index: n={coll.n} d={coll.d} built in "
         f"{time.perf_counter() - t0}s; stages (s): "
         + json.dumps(flat.build_seconds))
    workload = random_substring_patterns(coll, 2000, 6, 128, seed=args.seed + 1)
    cfg = RuntimeConfig(max_batch=args.batch, k=10,
                        max_df=min(256, coll.d + 1), default_deadline_s=1e9)
    rng = np.random.default_rng(args.seed)

    if args.chips == 1:
        _log(f"bytes_in_use after build: {_bytes_in_use([dev])}")
        rt = ServeRuntime(flat, cfg)
        for kind in KINDS:
            t0 = time.perf_counter()
            payloads, answers = _serve(rt, workload, kind, args.batches,
                                       args.batch, rng)
            t1 = time.perf_counter()
            want = _reference(flat, cfg, kind, payloads)
            _log(f"{kind}: served in {t1 - t0}s, reference in "
                 f"{time.perf_counter() - t1}s (host clock, compiles included)")
            _check_answers(kind, answers, want, failures, 'engine="reference"')
        _kernel_launches(flat, 1, failures)
        _log(f"bytes_in_use after serving: {_bytes_in_use([dev])}")
    else:
        # the cross-shard merges equal the flat answers exactly where
        # nothing truncates: every document fits max_df, every occurrence
        # max_buf
        occ = int(flat.plan(workload)["occ"].max())
        cfg = dataclasses.replace(cfg, max_df=coll.d + 1,
                                  max_buf=max(cfg.max_buf, 1 << occ.bit_length()))
        _log(f"non-truncating regime: max_df={cfg.max_df} max_buf={cfg.max_buf}")
        mesh = make_docs_mesh(args.chips)
        t0 = time.perf_counter()
        sharded = _build(RetrievalService, coll, mesh=mesh)
        _log(f"docs mesh of {args.chips}: built in "
             f"{time.perf_counter() - t0}s")
        mesh_devices = list(mesh.devices.flat)
        _log(f"bytes_in_use per device after build: "
             f"{_bytes_in_use(mesh_devices)}")
        rt_flat = ServeRuntime(flat, cfg)
        rt = ServeRuntime(sharded, cfg)
        for kind in KINDS:
            payloads, answers = _serve(rt, workload, kind, args.batches,
                                       args.batch, rng)
            want = [a.result for a in rt_flat.serve(
                [(kind, p) for p in payloads], deadline_s=1e9)]
            _check_answers(kind, answers, want, failures, "flat service")
        _kernel_launches(sharded, args.chips, failures)
        _log(f"bytes_in_use per device after serving: "
             f"{_bytes_in_use(mesh_devices)}")

    if failures:
        _log("smoke failed: " + "; ".join(failures))
        return 1
    _log(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
