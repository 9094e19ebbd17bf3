"""The control of ``correct``: the reference put in the program's place,
with one of the configuration's guarantees broken, must come out not
correct.

    python bench/control.py --workload version-p001.mixed-open --seeds 1 2 3

The configuration states exact answers in the regime where nothing
truncates, and tf-idf scores in float32.  The control answers the cell's own
requests (the same collection, pool and arrivals as a run with that seed,
as many requests as the run's window offers) from the plain reference with
the step that would tempt a later change:

* listing and counting stop at 256 documents, the runtime's default
  ``max_df`` (it bites only where d > 256);
* tf-idf weights, products and sums are rounded to bfloat16, the precision
  below the stated float32.

It prints the numbers compared, each beside its limit, per seed.  The
benchmark's own runs never run it; it needs no chip, since the program does
not run.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT)]

import ml_dtypes  # noqa: E402
import numpy as np  # noqa: E402

from bench import check, harness, spec  # noqa: E402
from bench.reference import Reference  # noqa: E402

TRUNCATE = 256
BF16 = ml_dtypes.bfloat16


def bf16_tfidf(ref: Reference, terms, k: int) -> list[tuple[int, float]]:
    """Ranked-OR tf-idf with every weight, product and sum in bfloat16."""
    scores: dict[int, object] = {}
    for term in terms:
        docs, tf = ref.doc_tf(term)
        w = BF16(np.log2(np.float32(ref.d) / np.float32(max(len(docs), 1))))
        for doc, f in zip(docs.tolist(), tf.tolist()):
            scores[doc] = BF16(scores.get(doc, BF16(0)) + BF16(BF16(f) * w))
    ranked = sorted(scores.items(), key=lambda kv: (-float(kv[1]), kv[0]))[:k]
    return [(doc, float(s)) for doc, s in ranked]


def answer(ref: Reference, kind: str, payload, k: int):
    if kind == "count":
        return min(ref.count(payload), TRUNCATE)
    if kind == "list":
        return ref.list(payload)[:TRUNCATE]
    if kind == "topk":
        return ref.topk(payload, k)
    return bf16_tfidf(ref, payload, k)


def control_checks(cell: spec.Cell, seed: int, seconds: float) -> dict:
    """The numbers compared when the control answers one run's requests."""
    cfg, traffic = cell.config, cell.traffic
    ref = Reference(*harness.collection(cell))
    pool = cell.module("pools", traffic["pool"]["kind"]).make(
        ref, traffic["pool"], harness._rng(seed, harness._POOL))
    src = cell.module("arrivals", traffic["arrivals"]["kind"]).make(
        traffic["arrivals"], seconds, harness._rng(seed, harness._TRAFFIC))
    stream = harness.Requests(traffic["mix"], pool, traffic.get("tfidf_terms", 2),
                              harness._rng(seed, harness._REQUESTS), src.n)
    k = cfg["runtime"]["k"]
    records = []
    for _ in range(src.n):
        kind, payload = stream.next()
        rec = harness.Record(kind, payload, due=0.0)
        rec.answer, rec.full = answer(ref, kind, payload, k), True
        records.append(rec)
    kinds = [kind for kind, share in traffic["mix"].items() if share > 0]
    return check.compare(records, ref, k=k, conjunctive=cfg["runtime"]["tfidf_conjunctive"],
                         limits=cfg["checks"], kinds=kinds)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window length (default: BENCHMARK.json's run_seconds)")
    args = ap.parse_args(argv)
    seconds = args.seconds or json.loads((spec.ROOT / "BENCHMARK.json").read_text())[
        "run_seconds"]
    cell = spec.load_cell(args.workload)
    caught = True
    for seed in args.seeds:
        checks = control_checks(cell, seed, seconds)
        caught &= not check.passed(checks)
        print(json.dumps({"seed": seed, "correct": check.passed(checks),
                          "checks": checks}), flush=True)
    print(json.dumps({"control_caught_on_every_seed": caught}))
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
