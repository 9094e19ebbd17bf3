"""Find an open-loop cell's knee once, on the chip.

    python bench/sweep.py --workload version-p001.mixed-open --seed 7 \\
        --seconds 6 --rates 50 100 200 400 800

One process sets the cell up once, then offers each rate in turn for
``--seconds`` (Poisson, the cell's own mix and pool) and prints one JSON line
per rate: latency percentiles, failed requests, requests that took longer than
the configuration's ``knee_latency_s``, and the backlog in the first and last
third of the window.  The knee is the highest rate at which no request takes
longer than that and the backlog does not grow; it stops after two rates past
the knee.  The cell's traffic file is then set to 0.8 x the knee by hand.
"""

from __future__ import annotations

import argparse
import bisect
import json
import pathlib
import sys
import time

T_START = time.perf_counter()
ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, spec  # noqa: E402


def backlog(run, third: int) -> int:
    """Most requests waiting at any step start in one third of the window."""
    t0 = run.window[0]
    lo, hi = t0 + third * run.seconds / 3, t0 + (third + 1) * run.seconds / 3
    starts = sorted(s for s, _, _ in run.steps if lo <= s < hi)
    if not starts:
        return 0
    sent = sorted(r.sent for r in run.records)
    done = sorted(r.done for r in run.records if r.done is not None)
    return max(bisect.bisect_right(sent, s) - bisect.bisect_right(done, s)
               for s in starts)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    harness.device_info(cell.chips)
    harness.enable_compile_cache()
    session = harness.Session(cell, args.seed, T_START)
    limit_s = cell.config["knee_latency_s"]
    max_batch = cell.config["runtime"]["max_batch"]
    print(json.dumps({"setup_s": session.setup_s}), flush=True)
    knee, past = None, 0
    for rate in args.rates:
        arrivals = dict(cell.traffic["arrivals"], rate=rate)
        run = session.measure(args.seconds, arrivals=arrivals)
        lat = sorted(r.latency for r in run.records if r.latency is not None)
        late = sum(1 for r in run.records if r.failed or r.latency is None
                   or r.latency > limit_s)
        first, last = backlog(run, 0), backlog(run, 2)
        ok = late == 0 and last <= max(first, max_batch)
        row = {"rate": rate, "attempted": len(run.records),
               "failed": sum(r.failed for r in run.records), "late": late,
               "p50_ms": 1e3 * lat[len(lat) // 2] if lat else None,
               "p95_ms": 1e3 * lat[int(0.95 * (len(lat) - 1))] if lat else None,
               "backlog_first": first, "backlog_last": last,
               "steps": len(run.steps), "compiles": run.compiles,
               "correct": harness.check.passed(session.check(run)), "ok": ok}
        print(json.dumps(row), flush=True)
        if ok:
            knee, past = rate, 0
        else:
            past += 1
            if past == 2:
                break
    print(json.dumps({"knee": knee, "at_0.8": None if knee is None else 0.8 * knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
