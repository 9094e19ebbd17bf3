"""One run of one benchmark cell.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the configuration's collection with the corpus plug-in that
it names (``corpora/<corpus>.py``), hands it to the program through
``repro.core.suffix.concat_documents``, builds ``RetrievalService`` and
``ServeRuntime`` over it, draws the pattern pool and warms every (endpoint
kind x batch bucket x pattern length) that the window can cut.  The window
then drives ``ServeRuntime.submit`` / ``step`` from the cell's arrival
process for ``--seconds``, and drains what is still queued.  Every answer is
compared with the plain reference (``reference.py``, ``check.py``) once the
window has closed.

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics read from a profiler trace of the window.  The last line
of standard output is one JSON object; the numbers compared, each beside its
limit, are the last lines of standard error and the result's last key.
Off a TPU, or on fewer chips than the cell asks for, the run exits 2 and
prints no result.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import os
import pathlib
import sys
import time

import numpy as np

from bench import check, corpus, devtrace, spec
from bench.reference import Reference

clock = time.perf_counter

#: streams of the seed: the collection, the pattern pool, the arrivals, the
#: requests' kinds and patterns
_CORPUS, _POOL, _TRAFFIC, _REQUESTS = 0, 1, 2, 3


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed, stream])


def collection(cell: spec.Cell) -> tuple[list[np.ndarray], int]:
    """The configuration's raw documents and the size of their alphabet,
    from its corpus plug-in and its fixed ``data_seed``.

    The collection is the deployment's, not the run's: the index's compiled
    shapes follow the data, so a collection drawn from --seed would compile
    every program anew on every seed."""
    cfg = cell.config
    corpus_kind = cell.module("corpora", cfg["corpus"])
    docs = corpus_kind.generate(cfg, _rng(cfg["data_seed"], _CORPUS))
    return docs, corpus_kind.alphabet_size(cfg)


class NoChip(RuntimeError):
    """The run found no accelerator, or fewer chips than the cell asks."""


def device_info(chips: int, require_chip: bool = True) -> dict:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_chip and (dev.platform != "tpu" or len(devices) < chips):
        raise NoChip(f"found {len(devices)} {dev.platform} device(s); the cell "
                     f"needs {chips} TPU chip(s)")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at one fixed, git-ignored
    directory of the checkout (or ``JAX_COMPILATION_CACHE_DIR``), holding
    every program, however quick to compile."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(spec.ROOT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


@dataclasses.dataclass
class Record:
    """One request of the window, on the benchmark's clock."""
    kind: str
    payload: object              # raw pattern, or list of raw terms (tfidf)
    due: float
    sent: float = 0.0
    step_start: float | None = None
    done: float | None = None
    answer: object = None
    full: bool = False           # answered on the full path, undegraded
    refused: bool = False

    @property
    def failed(self) -> bool:
        return self.refused or self.answer is None or not self.full

    @property
    def latency(self) -> float | None:
        return None if self.done is None else self.done - self.due


class Requests:
    """The cell's ``n`` requests: endpoint kinds in exact counts by the
    mix's shares, shuffled, and patterns drawn from the pool."""

    def __init__(self, mix: dict, pool: list, terms: int, rng, n: int):
        self.kinds = [k for k, share in mix.items() if share > 0]
        shares = np.asarray([mix[k] for k in self.kinds], float)
        counts = np.floor(shares / shares.sum() * n).astype(int)
        counts[: n - counts.sum()] += 1
        self.order = np.repeat(np.arange(len(self.kinds)), counts)
        rng.shuffle(self.order)
        self.pool, self.terms, self.rng = pool, terms, rng
        self.i = 0

    def next(self) -> tuple[str, object]:
        kind = self.kinds[self.order[self.i]]
        self.i += 1
        if kind == "tfidf":
            idx = self.rng.integers(0, len(self.pool), self.terms)
            return kind, [self.pool[j] for j in idx]
        return kind, self.pool[self.rng.integers(0, len(self.pool))]


def _served(kind: str, payload):
    if kind == "tfidf":
        return [corpus.served_pattern(t) for t in payload]
    return corpus.served_pattern(payload)


@contextlib.contextmanager
def _span(name: str, on: bool):
    if on:
        import jax

        with jax.profiler.TraceAnnotation(name):
            yield
    else:
        yield


@dataclasses.dataclass
class Run:
    """What a metric's reader reads (``metrics/<name>.py``)."""
    cell: spec.Cell
    seconds: float
    setup_s: float
    records: list
    steps: list                  # (start, end, answers) per step
    window: tuple[float, float]  # (open, close of the drain), benchmark clock
    live_bytes: int
    n_symbols: int
    sigma: int                   # the program's alphabet, terminator included
    compiles: int
    device_kind: str
    trace: devtrace.Summary | None = None


class Session:
    """Everything set-up makes, handed unchanged to the window."""

    def __init__(self, cell: spec.Cell, seed: int, t_start: float):
        from repro.core.suffix import concat_documents
        from repro.serve.retrieval import RetrievalService
        from repro.serve.runtime import RuntimeConfig, ServeRuntime

        self.cell, self.seed, self.t_start = cell, seed, t_start
        cfg, traffic = cell.config, cell.traffic
        docs, alphabet_size = collection(cell)
        self.ref = Reference(docs, alphabet_size)
        self.coll = concat_documents(docs)
        self.svc = RetrievalService.build(self.coll, **cfg["service"])
        pool_spec = traffic["pool"]
        self.pool = cell.module("pools", pool_spec["kind"]).make(
            self.ref, pool_spec, _rng(seed, _POOL))
        rc = cfg["runtime"]
        most = max(self.ref.occ(p) for p in self.pool)
        if most > rc["max_buf"] or rc["max_df"] <= self.coll.d:
            raise ValueError(
                f"not the exact regime: largest occ {most} against max_buf "
                f"{rc['max_buf']}, d {self.coll.d} against max_df {rc['max_df']}")
        self.runtime_config = RuntimeConfig(
            max_batch=rc["max_batch"], default_deadline_s=rc["deadline_s"],
            k=rc["k"], max_df=rc["max_df"], max_buf=rc["max_buf"],
            tfidf_conjunctive=rc["tfidf_conjunctive"])
        self.rt = ServeRuntime(self.svc, self.runtime_config)
        self.kinds = [k for k, share in traffic["mix"].items() if share > 0]
        self.warm_up()
        gc.collect()
        import jax

        self.live_bytes = sum(a.nbytes for a in jax.live_arrays())
        self.compiles_before = sum(self.svc.compile_counts.values())
        self.setup_s = clock() - t_start

    def warm_up(self) -> None:
        """Serve every (kind, batch bucket, pattern length) once, off the
        clock, so that every program the window can cut is compiled.  Each
        warm batch leads with the pool's highest-occ pattern that the
        planner sends to Brute-L, so the grow-only Brute-L windows reach
        their final size here."""
        thr = self.svc.occ_df_threshold
        by_len: dict[int, list] = {}
        for p in self.pool:
            by_len.setdefault(len(p), []).append(p)
        buckets, b = [], 1
        while b < self.runtime_config.max_batch:
            buckets.append(b)
            b *= 2
        buckets.append(self.runtime_config.max_batch)
        terms = self.cell.traffic.get("tfidf_terms", 2)
        for length, pats in sorted(by_len.items()):
            keys = []
            for p in pats:
                occ = self.ref.occ(p)
                keys.append((occ >= thr * max(self.ref.count(p), 1), -occ))
            pats = [pats[i] for i in sorted(range(len(pats)), key=keys.__getitem__)]
            for kind in self.kinds:
                for b in buckets:
                    chosen = [pats[i % len(pats)] for i in range(b * terms)]
                    for i in range(b):
                        payload = (chosen[i * terms:(i + 1) * terms]
                                   if kind == "tfidf" else chosen[i])
                        self.rt.submit(kind, _served(kind, payload), deadline_s=1e9)
                    self.rt.run_until_idle()

    def measure(self, seconds: float, trace: bool = False,
                drain_s: float = 60.0, trace_out: str | None = None,
                arrivals: dict | None = None) -> Run:
        """Drive the runtime for ``seconds`` from the cell's arrival process
        (or ``arrivals`` in its place), then drain."""
        from repro.errors import QueueFullError

        traffic = self.cell.traffic
        arrivals = arrivals or traffic["arrivals"]
        src = self.cell.module("arrivals", arrivals["kind"]).make(
            arrivals, seconds, _rng(self.seed, _TRAFFIC))
        stream = Requests(traffic["mix"], self.pool, traffic.get("tfidf_terms", 2),
                          _rng(self.seed, _REQUESTS), src.n)
        records: list[Record] = []
        pending: dict[int, Record] = {}
        steps = []
        profiler = None
        if trace:
            import jax
            from jax._src.lib import _profiler

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            # the session itself rather than jax.profiler.start_trace, whose
            # stop writes the trace to disk and converts it to JSON: minutes
            # for a whole window on the chip, inside the run's time limit
            profiler = _profiler.ProfilerSession(opts)
        with _span("window", trace):
            t0 = clock()
            src.start(t0)
            drain_end = t0 + seconds + drain_s
            while True:
                now = clock()
                due = src.poll(now)
                if due:
                    with _span("submit", trace):
                        for when in due:
                            kind, payload = stream.next()
                            rec = Record(kind, payload, due=when)
                            records.append(rec)
                            rec.sent = clock()
                            try:
                                rid = self.rt.submit(kind, _served(kind, payload))
                                pending[rid] = rec
                            except QueueFullError:
                                rec.refused = True
                if pending:
                    s0 = clock()
                    with _span("step", trace):
                        answers = self.rt.step()
                    s1 = clock()
                    if answers:
                        steps.append((s0, s1, len(answers)))
                    for ans in answers:
                        rec = pending.pop(ans.rid)
                        rec.answer, rec.step_start, rec.done = ans.result, s0, s1
                        rec.full = ans.path == "full" and not ans.degraded
                    if not answers and not due:
                        if s1 > drain_end:
                            break             # requests the runtime lost
                        time.sleep(0.001)
                    continue
                if src.finished(now):
                    break
                nxt = src.next_due()
                if nxt is not None:
                    with _span("arrival_wait", trace):
                        time.sleep(max(0.0, nxt - clock()))
            t1 = clock()
        summary = None
        if profiler is not None:
            xspace = profiler.stop()
            if trace_out:
                out = pathlib.Path(trace_out)
                out.mkdir(parents=True, exist_ok=True)
                (out / "window.xplane.pb").write_bytes(xspace)
            summary = devtrace.summarize(devtrace.parse_xspace(xspace), self.cell.chips)
        import jax

        return Run(
            cell=self.cell, seconds=seconds, setup_s=self.setup_s,
            records=records, steps=steps, window=(t0, t1),
            live_bytes=self.live_bytes, n_symbols=self.coll.n,
            sigma=self.coll.sigma,
            compiles=sum(self.svc.compile_counts.values()) - self.compiles_before,
            device_kind=jax.devices()[0].device_kind, trace=summary)

    def check(self, run: Run) -> dict:
        rc = self.cell.config["runtime"]
        return check.compare(run.records, self.ref, k=rc["k"],
                             conjunctive=rc["tfidf_conjunctive"],
                             limits=self.cell.config["checks"], kinds=self.kinds)


def memory_peak(chips: int) -> int | None:
    import jax

    peaks = []
    for dev in jax.devices()[:chips]:
        stats = dev.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def read_metrics(run: Run, metrics) -> dict:
    out = {}
    for m in metrics:
        value = run.cell.module("metrics", m.name).read(run)
        if value is not None:
            out[m.name] = {"value": float(value), "unit": m.unit}
    return out


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-out", default=None,
                    help="also keep the raw profiler trace in this directory")
    return ap.parse_args(argv)


def run_cell(args, *, t_start: float, require_chip: bool = True,
             spec_path=None, drain_s: float = 60.0, patch=None,
             session: Session | None = None) -> dict:
    """Set up (unless handed a ``session``), measure, check: the result
    line as a dict.  ``patch`` is called with the session before the window
    (tests break the timed path with it)."""
    cell = session.cell if session else spec.load_cell(args.workload, spec_path)
    device = device_info(cell.chips, require_chip)
    if session is None:
        enable_compile_cache()
        session = Session(cell, args.seed, t_start)
    if patch is not None:
        patch(session)
    run = session.measure(args.seconds, trace=bool(args.trace), drain_s=drain_s,
                          trace_out=args.trace_out)
    device["memory_peak_bytes"] = memory_peak(cell.chips)
    checks = session.check(run)
    metrics = read_metrics(run, cell.per_layer if args.trace else cell.end_to_end)
    result = {
        "correct": check.passed(checks),
        "attempted": len(run.records),
        "failed": sum(r.failed for r in run.records),
        "metrics": metrics,
        "device": device,
    }
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = {"device_ops": devtrace.top(run.trace.op_seconds),
                               "idle_gaps": devtrace.top(run.trace.gaps)}
    result["checks"] = checks
    return result


def main(argv=None, t_start: float | None = None) -> int:
    t_start = clock() if t_start is None else t_start
    args = parse(argv)
    try:
        result = run_cell(args, t_start=t_start)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
