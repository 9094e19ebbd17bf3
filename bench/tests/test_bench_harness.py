"""The harness end to end at a tiny size on the CPU, with the chip check
skipped: parts found by name in a directory of their own, a sound run comes
out correct, and every fault of the timed path, and the control, come out
not correct.  ``bench/run.py`` itself refuses to measure off a TPU."""

import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import check, control, harness, spec

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

STEADY = '''
"""Open loop, evenly spaced arrivals (a test's own arrival process)."""
import numpy as np


class Source:
    def __init__(self, rate, seconds):
        self.n = max(1, round(rate * seconds))
        self.offsets = np.arange(self.n) / rate
        self._next, self._t0 = 0, 0.0

    def start(self, t0):
        self._t0 = t0

    def poll(self, now):
        due = []
        while self._next < self.n and self._t0 + self.offsets[self._next] <= now:
            due.append(self._t0 + float(self.offsets[self._next]))
            self._next += 1
        return due

    def next_due(self):
        return self._t0 + float(self.offsets[self._next]) if self._next < self.n else None

    def finished(self, now):
        return self._next >= self.n


def make(params, seconds, rng):
    return Source(float(params["rate"]), seconds)
'''

FIRST_SUBSTRINGS = '''
"""The first substrings of each document (a test's own pool)."""
import numpy as np


def make(ref, params, rng):
    m = int(params["length"])
    starts = ref.doc_starts[: int(params["size"])]
    return [ref.text[s + 3: s + 3 + m].astype(np.int32) for s in starts]
'''

ANSWERED = '''
"""Requests answered on the full path (a test's own metric)."""


def read(run):
    return sum(not r.failed for r in run.records)
'''


UTF8_WORDS = '''
"""Documents of words in UTF-8, as raw bytes (a test's own collection)."""
import numpy as np

WORDS = ("äitiään", "ja", "isä", "sääntö", "öljyä", "yö", "tänään", "€uro",
         "Åbo", "kő", "hyvää", "päivää")


def alphabet_size(config):
    return 256


def generate(config, rng):
    return [np.frombuffer(" ".join(rng.choice(WORDS, config["n_words"])).encode(),
                          np.uint8).astype(np.int64)
            for _ in range(config["n_docs"])]
'''

BYTE_SPANS = '''
"""Spans of the text in several lengths, every other one starting at a byte
of 0x80 or above (a test's own pool)."""
import numpy as np


def make(ref, params, rng):
    lengths, pats = params["lengths"], []
    while len(pats) < int(params["size"]):
        m = lengths[len(pats) % len(lengths)]
        p = int(rng.integers(0, ref.n - m))
        sub = ref.text[p: p + m]
        if (sub == ref.base - 1).any() or (sub[0] >= 0x80) != len(pats) % 2:
            continue
        pats.append(sub.astype(np.int32))
    return pats
'''


@pytest.fixture(scope="module")
def bench_dir(tmp_path_factory):
    """A benchmark tree of its own: the harness's plug-ins, plus a new
    configuration, traffic mix, arrival process, pool and metric, and a
    configuration that brings its own collection of UTF-8 bytes."""
    root = tmp_path_factory.mktemp("benchroot")
    b = root / "b"
    for group in ("corpora", "arrivals", "pools", "metrics"):
        shutil.copytree(BENCH / group, b / group)
    (b / "configs").mkdir()
    (b / "traffic").mkdir()
    (b / "arrivals" / "steady.py").write_text(STEADY)
    (b / "pools" / "first_substrings.py").write_text(FIRST_SUBSTRINGS)
    (b / "metrics" / "answered.py").write_text(ANSWERED)
    (b / "corpora" / "utf8_words.py").write_text(UTF8_WORDS)
    (b / "pools" / "byte_spans.py").write_text(BYTE_SPANS)
    cfg = json.loads((BENCH / "configs" / "version-p001.json").read_text())
    cfg.update(name="tiny", n_base=3, n_variants=3, base_len=300, mutation_rate=0.02)
    cfg["runtime"].update(max_batch=4, max_df=10, max_buf=512)
    (b / "configs" / "tiny.json").write_text(json.dumps(cfg))
    mixed = json.loads((BENCH / "traffic" / "mixed-open-version-p001.json").read_text())
    mixed["arrivals"] = {"kind": "poisson", "rate": 30.0}
    mixed["pool"].update(extract=300, keep=12)
    (b / "traffic" / "mixed.json").write_text(json.dumps(mixed))
    steady = dict(mixed, arrivals={"kind": "steady", "rate": 20.0},
                  pool={"kind": "first_substrings", "length": 5, "size": 6},
                  mix={"count": 0.5, "topk": 0.5})
    (b / "traffic" / "steady.json").write_text(json.dumps(steady))
    words = {"name": "words", "corpus": "utf8_words", "n_docs": 6, "n_words": 60,
             "data_seed": 0, "service": cfg["service"],
             "runtime": dict(cfg["runtime"], max_df=7), "checks": cfg["checks"]}
    (b / "configs" / "words.json").write_text(json.dumps(words))
    spans = dict(mixed, arrivals={"kind": "steady", "rate": 20.0},
                 pool={"kind": "byte_spans", "lengths": [8, 12, 16], "size": 12})
    (b / "traffic" / "spans.json").write_text(json.dumps(spans))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench.update(paths=["b"],
                 configs=[dict(bench["configs"][0], name="tiny", file="b/configs/tiny.json"),
                          dict(bench["configs"][0], name="words",
                               file="b/configs/words.json")],
                 workloads=[
                     {"name": "tiny.mixed", "config": "tiny", "traffic": "mixed",
                      "chips": 1, "why": "test"},
                     {"name": "tiny.steady", "config": "tiny", "traffic": "steady",
                      "chips": 1, "why": "test"},
                     {"name": "words.spans", "config": "words", "traffic": "spans",
                      "chips": 1, "why": "test"}])
    bench["end_to_end"] = [dict(m, workloads=["tiny.mixed"]) if "workloads" in m else m
                           for m in bench["end_to_end"]]
    bench["end_to_end"].append({"name": "answered", "unit": "requests", "better": "higher",
                                "bound": 0.01, "source": "host_clock",
                                "workloads": ["tiny.steady"]})
    bench["per_layer"] = [dict(m, workloads=["tiny.mixed"]) for m in bench["per_layer"]]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _args(workload, seconds=1.0):
    return harness.parse(["--workload", workload, "--seed", str(2**31 + 11),
                          "--seconds", str(seconds), "--trace", "0"])


@pytest.fixture(scope="module")
def session(bench_dir):
    cell = spec.load_cell("tiny.mixed", bench_dir / "BENCHMARK.json")
    return harness.Session(cell, 2**31 + 11, time.perf_counter())


def _run(session, patch=None, drain_s=2.0):
    return harness.run_cell(_args("tiny.mixed"), t_start=time.perf_counter(),
                            require_chip=False, session=session, patch=patch,
                            drain_s=drain_s)


def test_new_parts_load_from_their_own_files(bench_dir):
    cell = spec.load_cell("tiny.steady", bench_dir / "BENCHMARK.json")
    assert cell.traffic["arrivals"]["kind"] == "steady"
    assert [m.name for m in cell.end_to_end][-1] == "answered"
    assert cell.per_layer == ()
    sess = harness.Session(cell, 5, time.perf_counter())
    assert len(sess.pool) == 6 and {len(p) for p in sess.pool} == {5}
    result = harness.run_cell(_args("tiny.steady"), t_start=time.perf_counter(),
                              require_chip=False, session=sess)
    assert result["correct"], result["checks"]
    assert result["attempted"] == 20
    assert result["metrics"]["answered"] == {"value": 20.0, "unit": "requests"}
    assert set(result["checks"]) == {"failed", "count_wrong", "topk_wrong"}


def test_a_new_collection_needs_files_only(bench_dir):
    """A corpus plug-in of UTF-8 bytes and a pool of 8 to 16 bytes, added as
    files: the program serves an alphabet of more than 128 symbols (the
    largest byte, 0xE2, shifted by one, and the terminator) and every answer
    agrees with the reference."""
    cell = spec.load_cell("words.spans", bench_dir / "BENCHMARK.json")
    sess = harness.Session(cell, 2**31 + 23, time.perf_counter())
    assert sess.coll.sigma == 0xE2 + 2
    assert {len(p) for p in sess.pool} == {8, 12, 16}
    assert sum(int(p[0]) >= 0x80 for p in sess.pool) == 6
    result = harness.run_cell(_args("words.spans"), t_start=time.perf_counter(),
                              require_chip=False, session=sess)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] == 20


def test_sound_run_is_correct(session):
    result = _run(session)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] == 30
    assert set(result["metrics"]) == {"setup_s", "p50_ms", "index_bits_per_symbol"}
    assert list(result)[-1] == "checks"
    assert all(set(c) == {"value", "limit"} for c in result["checks"].values())


def test_a_host_stall_makes_answers_late_not_wrong(session):
    """Two stalls of the host, each longer than the runtime's default
    deadline: the first lets a backlog build, the second holds it in the
    queue.  With no deadline configured every request is still answered in
    full, and the wait shows in the latency."""
    assert session.cell.config["runtime"]["deadline_s"] is None
    orig = session.rt._call
    stalled = []

    def call(kind, reqs, path):
        if len(stalled) < 2:
            stalled.append(kind)
            time.sleep(0.8)
        return orig(kind, reqs, path)

    try:
        result = _run(session, lambda sess: setattr(sess.rt, "_call", call))
    finally:
        session.rt.__dict__.pop("_call", None)
    assert len(stalled) == 2
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] == 30


def _alter(name, fn):
    """Patch ``svc.<name>`` so each answer it produces passes through ``fn``."""
    def patch(sess):
        orig = getattr(sess.svc, name)
        setattr(sess.svc, name, lambda *a, **kw: fn(*orig(*a, **kw)))
    return patch


def _count_off_by_one(df):
    df = np.array(df)
    df[0] += 1
    return df


def _drop_a_document(docs, cnt):
    cnt = np.array(cnt)
    cnt[np.argmax(cnt)] -= 1
    return docs, cnt


def _bump_a_tf(docs, tfs):
    tfs = np.array(tfs)
    tfs[0, 0] += 1
    return docs, tfs


def _skew_scores(docs, scores):
    return docs, np.asarray(scores) * np.float32(1.001) + np.float32(1e-3)


def _half_the_batch(sess):
    orig = sess.rt._call
    sess.rt._call = lambda kind, reqs, path: orig(kind, reqs, path)[: len(reqs) // 2]


def _full_path_fails(sess):
    """Every full-path call fails, so batches are served by the fallbacks."""
    from repro.errors import TransientExecutionError

    orig = sess.rt._call

    def call(kind, reqs, path):
        if path == "full":
            raise TransientExecutionError("planted")
        return orig(kind, reqs, path)
    sess.rt._call = call


def _expire_in_the_queue(sess):
    orig = sess.rt.submit
    sess.rt.submit = lambda kind, payload, **kw: orig(kind, payload, deadline_s=0.0)


FAULTS = {
    "count_altered": (_alter("count", lambda df: (_count_off_by_one(df),)), "count_wrong"),
    "list_altered": (_alter("list_docs_arrays", _drop_a_document), "list_wrong"),
    "topk_altered": (_alter("topk_arrays", _bump_a_tf), "topk_wrong"),
    "tfidf_altered": (_alter("tfidf_arrays", _skew_scores), "tfidf_score_err"),
    "half_the_batch_left_out": (_half_the_batch, "failed"),
    "answered_off_the_full_path": (_full_path_fails, "failed"),
    "expired_in_the_queue": (_expire_in_the_queue, "failed"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_is_not_correct(session, fault):
    patch, caught_by = FAULTS[fault]
    if fault == "count_altered":
        # count returns one array, not a tuple
        def patch(sess):
            orig = sess.svc.count
            sess.svc.count = lambda *a, **kw: _count_off_by_one(orig(*a, **kw))
    try:
        result = _run(session, patch)
    finally:
        for name in ("count", "list_docs_arrays", "topk_arrays", "tfidf_arrays"):
            session.svc.__dict__.pop(name, None)
        session.rt.__dict__.pop("_call", None)
        session.rt.__dict__.pop("submit", None)
        session.rt.breaker._st.clear()
    assert not result["correct"]
    c = result["checks"][caught_by]
    assert c["value"] > c["limit"], result["checks"]


def test_the_control_is_not_correct(bench_dir):
    cell = spec.load_cell("tiny.mixed", bench_dir / "BENCHMARK.json")
    for seed in (1, 2, 3):
        checks = control.control_checks(cell, seed, 20.0)
        assert not check.passed(checks)
        assert checks["tfidf_score_err"]["value"] > checks["tfidf_score_err"]["limit"]


@pytest.mark.parametrize("tree", ["checkout", "benchmark_files_only"])
def test_run_refuses_to_measure_off_a_tpu(tmp_path, tree):
    cwd = ROOT
    if tree == "benchmark_files_only":
        cwd = tmp_path
        shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
        shutil.copytree(BENCH, tmp_path / "bench",
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "version-p001.mixed-open",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
