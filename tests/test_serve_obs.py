"""Spans and counters of the served path (``repro.serve.obs``).

Recording follows the profiler's own switch: off with no session, on under
a ``ProfilerSession`` that records host events, as the benchmark's traced
run and ``jax.profiler.trace`` start one.
"""

import contextlib
import pathlib
import re
import time

import jax
import pytest
from jax._src.lib import _profiler

from bench import devtrace
from repro.data.collections import SyntheticSpec, generate, random_substring_patterns
from repro.serve import obs
from repro.serve.retrieval import RetrievalService
from repro.serve.runtime import RuntimeConfig, ServeRuntime

SERVE = pathlib.Path(obs.__file__).parent


@contextlib.contextmanager
def profiling():
    """A host-event profiler session, as the benchmark's traced run opens;
    yields a list that holds the serialized trace once it stops."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    session = _profiler.ProfilerSession(opts)
    out = []
    try:
        yield out
    finally:
        out.append(session.stop())


def recorded(fn):
    """Events that ``fn()`` recorded under a profiler session."""
    with profiling():
        t0 = time.perf_counter()
        fn()
        t1 = time.perf_counter()
    events, dropped = obs.events(t0, t1)
    assert not dropped
    return events


@pytest.fixture(scope="module")
def served():
    coll = generate(SyntheticSpec("version", n_base=2, n_variants=6,
                                  base_len=80, mutation_rate=0.01, seed=3))
    svc = RetrievalService.build(coll, block_size=16, beta=8.0)
    pats = random_substring_patterns(coll, 40, 4, 12)
    rt = ServeRuntime(svc, RuntimeConfig(default_deadline_s=300.0))
    rt.serve([("list", p) for p in pats[:3]])      # compile off the profiler
    return svc, rt, pats[:3]


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_off_records_nothing_and_computes_no_attrs():
    assert not obs.enabled()

    def costly():
        raise AssertionError("an attr was computed with tracing off")

    t0 = time.perf_counter()
    with obs.span("serve.step", rows=costly) as sp:
        sp.set(kind=costly)
        obs.add(transfers=1)
    assert obs.events(t0, time.perf_counter()) == ([], False)


def test_a_list_batch_of_three_splits_into_nested_stages(served):
    svc, rt, pats = served

    def step():
        for p in pats:
            rt.submit("list", p)
        rt.step()

    events = recorded(step)
    by_name = {}
    for e in events:
        by_name.setdefault(e[0], []).append(e)
    (st,) = by_name["serve.step"]
    assert {k: st[3][k] for k in ("kind", "rows", "bucket")} == {
        "kind": "list", "rows": 3, "bucket": 4}
    (call,) = by_name["serve.call"]
    assert call[3]["path"] == "full" and _inside(call, st)
    assert "serve.window_plan" not in by_name
    (pack,) = by_name["serve.pack"]
    (run,) = by_name["serve.run"]
    (unpack,) = by_name["serve.unpack"]
    # one pack, one program run and one unpack follow each other inside
    # the call: the Brute-L window needs no plan of its own
    stages = [pack, run, unpack]
    assert all(_inside(e, call) for e in stages)
    assert all(a[2] <= b[1] for a, b in zip(stages, stages[1:]))
    assert pack[3]["rows"] == 3 and pack[3]["padded_rows"] == 4
    assert run[3]["brute_window"] == svc._brute_window_for(
        "auto", rt.config.max_buf)
    # transfers: patterns, lengths and two knobs put; two outputs fetched
    assert pack[3]["transfers"] == 4 and run[3]["transfers"] == 2
    assert st[3]["transfers"] == call[3]["transfers"] == 6


def test_a_cache_miss_emits_a_compile_span(served):
    svc, _, pats = served
    for key in [k for k in svc._cache if k[0] == "plan"]:
        del svc._cache[key]
    events = recorded(lambda: svc.plan(pats))
    (comp,) = [e for e in events if e[0] == "serve.compile"]
    assert comp[3]["kind"] == "plan"
    (run,) = [e for e in events if e[0] == "serve.run"]
    assert comp[2] <= run[1]


def test_span_names_keep_clear_of_the_benchmarks_own():
    names = set()
    for path in SERVE.glob("*.py"):
        names |= set(re.findall(r'obs\.span\(\s*"([^"]+)"', path.read_text()))
    assert {"serve.step", "serve.call", "serve.pack", "serve.run",
            "serve.compile", "serve.unpack"} <= names
    assert all(n.startswith("serve.") for n in names)
    assert not names & set(devtrace.SPANS)


def test_a_full_ring_flags_the_windows_it_dropped_from():
    ring = obs.Ring(maxlen=4)
    with profiling():
        marks = []
        for i in range(6):
            marks.append(time.perf_counter())
            with ring.span("serve.unpack", i=i):
                pass
        marks.append(time.perf_counter())
    assert ring.dropped == 2
    events, dropped = ring.events(marks[0], marks[-1])
    assert dropped and [e[3]["i"] for e in events] == [2, 3, 4, 5]
    assert ring.events(marks[2], marks[-1]) == (events, False)


def test_programs_carry_scope_and_kernel_names(served):
    """Named scopes reach the lowered program's metadata; both Pallas
    kernels carry their names."""
    svc, _, _ = served
    fn, args = svc.endpoint_program("list")
    text = jax.jit(fn).lower(*args(4, 8)).as_text(debug_info=True)
    assert set(re.findall(r"/(plan|brute|ilcp|pdl)/", text)) == {
        "plan", "brute", "ilcp", "pdl"}
    jaxpr = svc.trace_endpoint("list", use_kernel=True, use_list_kernel=True)
    from repro.analysis.jaxpr import pallas_eqns

    assert {e.params["name"] for e in pallas_eqns(jaxpr.jaxpr)} == {
        "backward_search", "ilcp_list"}
