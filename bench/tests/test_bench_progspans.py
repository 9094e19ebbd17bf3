"""The per-layer readers of the program's own spans, on synthetic events,
and the program's spans kept out of the benchmark's trace reduction."""

import sys
import time
import types

import pytest

from bench import devtrace, progspans, spec
from repro.serve import obs

MS = 1e-3


def _ev(name, a, b, **attrs):
    return (name, a * MS, b * MS, attrs)


def _events():
    """A list, a count and a top-k step inside the window (0, 100 ms), a
    step past its end, and a step that found no batch.  The list step's
    run holds a pack of its own, a stage inside another."""
    return sorted([
        _ev("serve.step", 10, 30, kind="list", rows=3, bucket=4, transfers=6),
        _ev("serve.call", 11, 29, path="full"),
        _ev("serve.pack", 11, 13, rows=3, padded_rows=4, transfers=4),
        _ev("serve.run", 13, 27, brute_window=40, transfers=2),
        _ev("serve.pack", 14, 16, rows=3, padded_rows=4, transfers=4),
        _ev("serve.unpack", 27, 29),
        _ev("serve.step", 40, 50, kind="count", rows=2, bucket=2, transfers=9),
        _ev("serve.call", 41, 49, path="full"),
        _ev("serve.pack", 41, 43, rows=2, padded_rows=2, transfers=4),
        _ev("serve.run", 43, 48, transfers=5),
        _ev("serve.unpack", 48, 49),
        _ev("serve.step", 60, 70, kind="topk", rows=1, bucket=1, transfers=6),
        _ev("serve.call", 61, 69, path="full"),
        _ev("serve.pack", 61, 62, rows=1, padded_rows=1, transfers=4),
        _ev("serve.run", 62, 68, brute_window=40, transfers=2),
        _ev("serve.unpack", 68, 69),
        _ev("serve.step", 80, 81),
        _ev("serve.step", 95, 105, kind="count", rows=1, bucket=1, transfers=9),
        _ev("serve.run", 96, 104, transfers=5),
    ], key=lambda e: (e[1], -e[2]))


@pytest.fixture
def run(monkeypatch):
    """A run whose program recorded ``_events()``; the test may swap what
    ``obs.events`` hands back."""
    state = {"events": _events(), "dropped": False}
    monkeypatch.setattr(obs, "events",
                        lambda t0, t1: (state["events"], state["dropped"]))
    return types.SimpleNamespace(window=(0.0, 100 * MS), state=state)


def read(name, run):
    return spec.load_module(spec.BENCH_DIR / "metrics" / f"{name}.py").read(run)


STAGES = ("pack", "run", "unpack", "runtime")


def test_steps_inside_the_window_that_ran_a_batch(run):
    found = progspans.steps(run)
    assert [s.attrs["kind"] for s in found] == ["list", "count", "topk"]
    assert [len(s.events) for s in found] == [5, 4, 4]


def test_a_stage_inside_another_counts_toward_the_outer(run):
    ms = {name: read(f"{name}_ms_per_step", run) for name in STAGES}
    # per step: pack 2, 2, 1 (not the pack inside the list step's run);
    # run 14, 5, 6; unpack 2, 1, 1; runtime 20-18, 10-8, 10-8
    assert ms == pytest.approx({"pack": 5 / 3, "run": 25 / 3, "unpack": 4 / 3,
                                "runtime": 2})
    # the four stages partition the step
    assert sum(ms.values()) == pytest.approx(40 / 3)
    assert read("transfers_per_step", run) == pytest.approx(7)


NEW = ("pack_ms_per_step", "run_ms_per_step", "unpack_ms_per_step",
       "runtime_ms_per_step", "transfers_per_step")


@pytest.mark.parametrize("case", ["no_events", "dropped", "no_module"])
def test_readers_report_nothing_without_whole_records(run, case, monkeypatch):
    if case == "no_events":
        run.state["events"] = []
    elif case == "dropped":
        run.state["dropped"] = True
    else:
        # a program built before the tracing module existed
        import repro.serve

        monkeypatch.delattr(repro.serve, "obs")
        monkeypatch.setitem(sys.modules, "repro.serve.obs", None)
    assert {name: read(name, run) for name in NEW} == dict.fromkeys(NEW)


def test_program_spans_stay_out_of_the_benchmarks_reduction():
    """A trace holding the benchmark's spans and the program's ``serve.*``
    spans reduces to the benchmark's alone, so the existing metrics read
    the same."""
    import jax
    from jax._src.lib import _profiler

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    session = _profiler.ProfilerSession(opts)
    try:
        with jax.profiler.TraceAnnotation("window"):
            with jax.profiler.TraceAnnotation("step"):
                with obs.span("serve.step") as sp:
                    sp.set(kind="list", rows=1, bucket=1)
                    with obs.span("serve.call", path="full"):
                        time.sleep(0.001)
    finally:
        xspace = session.stop()
    held = {e.name for plane in jax.profiler.ProfileData.from_serialized_xspace(
        xspace).planes for line in plane.lines for e in line.events}
    assert {"serve.step", "serve.call"} <= held
    trace = devtrace.parse_xspace(xspace)
    assert [s[0] for s in trace.spans] == ["window", "step"]
