"""The reduction from a profiler trace to the per-layer metrics."""

import types

import pytest

from bench import devtrace, spec


def test_union_and_covered():
    merged = devtrace.union([(0, 2), (1, 3), (5, 6), (8, 10), (4, 4)])
    assert merged == [(0, 3), (5, 6), (8, 10)]
    assert devtrace.covered(merged, 0, 10) == 6
    assert devtrace.covered(merged, 2.5, 5.5) == 1
    assert devtrace.covered(merged, 6, 8) == 0
    assert devtrace.covered(merged, -5, 0.5) == 0.5


def _synthetic():
    ms = 1e6
    ops = {"/device:TPU:0": [("fusion.1", 1 * ms, 2 * ms),
                             ("_backward_search_kernel", 2 * ms, 3 * ms),
                             ("fusion.1", 6 * ms, 7 * ms),
                             ("outside", 20 * ms, 21 * ms)]}
    spans = [("window", 0, 10 * ms), ("submit", 0, 0.5 * ms),
             ("step", 0.5 * ms, 4 * ms), ("arrival_wait", 4 * ms, 5.5 * ms),
             ("step", 5.5 * ms, 8 * ms), ("arrival_wait", 8 * ms, 10 * ms)]
    return devtrace.Trace(ops=ops, spans=spans)


def test_summary_of_a_synthetic_trace():
    s = devtrace.summarize(_synthetic())
    assert s.window_s == pytest.approx(0.010)
    assert s.busy_s == pytest.approx(0.003)
    assert s.steps == [pytest.approx((0.0035, 0.002)), pytest.approx((0.0025, 0.001))]
    assert s.op_seconds == {"fusion.1": pytest.approx(0.002),
                            "_backward_search_kernel": pytest.approx(0.001)}
    assert s.gaps == {"submit": pytest.approx(0.0005), "step": pytest.approx(0.003),
                      "arrival_wait": pytest.approx(0.0035)}
    assert devtrace.top(s.gaps, 2)[0] == ["arrival_wait", pytest.approx(0.0035)]


def _run(summary, records=(), kind="TPU v5 lite"):
    return types.SimpleNamespace(trace=summary, records=list(records),
                                 device_kind=kind, sigma=5)


def test_readers_on_a_synthetic_trace():
    s = devtrace.summarize(_synthetic())
    read = lambda name, run: spec.load_module(
        spec.BENCH_DIR / "metrics" / f"{name}.py").read(run)
    assert read("device_idle_share", _run(s)) == pytest.approx(0.7)
    assert read("device_ms_per_step", _run(s)) == pytest.approx(1.5)
    assert read("host_ms_per_step", _run(s)) == pytest.approx(1.5)
    assert read("device_idle_share", _run(None)) is None

