"""Tests for the static-analysis gate (repro.analysis).

Three layers, mirroring the subsystem:

* the jaxpr walker descends into params-nested sub-jaxprs (the gap the
  old hand-rolled ``count_eqns`` in test_kernels had);
* the contract auditor catches each seeded violation class — an extra
  pallas_call, an injected pure_callback, an f64 leak, an over-budget
  block set — and passes the real service clean;
* each AST lint rule fires on a minimal fixture snippet while the real
  tree stays clean, and the allowlist suppresses exactly what it names.
"""

import json
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import jaxpr as jx
from repro.analysis import lint as lint_mod
from repro.analysis.contracts import (
    EndpointContract,
    audit_jaxpr,
    audit_service,
    build_registry,
    pair_descent_gather_ceiling,
    trace_for_contract,
)
from repro.data.collections import SyntheticSpec, generate
from repro.serve.retrieval import RetrievalService


@pytest.fixture(scope="module")
def svc():
    coll = generate(SyntheticSpec(
        "version", n_base=2, n_variants=4, base_len=60,
        mutation_rate=0.01, seed=7,
    ))
    return RetrievalService.build(coll, validate=False)


# ---------------------------------------------------------------------------
# jaxpr walker
# ---------------------------------------------------------------------------


def test_count_primitive_flat():
    jpr = jax.make_jaxpr(lambda x: jnp.sin(x) + jnp.sin(2 * x))(1.0)
    assert jx.count_primitive(jpr, "sin") == 2
    assert jx.count_primitive(jpr, "cos") == 0


def test_count_primitive_descends_into_params_jaxprs():
    # sin nested inside cond branches inside a scanned body inside jit:
    # every level stores its sub-jaxpr in eqn *params*, which is exactly
    # where the old subjaxprs-based counter could lose track.
    def branch_true(x):
        return jnp.sin(x)

    def branch_false(x):
        return jnp.sin(jnp.sin(x))

    @jax.jit
    def step(c, _):
        c = jax.lax.cond(c > 0, branch_true, branch_false, c)
        return c, c

    def prog(x):
        out, _ = jax.lax.scan(step, x, None, length=3)
        return out

    jpr = jax.make_jaxpr(prog)(1.0)
    # one sin in the true branch + two in the false branch, counted once
    # each (static program structure, not trip counts)
    assert jx.count_primitive(jpr, "sin") == 3


def test_gather_and_find_primitives():
    def prog(t, i):
        return t[i] + t[i + 1]

    jpr = jax.make_jaxpr(prog)(jnp.arange(8), 2)
    assert jx.gather_count(jpr) == jx.count_primitive(jpr, "gather")
    names = {e.primitive.name for e in jx.find_primitives(jpr, ("gather",))}
    assert names <= {"gather"}


def test_wide_dtype_eqns_flags_f64():
    with jax.enable_x64(True):
        jpr = jax.make_jaxpr(
            lambda x: x.astype(jnp.float64) * 2.0
        )(jnp.ones((2,), jnp.float32))
    wide = jx.wide_dtype_eqns(jpr)
    assert wide and all(dt == "float64" for _, dt in wide)


def test_wide_dtype_eqns_clean_on_f32():
    jpr = jax.make_jaxpr(lambda x: x * 2.0)(jnp.ones((2,), jnp.float32))
    assert jx.wide_dtype_eqns(jpr) == []


def test_find_host_callbacks():
    def prog(x):
        return jax.pure_callback(
            lambda v: np.asarray(v), jax.ShapeDtypeStruct((2,), jnp.float32), x
        )

    jpr = jax.make_jaxpr(prog)(jnp.ones((2,), jnp.float32))
    found = jx.find_host_callbacks(jpr)
    assert [e.primitive.name for e in found] == ["pure_callback"]


# ---------------------------------------------------------------------------
# contract auditor
# ---------------------------------------------------------------------------


def test_registry_shape(svc):
    reg = build_registry(svc, buckets=((1, 8), (8, 8)))
    # per bucket: 4 kinds x 3 backends (tfidf gained its kernel and
    # over-budget contracts alongside the sharded registry work)
    assert len(reg) == 2 * (4 * 3)
    keys = {c.key for c in reg}
    assert "plan/B8xm8/kernel" in keys
    assert "tfidf/B8xm8/xla" in keys
    assert "tfidf/B8xm8/kernel" in keys
    assert "tfidf/B8xm8/kernel_overbudget" in keys
    levels = int(svc.csa.wm.words.shape[0])
    plan = next(c for c in reg if c.key == "plan/B8xm8/kernel")
    assert plan.max_gathers == pair_descent_gather_ceiling(levels)


def test_audit_service_clean(svc):
    report, violations = audit_service(svc, buckets=((1, 8), (8, 8)))
    assert violations == []
    assert report["contracts_audited"] == len(report["endpoints"])
    assert all(e["ok"] for e in report["endpoints"])
    kernel_rows = [e for e in report["endpoints"] if e["contract"].endswith("/kernel")]
    # list programs fuse search + listing -> two launches; everything else one
    assert kernel_rows and all(
        e["pallas_calls"] == (2 if e["contract"].startswith("list/") else 1)
        for e in kernel_rows
    )
    assert any(e["contract"].startswith("list/") for e in kernel_rows)
    over_rows = [
        e for e in report["endpoints"]
        if e["contract"].endswith("/kernel_overbudget")
    ]
    # fallback proven at lowering time: budget clamped -> zero launches
    assert over_rows and all(e["pallas_calls"] == 0 for e in over_rows)


def test_audit_catches_extra_pallas_call(svc):
    contract = EndpointContract("plan", (8, 8), "kernel", pallas_calls=2)
    traced = trace_for_contract(
        svc, EndpointContract("plan", (8, 8), "kernel", pallas_calls=1)
    )
    vs = audit_jaxpr(traced, contract)
    assert [v.check for v in vs] == ["pallas_calls"]


def test_audit_catches_injected_host_callback(svc):
    fn, build_args = svc.endpoint_program("plan", use_kernel=False)

    def poisoned(*a):
        out = fn(*a)
        leaf = jax.tree.leaves(out)[0]
        leaf = jax.pure_callback(
            lambda v: np.asarray(v),
            jax.ShapeDtypeStruct(leaf.shape, leaf.dtype), leaf,
        )
        return out, leaf

    traced = jax.make_jaxpr(poisoned)(*build_args(8, 8))
    contract = EndpointContract("plan", (8, 8), "xla", pallas_calls=0)
    vs = audit_jaxpr(traced, contract)
    assert "host_callback" in {v.check for v in vs}


def test_audit_catches_f64_widening(svc):
    fn, build_args = svc.endpoint_program("plan", use_kernel=False)

    def widened(*a):
        out = fn(*a)
        leaf = jax.tree.leaves(out)[0]
        return out, leaf.astype(jnp.float64).sum()

    with jax.enable_x64(True):
        traced = jax.make_jaxpr(widened)(*build_args(8, 8))
    contract = EndpointContract("plan", (8, 8), "xla", pallas_calls=0)
    vs = audit_jaxpr(traced, contract)
    assert "wide_dtype" in {v.check for v in vs}


def test_audit_catches_gather_regression(svc):
    traced = trace_for_contract(
        svc, EndpointContract("plan", (8, 8), "xla", pallas_calls=0)
    )
    tight = EndpointContract("plan", (8, 8), "xla", pallas_calls=0, max_gathers=1)
    vs = audit_jaxpr(traced, tight)
    assert "gathers" in {v.check for v in vs}


def test_audit_catches_vmem_overbudget(svc):
    traced = trace_for_contract(
        svc, EndpointContract("plan", (8, 8), "kernel", pallas_calls=1)
    )
    tiny = EndpointContract(
        "plan", (8, 8), "kernel", pallas_calls=1, vmem_budget=1
    )
    vs = audit_jaxpr(traced, tiny)
    assert "vmem" in {v.check for v in vs}


def test_overbudget_contract_traces_zero_launches(svc):
    # the kernel wrapper reads the module-global budget at trace time, so
    # clamping it during the trace proves the fallback at lowering time
    contract = EndpointContract("plan", (8, 8), "kernel_overbudget", pallas_calls=0)
    traced = trace_for_contract(svc, contract)
    assert jx.count_primitive(traced, "pallas_call") == 0
    assert audit_jaxpr(traced, contract) == []


# ---------------------------------------------------------------------------
# AST lint rules — each fires on a fixture snippet, real tree stays clean
# ---------------------------------------------------------------------------


def _lint_snippet(tmp_path, rel, source):
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    return lint_mod.lint_file(path, rel)


def test_rt001_direct_clock_call(tmp_path):
    vs = _lint_snippet(tmp_path, "repro/serve/bad_runtime.py", """
        import time

        def tick():
            return time.monotonic()
    """)
    assert [v.rule for v in vs] == ["RT001"]
    assert "injectable" in vs[0].message + vs[0].fixit


def test_rt001_allows_injected_clock_reference(tmp_path):
    vs = _lint_snippet(tmp_path, "repro/serve/good_runtime.py", """
        import time

        def tick(clock=time.monotonic):
            return clock()
    """)
    assert vs == []


def test_tr001_item_and_cast_in_batch_executor(tmp_path):
    vs = _lint_snippet(tmp_path, "repro/serve/bad_exec.py", """
        def scores_batch(x, lens):
            n = int(lens)
            return x.sum().item() + n
    """)
    assert sorted(v.rule for v in vs) == ["TR001", "TR001"]


def test_tr001_branch_on_traced_param(tmp_path):
    vs = _lint_snippet(tmp_path, "repro/kernels/bad_kernel.py", """
        def descend(lo, hi, words):
            if lo > 0:
                return hi
            return lo
    """)
    assert [v.rule for v in vs] == ["TR001"]


def test_tr001_static_shape_branch_is_clean(tmp_path):
    vs = _lint_snippet(tmp_path, "repro/kernels/good_kernel.py", """
        def descend(lo, hi, words, block=None):
            if words.shape[0] > 4 and block is None:
                return hi
            return lo
    """)
    assert vs == []


def test_tr001_keyword_knob_is_clean(tmp_path):
    vs = _lint_snippet(tmp_path, "repro/serve/good_exec.py", """
        def scores_batch(x, lens, *, use_kernel=True):
            if use_kernel:
                return x
            return x + 1
    """)
    assert vs == []


def test_fj001_fault_site_outside_serving_module(tmp_path):
    vs = _lint_snippet(tmp_path, "repro/core/bad_core.py", """
        from repro.serve import faults

        def lookup(x):
            faults.fire("lookup")
            return x
    """)
    assert [v.rule for v in vs] == ["FJ001"]


def test_fj001_fault_site_on_reference_path(tmp_path):
    vs = _lint_snippet(tmp_path, "repro/serve/retrieval.py", """
        from repro.serve import faults

        def plan_reference(x):
            faults.fire("plan")
            return x
    """)
    assert [v.rule for v in vs] == ["FJ001"]
    assert "reference" in vs[0].message


def test_fj001_direct_fault_error(tmp_path):
    vs = _lint_snippet(tmp_path, "repro/serve/bad_site.py", """
        from repro.serve.faults import FaultInjectedError

        def go():
            raise FaultInjectedError("boom")
    """)
    assert [v.rule for v in vs] == ["FJ001"]


def test_jx001_import_time_jit_execution(tmp_path):
    vs = _lint_snippet(tmp_path, "repro/core/bad_import.py", """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def warm(x):
            return x + 1

        _ = warm(jnp.zeros(4))
        _ = jax.jit(lambda x: x)(jnp.zeros(2))
    """)
    assert [v.rule for v in vs] == ["JX001", "JX001"]


def test_jx001_module_scope_wrapping_is_clean(tmp_path):
    vs = _lint_snippet(tmp_path, "repro/core/good_import.py", """
        import jax

        def f(x):
            return x + 1

        g = jax.jit(f)

        @jax.jit
        def h(x):
            return x - 1

        def main(x):
            return g(x) + h(x)
    """)
    assert vs == []


def test_allowlist_suppresses_named_entry(tmp_path):
    path = tmp_path / "repro/serve/noisy.py"
    path.parent.mkdir(parents=True)
    path.write_text("import time\n\ndef tick():\n    return time.time()\n")
    vs = lint_mod.lint_file(path, "repro/serve/noisy.py")
    assert vs
    allow = {"RT001": ["repro/serve/noisy.py:tick"]}
    assert all(lint_mod._allowed(v, allow) for v in vs)
    assert not any(lint_mod._allowed(v, {"RT001": ["other.py"]}) for v in vs)


def test_real_tree_is_clean():
    import pathlib

    root = pathlib.Path(lint_mod.__file__).resolve().parents[1]
    violations, stats = lint_mod.lint_tree(root)
    assert violations == [], [v.as_dict() for v in violations]
    assert stats["files_scanned"] > 30


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def test_cli_lint_only_clean(tmp_path):
    from repro.analysis.report import run

    out = tmp_path / "report.json"
    assert run(["--lint-only", "--report", str(out)]) == 0
    report = json.loads(out.read_text())
    assert report["ok"] is True
    assert report["lint"]["violations"] == []
    assert "contracts" not in report


def test_cli_flags_dirty_tree(tmp_path):
    from repro.analysis.report import run

    bad = tmp_path / "repro/serve/bad.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("import time\n\ndef tick():\n    return time.sleep(1)\n")
    out = tmp_path / "report.json"
    assert run(["--lint-only", "--root", str(tmp_path), "--report", str(out)]) == 1
    report = json.loads(out.read_text())
    assert report["ok"] is False
    assert [v["rule"] for v in report["lint"]["violations"]] == ["RT001"]
