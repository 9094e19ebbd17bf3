"""Resilient-runtime tests: every admitted request gets an answer.

Fault injection is deterministic (seeded schedules), the clock and sleep
are injectable, so every degradation path — retry-then-degrade, queued
deadline expiry, breaker trip + cooldown recovery — is exercised exactly,
not probabilistically.
"""

import numpy as np
import pytest

from repro.data.collections import SyntheticSpec, generate, random_substring_patterns
from repro.errors import InvalidQueryError, QueueFullError
from repro.serve import faults
from repro.serve.faults import POISON, FaultSpec, parse_fault_specs
from repro.serve.retrieval import RetrievalService
from repro.serve.runtime import CircuitBreaker, RuntimeConfig, ServeRuntime

GENEROUS = 300.0  # deadline that a CPU test runner cannot miss


@pytest.fixture(scope="module")
def svc_pats():
    coll = generate(SyntheticSpec("version", n_base=2, n_variants=6,
                                  base_len=80, mutation_rate=0.01, seed=3))
    svc = RetrievalService.build(coll, block_size=16, beta=8.0)
    pats = random_substring_patterns(coll, 40, 4, 12)
    assert pats
    return svc, pats


def _runtime(svc, **over):
    kw = dict(default_deadline_s=GENEROUS, backoff_base_s=0.0)
    kw.update(over)
    return ServeRuntime(svc, RuntimeConfig(**kw))


# ---------------------------------------------------------------------------
# Admission
# ---------------------------------------------------------------------------


def test_structurally_bad_input_rejected_at_submit(svc_pats):
    svc, _ = svc_pats
    rt = _runtime(svc)
    with pytest.raises(InvalidQueryError):
        rt.submit("list", np.ones((2, 2)))
    with pytest.raises(InvalidQueryError):
        rt.submit("frobnicate", np.ones(3, np.int32))
    with pytest.raises(InvalidQueryError):
        rt.submit("tfidf", np.ones(3, np.int32))  # terms must be a list
    assert rt.metrics.invalid == 3
    assert rt.metrics.submitted == 0


def test_soft_invalid_input_answers_empty(svc_pats):
    svc, _ = svc_pats
    rt = _runtime(svc)
    sigma = svc.coll.sigma
    answers = rt.serve([
        ("list", np.array([], dtype=np.int32)),                 # empty
        ("list", np.full(4, sigma + 5, dtype=np.int32)),        # out of alphabet
        ("count", np.full(4, sigma + 5, dtype=np.int32)),
    ])
    assert [a.result for a in answers] == [[], [], 0]
    assert not any(a.degraded for a in answers)


def test_queue_full_sheds_load(svc_pats):
    svc, pats = svc_pats
    rt = _runtime(svc, max_queue=2)
    rt.submit("count", pats[0])
    rt.submit("count", pats[1])
    with pytest.raises(QueueFullError):
        rt.submit("count", pats[2])
    assert rt.metrics.rejected == 1
    assert {a.rid for a in rt.step()} == {0, 1}


# ---------------------------------------------------------------------------
# Fault handling: retry, degrade, never an exception to the caller
# ---------------------------------------------------------------------------


def test_injected_failure_is_retried_then_succeeds(svc_pats):
    svc, pats = svc_pats
    rt = _runtime(svc, max_retries=2)
    # exactly one failure: first attempt dies, the retry runs clean
    with faults.inject(FaultSpec("executor", "error", rate=1.0, limit=1)) as inj:
        (ans,) = rt.serve([("list", pats[0])])
    assert len(inj.fired) == 1
    assert not ans.degraded and ans.path == "full"
    assert ans.retries == 1
    assert ans.result == svc.list_docs([pats[0]], engine="reference",
                                       max_df=rt.config.max_df)[0]


def test_retries_exhausted_degrades_never_raises(svc_pats):
    svc, pats = svc_pats
    rt = _runtime(svc, max_retries=1)
    ref = svc.list_docs(pats[:3], engine="reference",
                        max_df=rt.config.max_df)
    with faults.inject(FaultSpec("executor", "error", rate=1.0)):
        answers = rt.serve([("list", p) for p in pats[:3]])
    assert all(a.degraded for a in answers)
    # the floor path is also executor-backed, so the ladder lands on the
    # (uninstrumented) host reference loop — answers stay correct
    assert all(a.path == "reference" for a in answers)
    assert all(a.degrade_reason == "retries_exhausted:reference"
               for a in answers)
    assert [a.result for a in answers] == ref
    assert rt.metrics.degraded_fraction == 1.0


def test_poisoned_payload_never_reaches_caller(svc_pats):
    svc, pats = svc_pats
    rt = _runtime(svc, max_retries=0)
    with faults.inject(FaultSpec("executor", "poison", rate=1.0)):
        answers = rt.serve([("topk", pats[0])])
    (ans,) = answers
    assert ans.degraded
    for doc, _tf in ans.result:
        assert doc != int(POISON) and 0 <= doc < svc.coll.d


def test_planner_and_compile_faults_degrade(svc_pats):
    svc, pats = svc_pats
    # the planner site guards the plan program, which count runs; a list
    # program plans on the device, so its buckets are made cold here for
    # the compile fault to reach it
    for key in [k for k in svc._cache if k[0] == "list"]:
        del svc._cache[key]
    specs = parse_fault_specs("planner_fail:1.0,compile_error:1.0")
    rt = _runtime(svc, max_retries=0)
    with faults.inject(*specs) as inj:
        answers = rt.serve([("count", pats[0]), ("list", pats[1])])
    assert all(a.degraded for a in answers)
    assert {"plan", "compile:list"} <= {site for site, _, _ in inj.fired}
    assert answers[0].result == int(
        svc.count([pats[0]], engine="reference")[0]
    )


def test_lowering_error_propagates_not_degrades(svc_pats, monkeypatch):
    """A program the device cannot lower is not a transient failure: the
    error leaves ``serve`` instead of turning into a degraded answer."""
    svc, pats = svc_pats

    def refuse(kind, statics, build_fn, args):
        raise NotImplementedError("Unimplemented primitive in Pallas TPU lowering")

    monkeypatch.setattr(svc, "_compiled", refuse)
    rt = _runtime(svc)
    with pytest.raises(NotImplementedError):
        rt.serve([("list", pats[0])])
    assert rt.metrics.retries == 0 and rt.metrics.degraded == 0


def test_mixed_fault_workload_answers_everything(svc_pats):
    svc, pats = svc_pats
    specs = parse_fault_specs("executor_fail,slow_list,compile_error",
                              rate=0.2)
    rt = _runtime(svc)
    reqs = [("count" if i % 3 == 0 else "list", pats[i % len(pats)])
            for i in range(48)]
    with faults.inject(*specs, sleep=lambda s: None):
        answers = rt.serve(reqs)
    assert len(answers) == len(reqs)
    assert rt.metrics.answered == len(reqs)
    for a in answers:  # degraded or not, results respect the ABI
        if a.kind == "count":
            assert 0 <= a.result <= svc.coll.d
        else:
            assert all(0 <= d < svc.coll.d for d in a.result)


# ---------------------------------------------------------------------------
# Deadlines
# ---------------------------------------------------------------------------


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


def test_expired_queued_requests_answer_empty_with_miss_counted(svc_pats):
    svc, pats = svc_pats
    clock = FakeClock()
    rt = ServeRuntime(svc, RuntimeConfig(backoff_base_s=0.0),
                      clock=clock, sleep=clock.sleep)
    rt.submit("count", pats[0], deadline_s=0.05)
    rt.submit("count", pats[1], deadline_s=GENEROUS)
    clock.t += 0.2          # the first request's deadline passes while queued
    answers = {a.rid: a for a in rt.step()}
    dead, live = answers[0], answers[1]
    assert dead.degraded and dead.path == "empty"
    assert dead.degrade_reason == "deadline:empty"
    assert dead.deadline_missed and dead.overrun_s > 0
    assert not live.deadline_missed
    assert live.result == int(svc.count([pats[1]], engine="reference")[0])
    assert rt.metrics.deadline_misses == 1


def test_deadline_aware_batch_shrinking(svc_pats):
    svc, pats = svc_pats
    clock = FakeClock()
    rt = ServeRuntime(svc, RuntimeConfig(max_batch=8),
                      clock=clock, sleep=clock.sleep)
    # pretend the 8-bucket is slow and the 1-bucket fast
    rt.metrics.steady_ema_s[("count", 8)] = 10.0
    rt.metrics.steady_ema_s[("count", 4)] = 10.0
    rt.metrics.steady_ema_s[("count", 2)] = 10.0
    rt.metrics.steady_ema_s[("count", 1)] = 0.001
    for p in pats[:8]:
        rt.submit("count", p, deadline_s=1.0)
    batch = rt._cut_batch(clock())
    assert len(batch) == 1          # shrunk until the estimate fits the slack
    assert batch[0].rid == 0        # earliest deadline first == FIFO here


# ---------------------------------------------------------------------------
# Circuit breaker
# ---------------------------------------------------------------------------


def test_breaker_state_machine_standalone():
    clock = FakeClock()
    br = CircuitBreaker(threshold=2, cooldown_s=1.0, clock=clock)
    key = ("list", 4)
    assert br.allow(key) == CircuitBreaker.CLOSED
    assert not br.record_failure(key)
    assert br.record_failure(key)            # second failure trips
    assert br.allow(key) == CircuitBreaker.OPEN
    clock.t += 1.5
    assert br.allow(key) == CircuitBreaker.HALF_OPEN
    assert br.record_failure(key)            # half-open probe fails: re-trip
    assert br.allow(key) == CircuitBreaker.OPEN
    clock.t += 3.0
    assert br.allow(key) == CircuitBreaker.HALF_OPEN
    br.record_success(key)
    assert br.allow(key) == CircuitBreaker.CLOSED
    assert br.trips == 2


def test_tripped_breaker_short_circuits_then_recovers(svc_pats):
    svc, pats = svc_pats
    clock = FakeClock()
    rt = ServeRuntime(
        svc,
        RuntimeConfig(default_deadline_s=GENEROUS, max_retries=0,
                      backoff_base_s=0.0, breaker_threshold=2,
                      breaker_cooldown_s=1.0),
        clock=clock, sleep=clock.sleep,
    )
    with faults.inject(FaultSpec("executor", "error", rate=1.0)):
        rt.serve([("list", pats[0])])        # failure 1
        rt.serve([("list", pats[1])])        # failure 2: trips the breaker
        assert rt.metrics.breaker_trips == 1
        ans = rt.serve([("list", pats[2])])[0]   # OPEN: no full-path attempt
    assert ans.degraded and ans.degrade_reason.startswith("breaker_open")
    assert rt.metrics.short_circuits == 1
    # cooldown elapses -> HALF_OPEN probe runs the (now fault-free) full path
    clock.t += 2.0
    ans = rt.serve([("list", pats[0])])[0]
    assert not ans.degraded and ans.path == "full"
    assert rt.breaker.state(("list", 1)) == CircuitBreaker.CLOSED


# ---------------------------------------------------------------------------
# Metrics / latency accounting
# ---------------------------------------------------------------------------


def test_compile_and_steady_latency_tracked_separately(svc_pats):
    svc, pats = svc_pats
    rt = _runtime(svc)
    for _ in range(3):
        rt.serve([("count", pats[0])])
    key = ("count", 1)
    assert key in rt.metrics.compile_s       # first run: compile cost
    assert key in rt.metrics.steady_ema_s    # later runs: steady EMA
    m = rt.metrics.as_dict()
    assert "count/1" in m["compile_s"] and "count/1" in m["steady_ema_s"]
    assert m["degraded_fraction"] == 0.0 and m["deadline_miss_rate"] == 0.0


def test_warmup_precompiles_buckets(svc_pats):
    svc, _ = svc_pats
    rt = _runtime(svc)
    compile_s = rt.warmup(kinds=("count",), batch_sizes=(1, 2))
    assert ("count", 1) in compile_s and ("count", 2) in compile_s
    assert rt.metrics.deadline_misses == 0
