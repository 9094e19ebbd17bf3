"""Declarative endpoint contracts + the jaxpr auditor that enforces them.

Every compiled serving endpoint (kind x pow2 batch bucket x backend) from
``repro.serve.retrieval`` carries implicit invariants that, until this
module, were enforced by two hand-rolled assertions in tests and nothing
else:

* **launch count** — the fused backward-search path lowers to exactly ONE
  ``pallas_call`` per batch; the XLA pair-descent fallback lowers to ZERO.
  A second launch (or a lost one) is a silent 2x regression that no
  correctness test notices.  The ``list`` endpoint's kernel path adds the
  fused ILCP listing launch on top of the search launch: exactly TWO
  per program (``2 * S`` sharded — each shard launches its own pair),
  and still ZERO on the XLA / over-budget fallback.
* **gather ceiling** — the pair-descent range search issues a bounded
  number of static gather eqns (2 per wavelet level inside the symbol
  scan, plus table lookups); an executor rewrite that reintroduces the
  legacy dual descent doubles it.
* **no host callbacks** — ``pure_callback`` / ``io_callback`` /
  ``debug_callback`` in a serving jaxpr is a host round-trip per batch.
* **no 64-bit widening** — the serving ABI is int32 indexes / float32
  scores; any f64/i64 aval means an x64 leak or an unpinned host scalar
  was folded into the program.
* **VMEM budget** — each ``pallas_call``'s block shapes must fit
  ``BACKWARD_SEARCH_VMEM_BUDGET``, and an over-budget index must provably
  be served by XLA: ``backend="kernel_overbudget"`` contracts clamp the
  budgets to 1 byte, take the kernel flags the build would select on TPU
  (``repro.serve.retrieval.kernel_selection``) and demand zero launches.

``build_registry`` derives the expected numbers from the service's own
index dimensions, ``audit_service`` traces every endpoint program through
``RetrievalService.endpoint_program`` and checks the jaxprs — nothing
executes on device.
"""

from __future__ import annotations

import dataclasses

from repro.analysis import jaxpr as jx
from repro.kernels import ops
from repro.serve.retrieval import kernel_selection

#: static gather slack on top of the 2-per-level pair-descent rank gathers:
#: pattern reversal, base/sym_starts lookups, and the Sada df counting that
#: shares the plan program (measured 4-6 on the current tree; 8 is margin
#: without room for a second descent, which would add 2 * levels)
GATHER_SLACK = 8


@dataclasses.dataclass(frozen=True)
class EndpointContract:
    """One audited (kind x bucket x backend) endpoint signature."""

    kind: str                 # "plan" | "list" | "topk" | "tfidf"
    bucket: tuple             # (batch_bucket, len_bucket)
    backend: str              # "kernel" | "xla" | "kernel_overbudget"
    pallas_calls: int         # exact whole-program launch count
    max_gathers: int | None = None    # static gather-eqn ceiling
    vmem_budget: int | None = None    # bytes per pallas_call block set
    #: collective primitives the program may contain.  () = none allowed
    #: (single-device endpoints); the sharded merge stages allowlist
    #: ("psum", "all_gather").
    collectives_allowed: tuple = ()
    #: marker for report grouping ("" = single-device, "docs" = sharded)
    mesh_axis: str = ""

    @property
    def key(self) -> str:
        pre = f"{self.mesh_axis}:" if self.mesh_axis else ""
        return (
            f"{pre}{self.kind}/B{self.bucket[0]}xm{self.bucket[1]}/"
            f"{self.backend}"
        )


@dataclasses.dataclass(frozen=True)
class Violation:
    contract: str             # EndpointContract.key (or a lint location)
    check: str                # "pallas_calls" | "gathers" | "host_callback"
    message: str              #   | "wide_dtype" | "vmem"

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def pair_descent_gather_ceiling(levels: int) -> int:
    """Static gather ceiling for a planned range search: the fused (lo,
    hi) pair descent costs 2 rank gathers per wavelet level inside the
    symbol scan (loop bodies count once in a jaxpr) plus bounded table
    lookups.  The legacy dual descent costs 4 per level and must not fit."""
    return 2 * levels + GATHER_SLACK


def build_registry(svc, buckets=((1, 8), (8, 8))) -> list[EndpointContract]:
    """Contracts for every endpoint the compile cache can lower, with the
    expected numbers derived from the service's index dimensions."""
    levels = int(svc.csa.wm.words.shape[0])
    ceiling = pair_descent_gather_ceiling(levels)
    budget = ops.BACKWARD_SEARCH_VMEM_BUDGET
    # the list endpoint carries two different kernels (search + listing);
    # each pallas_call is audited against the looser of the two budgets —
    # the wrappers enforce the per-kernel number, the audit proves neither
    # launch escaped its fallback by more than the whole budget class
    list_budget = max(budget, ops.ILCP_LIST_VMEM_BUDGET)
    contracts = []
    for bucket in buckets:
        for kind in ("plan", "list", "topk"):
            gath = ceiling if kind == "plan" else None
            # kernel path: one fused backward-search launch, plus — for
            # list only — the fused ILCP listing launch (PR 6)
            launches = 2 if kind == "list" else 1
            contracts.append(EndpointContract(
                kind, bucket, "kernel", pallas_calls=launches,
                max_gathers=gath,
                vmem_budget=list_budget if kind == "list" else budget,
            ))
            contracts.append(EndpointContract(
                kind, bucket, "xla", pallas_calls=0, max_gathers=gath,
            ))
            contracts.append(EndpointContract(
                kind, bucket, "kernel_overbudget", pallas_calls=0,
            ))
        # tfidf's term range search runs batch-reshaped through the same
        # planned CSA search: its whole [Q*T] term batch is ONE fused
        # kernel launch on the kernel backend, zero on XLA / over-budget
        contracts.append(EndpointContract(
            "tfidf", bucket, "kernel", pallas_calls=1, vmem_budget=budget,
        ))
        contracts.append(EndpointContract(
            "tfidf", bucket, "xla", pallas_calls=0,
        ))
        contracts.append(EndpointContract(
            "tfidf", bucket, "kernel_overbudget", pallas_calls=0,
        ))
    return contracts


def build_sharded_registry(svc, buckets=((1, 8), (8, 8))) -> list[EndpointContract]:
    """Contracts for a docs-mesh ShardedRetrievalService: per-shard launch
    counts (the kernel path launches once PER SHARD — the unrolled
    executors each carry their own shard's wavelet matrix), and the merge
    stages may use ``psum`` / ``all_gather`` and nothing else."""
    S = svc.n_shards
    levels = max(int(sh.csa.wm.words.shape[0]) for sh in svc.shards)
    # per-shard pair descents are unrolled: S times the single-index ceiling
    ceiling = S * pair_descent_gather_ceiling(levels)
    budget = ops.BACKWARD_SEARCH_VMEM_BUDGET
    list_budget = max(budget, ops.ILCP_LIST_VMEM_BUDGET)
    allowed = ("psum", "all_gather")
    contracts = []
    for bucket in buckets:
        for kind in ("plan", "list", "topk", "tfidf"):
            gath = ceiling if kind == "plan" else None
            # list launches search + listing kernels per shard: 2 * S
            launches = 2 * S if kind == "list" else S
            contracts.append(EndpointContract(
                kind, bucket, "kernel", pallas_calls=launches,
                max_gathers=gath,
                vmem_budget=list_budget if kind == "list" else budget,
                collectives_allowed=allowed, mesh_axis="docs",
            ))
            contracts.append(EndpointContract(
                kind, bucket, "xla", pallas_calls=0, max_gathers=gath,
                collectives_allowed=allowed, mesh_axis="docs",
            ))
            contracts.append(EndpointContract(
                kind, bucket, "kernel_overbudget", pallas_calls=0,
                collectives_allowed=allowed, mesh_axis="docs",
            ))
    return contracts


def audit_jaxpr(traced, contract: EndpointContract) -> list[Violation]:
    """Check one traced endpoint against one contract.  Pure jaxpr
    inspection — nothing is compiled or executed."""
    out = []
    key = contract.key

    n_pallas = jx.count_primitive(traced, "pallas_call")
    if n_pallas != contract.pallas_calls:
        out.append(Violation(key, "pallas_calls", (
            f"expected exactly {contract.pallas_calls} pallas_call eqn(s), "
            f"found {n_pallas} — the launch-count contract of the fused "
            f"backward-search path (PR 2) is broken"
        )))

    if contract.max_gathers is not None:
        n_gather = jx.gather_count(traced)
        if n_gather > contract.max_gathers:
            out.append(Violation(key, "gathers", (
                f"{n_gather} static gather eqns exceed the pair-descent "
                f"ceiling {contract.max_gathers} — a second wavelet descent "
                f"(or per-boundary rank calls) crept back into the range "
                f"search"
            )))

    for eqn in jx.collective_eqns(traced):
        if eqn.primitive.name not in contract.collectives_allowed:
            allowed = ", ".join(contract.collectives_allowed) or "none"
            out.append(Violation(key, "collective", (
                f"collective primitive {eqn.primitive.name!r} in the "
                f"program; this endpoint allows {allowed} — merge stages "
                f"are restricted to the psum/all_gather reduction algebra"
            )))

    for eqn in jx.find_host_callbacks(traced):
        out.append(Violation(key, "host_callback", (
            f"host callback primitive {eqn.primitive.name!r} in a serving "
            f"jaxpr — every batch would pay a host round-trip; move the "
            f"logic on-device or behind the reference path"
        )))

    for eqn, dtype in jx.wide_dtype_eqns(traced):
        out.append(Violation(key, "wide_dtype", (
            f"eqn {eqn.primitive.name!r} produces {dtype} — the serving ABI "
            f"is int32/float32; pin the dtype at the source instead of "
            f"letting x64 or a host scalar widen the program"
        )))

    if contract.vmem_budget is not None:
        for eqn in jx.pallas_eqns(traced):
            est = jx.pallas_block_bytes(eqn)
            if est > contract.vmem_budget:
                out.append(Violation(key, "vmem", (
                    f"pallas_call block set is ~{est} bytes, over the "
                    f"{contract.vmem_budget}-byte VMEM budget — the wrapper "
                    f"should have taken the XLA fallback for this index"
                )))
    return out


def trace_for_contract(svc, contract: EndpointContract):
    """Trace the endpoint program a contract describes, with the backend
    forced — for ``kernel_overbudget``, to the flags the build's selection
    picks on TPU with BOTH VMEM budgets clamped, so an over-budget index is
    shown to be served by the XLA executors (the list endpoint carries two
    kernels, and each needs its own selection to fall to XLA)."""
    B, m = contract.bucket
    if contract.backend == "kernel_overbudget":
        saved = (ops.BACKWARD_SEARCH_VMEM_BUDGET, ops.ILCP_LIST_VMEM_BUDGET)
        ops.BACKWARD_SEARCH_VMEM_BUDGET = 1
        ops.ILCP_LIST_VMEM_BUDGET = 1
        try:
            use_kernel, use_list_kernel = kernel_selection(
                getattr(svc, "shards", [svc]), "tpu"
            )
        finally:
            ops.BACKWARD_SEARCH_VMEM_BUDGET, ops.ILCP_LIST_VMEM_BUDGET = saved
    else:
        use_kernel = use_list_kernel = contract.backend == "kernel"
    return svc.trace_endpoint(contract.kind, B, m, use_kernel=use_kernel,
                              use_list_kernel=use_list_kernel)


def _csa_static_vmem_bytes(csa, buckets) -> int:
    """Static (metadata-level) VMEM estimate, independent of tracing: the
    same block layout the kernel wrapper will claim for this index."""
    return ops.block_meta_bytes(ops.backward_search_block_meta(
        csa.wm.words, csa.wm.ones_prefix,
        batch=max(b for b, _ in buckets), max_m=max(m for _, m in buckets),
    ))


def _list_static_vmem_bytes(svc, buckets, max_df: int = 64) -> int:
    """Static VMEM estimate for the fused ILCP listing kernel on this
    index: resident tables + query tiles + scratch (interval stacks and
    the distinct-document bitmap), exactly the layout
    ``ops.ilcp_list_block_meta`` describes and the wrapper gates on.
    ``max_df`` matches the audit default of ``endpoint_program``."""
    ilcp = svc.ilcp
    return ops.block_meta_bytes(ops.ilcp_list_block_meta(
        ilcp.vilcp, ilcp.rmq.table, ilcp.run_starts, svc.da,
        batch=max(b for b, _ in buckets), d=ilcp.d, max_df=max_df,
    ))


def _audit_contracts(svc, registry) -> tuple[list, list[Violation]]:
    audited, violations = [], []
    for contract in registry:
        traced = trace_for_contract(svc, contract)
        vs = audit_jaxpr(traced, contract)
        violations.extend(vs)
        audited.append({
            "contract": contract.key,
            "expected_pallas_calls": contract.pallas_calls,
            "pallas_calls": jx.count_primitive(traced, "pallas_call"),
            "gathers": jx.gather_count(traced),
            "gather_ceiling": contract.max_gathers,
            "collectives": sorted(
                {e.primitive.name for e in jx.collective_eqns(traced)}
            ),
            "vmem_block_bytes": max(
                (jx.pallas_block_bytes(e) for e in jx.pallas_eqns(traced)),
                default=0,
            ),
            "ok": not vs,
        })
    return audited, violations


def audit_service(svc, buckets=((1, 8), (8, 8))) -> tuple[dict, list[Violation]]:
    """Audit every (kind x bucket x backend) contract of a service.

    Returns (report, violations): the report lists each audited contract
    with its measured numbers (launches, gathers, VMEM estimate) so the CI
    artifact doubles as a lowering-cost trend record."""
    registry = build_registry(svc, buckets)
    violations = []
    meta_bytes = _csa_static_vmem_bytes(svc.csa, buckets)
    if meta_bytes > ops.BACKWARD_SEARCH_VMEM_BUDGET:
        violations.append(Violation(
            "index/static", "vmem",
            f"index block metadata claims ~{meta_bytes} bytes of VMEM, over "
            f"the {ops.BACKWARD_SEARCH_VMEM_BUDGET}-byte budget — kernel "
            f"launches on this index would be routed to XLA",
        ))
    list_bytes = _list_static_vmem_bytes(svc, buckets)
    if list_bytes > ops.ILCP_LIST_VMEM_BUDGET:
        violations.append(Violation(
            "index/static-list", "vmem",
            f"listing block metadata (resident + tiles + scratch) claims "
            f"~{list_bytes} bytes of VMEM, over the "
            f"{ops.ILCP_LIST_VMEM_BUDGET}-byte budget — listing kernel "
            f"launches on this index would be routed to XLA",
        ))
    audited, vs = _audit_contracts(svc, registry)
    violations.extend(vs)
    report = {
        "contracts_audited": len(registry),
        "vmem_budget_bytes": ops.BACKWARD_SEARCH_VMEM_BUDGET,
        "list_vmem_budget_bytes": ops.ILCP_LIST_VMEM_BUDGET,
        "index_static_vmem_bytes": meta_bytes,
        "list_static_vmem_bytes": list_bytes,
        "endpoints": audited,
        "violations": [v.as_dict() for v in violations],
    }
    return report, violations


def audit_sharded_service(svc, buckets=((1, 8), (8, 8))) -> tuple[dict, list[Violation]]:
    """Audit a docs-mesh ShardedRetrievalService: the per-shard launch-count
    contracts (kernel path = one ``pallas_call`` per shard), the
    psum/all_gather collective allowlist, and the per-shard static VMEM
    claims.  The per-shard VMEM check is the sharding payoff made a
    contract: each shard's wavelet matrix must fit the budget even when the
    unsharded index would not."""
    registry = build_sharded_registry(svc, buckets)
    violations = []
    shard_meta = [
        _csa_static_vmem_bytes(sh.csa, buckets) for sh in svc.shards
    ]
    for s, meta_bytes in enumerate(shard_meta):
        if meta_bytes > ops.BACKWARD_SEARCH_VMEM_BUDGET:
            violations.append(Violation(
                f"docs:shard{s}/static", "vmem",
                f"shard {s} block metadata claims ~{meta_bytes} bytes of "
                f"VMEM, over the {ops.BACKWARD_SEARCH_VMEM_BUDGET}-byte "
                f"budget — this shard's kernel launches would fall back to "
                f"XLA; use more shards",
            ))
    shard_list_meta = [
        _list_static_vmem_bytes(sh, buckets) for sh in svc.shards
    ]
    for s, meta_bytes in enumerate(shard_list_meta):
        if meta_bytes > ops.ILCP_LIST_VMEM_BUDGET:
            violations.append(Violation(
                f"docs:shard{s}/static-list", "vmem",
                f"shard {s} listing block metadata claims ~{meta_bytes} "
                f"bytes of VMEM, over the {ops.ILCP_LIST_VMEM_BUDGET}-byte "
                f"budget — this shard's listing launches would fall back "
                f"to XLA; use more shards",
            ))
    audited, vs = _audit_contracts(svc, registry)
    violations.extend(vs)
    report = {
        "mesh_axis": "docs",
        "n_shards": svc.n_shards,
        "contracts_audited": len(registry),
        "vmem_budget_bytes": ops.BACKWARD_SEARCH_VMEM_BUDGET,
        "list_vmem_budget_bytes": ops.ILCP_LIST_VMEM_BUDGET,
        "shard_static_vmem_bytes": shard_meta,
        "shard_list_static_vmem_bytes": shard_list_meta,
        "endpoints": audited,
        "violations": [v.as_dict() for v in violations],
    }
    return report, violations
