"""Figures 6-8: document listing — time per query vs index bits/char.

Indexes (Section 6.2.1): Brute-L, Brute-D, Sada-C-D, Sada-I-D (ILCP),
Sada-I-L, PDL.  Query time excludes range finding, as in the paper; space
is the modeled compressed size of the *listing structure* (the CSA is
reported separately by collection_stats).

``--list-kernel`` adds fused-ILCP comparison rows: the same Fig-1
recursion through ``ilcp_list_docs_da_planned`` as one Pallas launch
(``on``), as the XLA lockstep fallback (``off``), or both (``auto``,
the default) — each row carries its whole-program ``pallas_call`` count
and the kernel's per-launch resident + scratch VMEM bytes."""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from benchmarks.common import (
    bench_collections, emit, patterns_for, suffix_data_for, time_batched,
)
from repro.analysis.jaxpr import count_primitive
from repro.core.csa import build_csa
from repro.core.ilcp import (
    build_ilcp,
    ilcp_list_docs_csa,
    ilcp_list_docs_da,
    ilcp_list_docs_da_planned,
)
from repro.core.listing import brute_list_csa, brute_list_da, sada_c_list_docs_da
from repro.core.pdl import build_pdl, pdl_list_docs
from repro.core.wtlist import build_da_wavelet, wt_list_docs, wt_modeled_bits
from repro.kernels import ops
from repro.succinct.rmq import rmq_build
from repro.common import ceil_log2, enable_compile_cache


def run(collections=("dna-p001", "dna-p03", "version-p001", "random"),
        list_kernel: str = "auto"):
    rows = []
    for name in collections:
        coll = bench_collections()[name]
        data = suffix_data_for(name)
        csa = build_csa(data)
        ilcp = build_ilcp(data)
        pdl = build_pdl(data, block_size=64, beta=16.0, mode="list")
        rmq_c = rmq_build(data.c)
        da = jnp.asarray(data.da)
        da_wm = build_da_wavelet(data.da, coll.d)
        pats, ranges = patterns_for(name)
        nz = ranges[:, 1] > ranges[:, 0]
        ranges = ranges[nz]
        if not len(ranges):
            continue
        lo = jnp.asarray(ranges[:, 0])
        hi = jnp.asarray(ranges[:, 1])
        max_df = coll.d + 1
        max_occ = min(int((ranges[:, 1] - ranges[:, 0]).max()), 8192)
        n = coll.n
        total_df = sum(
            len(set(data.da[a:b].tolist())) for a, b in ranges
        )

        da_bits = n * max(1, ceil_log2(coll.d))
        engines = {
            "Brute-L": (
                jax.jit(jax.vmap(lambda a, b, csa=csa, mo=max_occ, md=max_df: brute_list_csa(csa, a, b, mo, md)[:2])),
                0,
            ),
            "Brute-D": (
                jax.jit(jax.vmap(lambda a, b, da=da, mo=max_occ, md=max_df: brute_list_da(da, a, b, mo, md)[:2])),
                da_bits,
            ),
            "Sada-C-D": (
                jax.jit(jax.vmap(lambda a, b, rmq_c=rmq_c, da=da, d=coll.d, md=max_df: sada_c_list_docs_da(rmq_c, da, a, b, d, md))),
                da_bits + 2 * n,
            ),
            "Sada-I-D": (
                jax.jit(jax.vmap(lambda a, b, ilcp=ilcp, da=da, md=max_df: ilcp_list_docs_da(ilcp, da, a, b, md))),
                da_bits + ilcp.modeled_bits_listing(),
            ),
            "Sada-I-L": (
                jax.jit(jax.vmap(lambda a, b, ilcp=ilcp, csa=csa, md=max_df: ilcp_list_docs_csa(ilcp, csa, a, b, md))),
                ilcp.modeled_bits_listing(),
            ),
            "PDL": (
                jax.jit(jax.vmap(lambda a, b, pdl=pdl, csa=csa, md=max_df: pdl_list_docs(pdl, csa, a, b, md, max_buf=2048))),
                pdl.modeled_bits(),
            ),
            "WT": (
                jax.jit(jax.vmap(lambda a, b, da_wm=da_wm, md=max_df: wt_list_docs(da_wm, a, b, md)[::2])),
                wt_modeled_bits(da_wm),
            ),
        }
        for ename, (fn, bits) in engines.items():
            t, out = time_batched(fn, lo, hi)
            us_per_doc = t * 1e6 / max(total_df, 1)
            rows.append(
                [name, ename, len(ranges), round(bits / n, 3),
                 round(t * 1e3, 2), round(us_per_doc, 2), 0, 0, 0]
            )

        # fused-ILCP comparison rows: one Pallas launch for the whole
        # batch (on) vs the XLA lockstep fallback (off), same bit pattern
        ilcp_bits = da_bits + ilcp.modeled_bits_listing()
        modes = {"auto": (False, True), "on": (True,), "off": (False,)}
        resident = ops.ilcp_list_resident_bytes(
            ilcp.vilcp, ilcp.rmq.table, ilcp.run_starts, da
        )
        scratch = ops.ilcp_list_scratch_bytes(coll.d)
        for use_k in modes[list_kernel]:
            fn = jax.jit(
                lambda a, b, ilcp=ilcp, da=da, md=max_df, uk=use_k:
                ilcp_list_docs_da_planned(ilcp, da, a, b, md, use_kernel=uk)
            )
            launches = count_primitive(
                jax.make_jaxpr(fn)(lo, hi).jaxpr, "pallas_call"
            )
            t, out = time_batched(fn, lo, hi)
            us_per_doc = t * 1e6 / max(total_df, 1)
            label = f"Sada-I-D-fused[{'on' if use_k else 'off'}]"
            rows.append(
                [name, label, len(ranges), round(ilcp_bits / n, 3),
                 round(t * 1e3, 2), round(us_per_doc, 2), launches,
                 resident if use_k else 0, scratch if use_k else 0]
            )
    return emit(rows, ["collection", "index", "queries", "bits_per_char",
                       "batch_ms", "us_per_result", "pallas_calls",
                       "resident_bytes", "scratch_bytes"])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--list-kernel", choices=("auto", "on", "off"),
                    default="auto",
                    help="fused-ILCP comparison rows: 'auto' benches both "
                         "backends, 'on'/'off' just one")
    ap.add_argument("--collections", nargs="*",
                    default=["dna-p001", "dna-p03", "version-p001", "random"])
    args = ap.parse_args()
    enable_compile_cache()
    run(collections=tuple(args.collections), list_kernel=args.list_kernel)


if __name__ == "__main__":
    main()
