"""Benchmark aggregator: one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only NAME]

Sections:
    table1    collection statistics                     (Table 1)
    fig5      H' runs vs mutation rate                  (Fig 5)
    fig6      document listing time/space               (Figs 6-8)
    fig9      single-term top-k                         (Fig 9)
    fig10     document counting                         (Fig 10)
    table2    TF-IDF ranked multi-term throughput       (Table 2)
    serve     batched serving QPS / latency percentiles
    roofline  (arch x shape x mesh) roofline terms from the dry-run
"""

from __future__ import annotations

import argparse
import time


SECTIONS = ["table1", "fig5", "fig6", "fig9", "fig10", "table2", "serve", "roofline"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=SECTIONS)
    args = ap.parse_args()
    from repro.common import enable_compile_cache

    enable_compile_cache()
    todo = [args.only] if args.only else SECTIONS

    for section in todo:
        t0 = time.time()
        print(f"=== {section} " + "=" * 50)
        try:
            if section == "table1":
                from benchmarks import collection_stats

                collection_stats.run()
            elif section == "fig5":
                from benchmarks import sada_runs

                sada_runs.run()
            elif section == "fig6":
                from benchmarks import doc_listing

                doc_listing.run()
            elif section == "fig9":
                from benchmarks import topk

                topk.run()
            elif section == "fig10":
                from benchmarks import doc_counting

                doc_counting.run()
            elif section == "table2":
                from benchmarks import tfidf_bench

                tfidf_bench.run()
            elif section == "serve":
                from benchmarks import serve_bench

                serve_bench.run()
            elif section == "roofline":
                from benchmarks import roofline_report

                roofline_report.run()
        except Exception as e:  # noqa: BLE001
            print(f"[section {section} FAILED] {type(e).__name__}: {e}")
            raise
        print(f"--- {section} done in {time.time() - t0:.1f}s\n")


if __name__ == "__main__":
    main()
