"""Benchmark harness: one function per paper table/figure + framework perf.

Prints ``name,us_per_call,derived`` CSV. Usage:
    PYTHONPATH=src python -m benchmarks.run [--only fig6,fig14]
"""

from __future__ import annotations

import argparse
import sys
import time


def _query_engine_bench():
    from .query_engine import bench_query_engine
    return bench_query_engine()


def _serving_tier_bench():
    from .serving_tier import bench_serving_tier
    return bench_serving_tier()


def all_benchmarks():
    from . import paper_figures as pf
    from . import perf
    return {
        "fig2": pf.bench_fig2_latency_curve,
        "fig5": pf.bench_fig5_false_positives,
        "fig6": pf.bench_fig6_end_to_end,
        "fig7": pf.bench_fig7_cross_region,
        "fig8": pf.bench_fig8_breakdown,
        "fig9": pf.bench_fig9_cost_model,
        "fig10": pf.bench_fig10_structure,
        "fig11": pf.bench_fig11_individual_breakdown,
        "table2": pf.bench_table2_corpus_stats,
        "fig14": pf.bench_fig14_lookup,
        "fig15": pf.bench_fig15_scalability,
        "fig16": pf.bench_fig16_tiny_sketch,
        "fig17": pf.bench_fig17_accuracy_f0,
        "regex": pf.bench_regex_ngram,
        "query_engine": _query_engine_bench,
        "serving_tier": _serving_tier_bench,
        "kernels": perf.bench_kernel_cpu_walltime,
        "roofline": perf.bench_roofline_table,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark keys")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    benches = all_benchmarks()
    keys = args.only.split(",") if args.only else list(benches)
    print("name,us_per_call,derived")
    failures = 0
    for key in keys:
        t0 = time.perf_counter()
        try:
            for line in benches[key]():
                print(line)
        except Exception as exc:  # noqa: BLE001 — report and continue
            failures += 1
            print(f"{key}/ERROR,0.0,{type(exc).__name__}:"
                  f"{str(exc)[:80].replace(',', ';')}")
        print(f"# {key} took {time.perf_counter() - t0:.1f}s",
              file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
