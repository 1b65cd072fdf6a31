"""Benchmark driver: one module per paper table/figure + the roofline
report. ``PYTHONPATH=src python -m benchmarks.run [--full]``.

A failing sub-benchmark no longer aborts the sweep silently-green: the
driver runs every remaining job, prints the per-job tracebacks, and
exits non-zero if ANY job raised — so CI cannot upload partial CSVs as
if the sweep succeeded (the ``check_contract`` gate depends on this).
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (hours); default quick sizes")
    ap.add_argument("--only", default="",
                    help="comma-list: fig7,fig7delta,table2,table2e2e,fig45,"
                         "fig6,serve,roofline")
    ap.add_argument("--static", action="store_true",
                    help="skip the dynamic sweep; run the static program "
                         "census (repro.analysis.check --census-only) and "
                         "emit experiments/bench/static_census.csv next "
                         "to the dynamic CSVs")
    args = ap.parse_args()
    if args.static:
        # before any benchmark module import so the check can still set
        # XLA_FLAGS for its 8 virtual devices prior to the jax import
        from repro.analysis import check as static_check
        sys.exit(static_check.main(
            ["--census-only",
             "--census-csv", "experiments/bench/static_census.csv"]))
    quick = not args.full
    only = set(args.only.split(",")) if args.only else None
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (beyond_minibatch, fig6_coreset,
                            fig7_delta_psi, fig7_mpsi, fig45_ablation,
                            roofline, serve_vfl, table2_framework)
    jobs = [
        ("fig7", fig7_mpsi.run),          # Fig 7 a/b/c: MPSI comparison
        ("fig7delta", fig7_delta_psi.run),  # Fig 7d: delta-PSI amortization
        ("table2", table2_framework.run),  # Table 2: framework end-to-end
        ("table2e2e", table2_framework.run_e2e),  # Table 2: stage timings
        ("fig45", fig45_ablation.run),     # Figs 4&5: clusters + weighting
        ("fig6", fig6_coreset.run),        # Fig 6: vs V-coreset
        ("beyond", beyond_minibatch.run),  # beyond-paper: minibatch CSS
        ("serve", serve_vfl.run),          # serving: p50/p99 vs load
        ("roofline", roofline.run),        # §Roofline report (dry-run JSONs)
    ]
    t00 = time.perf_counter()
    failures = []
    for name, fn in jobs:
        if only and name not in only:
            continue
        print(f"\n######## {name} ########")
        t0 = time.perf_counter()
        try:
            fn(quick=quick)
        except Exception:
            traceback.print_exc()
            failures.append(name)
            print(f"[{name}] FAILED after {time.perf_counter()-t0:.1f}s")
            continue
        print(f"[{name}] done in {time.perf_counter()-t0:.1f}s")
    if failures:
        print(f"\nBENCHMARKS FAILED: {', '.join(failures)} "
              f"(after {time.perf_counter()-t00:.1f}s)")
        sys.exit(1)
    print(f"\nALL BENCHMARKS DONE in {time.perf_counter()-t00:.1f}s")


if __name__ == "__main__":
    main()
