"""Benchmark registry — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:

  fig2_accuracy — paper Fig. 2 (accuracy vs rounds, 4 methods, Non-IID)
  fig3_comm     — paper Fig. 3 (MB to accuracy thresholds, IID)
  agg_ablation  — §III-A aggregation-vs-sparsity analysis
  kernel_*      — Pallas kernel hot-spot microbenches

``python -m benchmarks.run`` runs quick variants (CI-speed); pass --full for
the long curves that populate EXPERIMENTS.md.
"""

from __future__ import annotations

import argparse
import sys
import os

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="long runs (minutes)")
    ap.add_argument("--only", default=None, help="comma-separated bench names")
    args = ap.parse_args()
    quick = not args.full

    from benchmarks import agg_ablation, engine_bench, fig2_accuracy, fig3_comm, kernel_bench

    benches = {
        "kernel": kernel_bench.bench,
        "engine": engine_bench.bench,
        "round": engine_bench.bench_round,
        "hetero": engine_bench.bench_hetero,
        "quant": engine_bench.bench_quant,
        "agg": agg_ablation.bench,
        "fig2": fig2_accuracy.bench,
        "fig3": fig3_comm.bench,
    }
    if args.only:
        keep = set(args.only.split(","))
        benches = {k: v for k, v in benches.items() if k in keep}

    print("name,us_per_call,derived")
    failures = []
    for name, fn in benches.items():
        try:
            for row_name, us, derived in fn(quick=quick):
                print(f"{row_name},{us:.0f},{derived}")
                sys.stdout.flush()
        except Exception as e:  # pragma: no cover - surface in CI output
            failures.append((name, repr(e)))
            print(f"{name},-1,FAILED:{e!r}")
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
