"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (see paper_benchmarks for
what each table measures and how it maps to the CPU-only container).

    PYTHONPATH=src python -m benchmarks.run [--fast]
"""
from __future__ import annotations

import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="skip the slower tables")
    args = ap.parse_args()

    from benchmarks import paper_benchmarks as pb

    print("name,us_per_call,derived")
    t2 = pb.table2_overall()
    pb.table3_speedups(t2)
    pb.backend_dtype_matrix()
    pb.sparsity_ablation()  # emits BENCH_sparsity.json at the repo root
    pb.fig4_gather_microbench()
    pb.fig5_scatter_microbench()
    if not args.fast:
        pb.bench_detr_train()
    # roofline summary (reads the dry-run sweep if present)
    try:
        from benchmarks import roofline

        print()
        sys.argv = ["roofline", "--mesh", "single"]
        roofline.main()
    except FileNotFoundError:
        print("roofline: experiments/dryrun_results.json missing — run "
              "`python -m repro.launch.dryrun --all --mesh both` first")


if __name__ == "__main__":
    main()
