"""Benchmark entry point — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. ``--full`` uses the larger
synthetic datasets (several minutes on CPU); default is the quick profile.
The roofline/dry-run numbers live in launch/dryrun.py, not here.
"""
from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--bench", default="all",
                    help="all|zoo|side|negatives|order|warmstart|throughput")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    quick = not args.full

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from benchmarks import (
        bench_alpha, bench_model_zoo, bench_negatives, bench_order,
        bench_side_info, bench_throughput, bench_warmstart,
    )

    table = {
        "zoo": bench_model_zoo.run,            # paper Tables 3/4
        "side": bench_side_info.run,           # paper Table 5
        "negatives": bench_negatives.run,      # paper Table 6
        "order": bench_order.run,              # paper Table 7
        "warmstart": bench_warmstart.run,      # paper Fig. 3/4
        "throughput": bench_throughput.run,    # paper Fig. 2 + kernels
        "alpha": bench_alpha.run,              # §3.5 over-smoothing residual
    }
    print("name,us_per_call,derived")
    for name, fn in table.items():
        if args.bench in ("all", name):
            fn(quick=quick)


if __name__ == "__main__":
    main()
