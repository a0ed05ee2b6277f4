"""Benchmark entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines per benchmark row, then the
human tables.  Sizes are container-scale (single CPU core); the table
*structure* matches the paper's.  ``--full`` uses larger datasets.
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: aps,early,multilevel,maintenance,"
                         "workloads,multiquery,scaling")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()

    from . import (bench_aps_variants, bench_early_termination,
                   bench_maintenance, bench_multilevel, bench_multiquery,
                   bench_scaling, bench_workloads)

    jobs = {
        "aps": ("Table2/APS-variants",
                lambda: bench_aps_variants.run(
                    n=30_000 if args.full else 12_000)),
        "early": ("Table5/early-termination",
                  lambda: bench_early_termination.run(
                      n=30_000 if args.full else 12_000,
                      n_queries=100 if args.full else 50)),
        "multilevel": ("Table6/multi-level",
                       lambda: bench_multilevel.run(
                           n=60_000 if args.full else 25_000)),
        "maintenance": ("Table7/maintenance-ablation",
                        lambda: bench_maintenance.run(
                            n=32_000 if args.full else 20_000,
                            n_ops=40 if args.full else 30)),
        "workloads": ("Table3/dynamic-workloads",
                      lambda: bench_workloads.run(
                          scale=1.0 if args.full else 0.4)[0]),
        "multiquery": ("Figure5/multi-query",
                       lambda: bench_multiquery.run(
                           n=30_000 if args.full else 12_000,
                           batches=(16, 64, 256, 1024) if args.full
                           else (16, 64, 256))),
        "scaling": ("Figure6/device-scaling",
                    lambda: bench_scaling.run(
                        device_counts=(1, 2, 4, 8) if args.full
                        else (1, 2, 4))),
    }
    failures = []
    for key, (name, fn) in jobs.items():
        if only and key not in only:
            continue
        print(f"\n#### {name}")
        try:
            rows = fn()
            for line in rows.csv_lines(name):
                print("CSV," + line)
        except Exception:  # noqa: BLE001
            traceback.print_exc()
            failures.append(name)
    if failures:
        print(f"\nFAILED: {failures}", file=sys.stderr)
        raise SystemExit(1)
    print("\nall benchmarks complete")


if __name__ == "__main__":
    main()
