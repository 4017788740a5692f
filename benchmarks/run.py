"""Benchmark driver — one module per paper table/figure.

Usage:
    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run table3 fig5
    BENCH_FAST=1 PYTHONPATH=src python -m benchmarks.run   # reduced budgets

Prints ``name,us_per_call,derived`` CSV (task spec)."""
from __future__ import annotations

import sys
import traceback

MODULES = [
    ("fig4", "benchmarks.fig4_params"),
    ("kernels", "benchmarks.kernels_bench"),
    ("roofline", "benchmarks.roofline_report"),
    ("table3", "benchmarks.table3_main"),
    ("fig5", "benchmarks.fig5_rounds"),
    ("fig6", "benchmarks.fig6_frequency"),
    ("fig7", "benchmarks.fig7_sync"),
    ("table4", "benchmarks.table4_ablation"),
    ("table5", "benchmarks.table5_cost"),
    ("table6", "benchmarks.table6_fusion"),
]


def main() -> None:
    import importlib

    from repro import runtime
    runtime.enable_compile_cache()
    want = set(sys.argv[1:])
    print("name,us_per_call,derived")
    failed = []
    for tag, modname in MODULES:
        if want and tag not in want:
            continue
        try:
            mod = importlib.import_module(modname)
            for row in mod.run():
                print(row)
                sys.stdout.flush()
        except Exception:
            failed.append(tag)
            traceback.print_exc()
    if failed:
        print(f"# FAILED: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == '__main__':
    main()
