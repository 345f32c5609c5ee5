"""Benchmark harness — one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV; details saved to
results/benchmarks/*.json.  --quick shrinks GA budgets for CI."""
import argparse
import sys

from . import (fig2_profiling, fig7_alpha_sweep, fig8_token_scaling,
               fig9_slm_suite, fig10_edge_comparison, table1_cim_comparison,
               kernel_bench)
from .common import csv_row, save_json
from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced GA budgets (CI)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    print("name,us_per_call,derived")
    jobs = {
        "fig2": lambda: fig2_profiling.run(),
        "fig7": lambda: fig7_alpha_sweep.run(
            n_runs=2 if args.quick else 5,
            gens=10 if args.quick else 50),
        "fig8": lambda: fig8_token_scaling.run(),
        "fig9": lambda: fig9_slm_suite.run(
            gens=10 if args.quick else 50,
            seeds=1 if args.quick else 3),
        "fig10_tableII": lambda: fig10_edge_comparison.run(),
        "table1": lambda: table1_cim_comparison.run(),
        "kernels": lambda: kernel_bench.run(),
    }
    for name, job in jobs.items():
        if args.only and args.only != name:
            continue
        out = job()
        save_json(name, out)


if __name__ == "__main__":
    main()
