"""Benchmark driver: one module per paper table/figure + the assignment's
roofline table. Prints ``name,us_per_call,derived`` CSV."""
from __future__ import annotations

import argparse
import importlib
import sys
import traceback

from benchmarks.common import emit

MODULES = [
    "benchmarks.table1_models",
    "benchmarks.table2_memory",
    "benchmarks.fig1_sine_adaptation",
    "benchmarks.fig2_convergence",
    "benchmarks.fig3_device_convergence",
    "benchmarks.fig4_omniglot_kws",
    "benchmarks.table34_round_time",
    "benchmarks.engine_bench",
    "benchmarks.fig56_hyperparams",
    "benchmarks.kernels_bench",
    "benchmarks.podclient_collectives",
    "benchmarks.roofline",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on module names")
    args = ap.parse_args()
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    failures = 0
    for modname in MODULES:
        if args.only and args.only not in modname:
            continue
        try:
            mod = importlib.import_module(modname)
            emit(mod.run())
        except Exception:
            failures += 1
            print(f"{modname},0.0,ERROR", file=sys.stdout)
            traceback.print_exc(file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
