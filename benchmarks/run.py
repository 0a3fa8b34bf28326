"""Benchmark entry point: one harness per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (us_per_call = wall time of the
harness; derived = its headline reproduction metric). Full sweep artifacts
land in results/*.csv. ``--fast`` shrinks grids for CI.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

from benchmarks import (fig1_design_space, fig5_sync_vs_async, fig6_fixed_time,
                        fig7_concurrency, fig8_fig9_regression,
                        fig10_async_design_space, table_component_breakdown,
                        table_compression, table_recipe_spread)
from benchmarks.common import write_csv

HARNESSES = [
    ("fig1_sync_design_space", fig1_design_space,
     "per_concurrency_linearity_r2_mean"),
    ("fig5_sync_vs_async", fig5_sync_vs_async,
     "carbon_ratio_async_over_sync"),
    ("fig6_fixed_time", fig6_fixed_time, "async_lower_ppl_at_4h"),
    ("fig7_concurrency", fig7_concurrency, "speedup_10x_concurrency"),
    ("fig8_fig9_predictor", fig8_fig9_regression, "sync_r2_total_kg"),
    ("fig10_async_design_space", fig10_async_design_space,
     "slope_increases_with_concurrency"),
    ("table_component_breakdown", table_component_breakdown,
     "sync_client_compute"),
    ("table_compression_int8", table_compression, "measured_reduction"),
    ("table_recipe_spread", table_recipe_spread, "spread_max_over_min"),
]


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--fast", action="store_true")
    p.add_argument("--only", default=None)
    args = p.parse_args()
    os.makedirs("results", exist_ok=True)

    print("name,us_per_call,derived")
    all_derived = {}
    for name, mod, key in HARNESSES:
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        try:
            rows, derived = mod.run(fast=args.fast)
            write_csv(rows, f"results/{name}.csv")
            us = (time.time() - t0) * 1e6
            val = derived.get(key, "")
            print(f"{name},{us:.0f},{val}")
            all_derived[name] = derived
        except Exception as e:  # keep the suite going
            print(f"{name},{(time.time()-t0)*1e6:.0f},ERROR:{e!r}")
    # every harness's derived metrics in one file
    import json
    with open("results/benchmark_derived.json", "w") as f:
        json.dump(all_derived, f, indent=1, default=str)


if __name__ == "__main__":
    main()
