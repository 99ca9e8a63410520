"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines and writes the full structured
results to experiments/bench_results.json. A phase that fails ends the run
with a non-zero exit. Everything runs in this process except the
``multidevice`` and ``hierarchy`` children, which fan the host CPU out into
placeholder devices and never touch an accelerator.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --only fig3,table6
  PYTHONPATH=src python -m benchmarks.run --fast     # mnist proxy only
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks import (bench_cohort, bench_hierarchy, bench_kernels,  # noqa: E402
                        bench_multidevice, bench_robust, bench_rounds,
                        bench_schedules, bench_topology, paper_tables,
                        roofline)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

OUT = os.path.join(os.path.dirname(__file__), "..", "experiments",
                   "bench_results.json")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: fig3,table2,...,fig10,kernels,rounds,"
                         "topology,schedules,cohort,multidevice,hierarchy,"
                         "robust,roofline")
    ap.add_argument("--fast", action="store_true",
                    help="mnist proxy only (skip fashion)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    enable_compile_cache()
    datasets = ["mnist"] if args.fast else ["mnist", "fashion"]

    benches = {
        "fig3": lambda ds: paper_tables.fig3_bound_gap(ds, args.seed),
        "table2": lambda ds: paper_tables.table2_alpha(ds, args.seed),
        "table3": lambda ds: paper_tables.table3_beta(ds, args.seed),
        "table4": lambda ds: paper_tables.table4_clients(ds, args.seed),
        "table5": lambda ds: paper_tables.table5_eta(ds, args.seed),
        "table6": lambda ds: paper_tables.table6_lazy(ds, args.seed),
        "table7": lambda ds: paper_tables.table7_sigma(ds, args.seed),
        "fig10": lambda ds: paper_tables.fig10_dp(ds, args.seed),
    }

    results = {}
    print("name,us_per_call,derived")
    t0 = time.time()
    for name, fn in benches.items():
        if only and name not in only:
            continue
        for ds in datasets:
            results[f"{name}_{ds}"] = fn(ds)
    if only is None or "kernels" in only:
        results["kernels"] = bench_kernels.run()
    if only is None or "rounds" in only:
        results["rounds_scan_vs_loop"] = bench_rounds.bench()
        results["rounds_kernel_path"] = bench_rounds.bench_kernel_path()
    if only is None or "topology" in only:
        results["topology_loss_vs_k"] = bench_topology.bench()
    if only is None or "schedules" in only:
        results["schedules_loss_vs_k"] = bench_schedules.bench()
    if only is None or "cohort" in only:
        results["cohort_population_scaling"] = bench_cohort.bench()
    if only is None or "multidevice" in only:
        results["multidevice_rounds_per_s"] = bench_multidevice.bench()
    if only is None or "hierarchy" in only:
        results["hierarchy_flat_vs_cluster"] = bench_hierarchy.bench()
    if only is None or "robust" in only:
        results["robust_attack_defense"] = bench_robust.bench()
    if only is None or "roofline" in only:
        results["roofline_pod16x16"] = roofline.run("pod16x16")
        results["roofline_pod2x16x16"] = roofline.run("pod2x16x16")

    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    if only is not None and os.path.exists(OUT):
        # partial runs merge over the previous results instead of dropping
        # every section they didn't re-run
        with open(OUT) as f:
            merged = json.load(f)
        merged.update(results)
        results = merged
    with open(OUT, "w") as f:
        json.dump(results, f, indent=1, default=str)
    print(f"# total {time.time() - t0:.1f}s -> {OUT}")


if __name__ == "__main__":
    main()
