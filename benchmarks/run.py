"""Benchmark driver: one module per paper table/figure + the roofline
analysis. Prints each benchmark's rows (CSV) and paper-claim checks, and
writes reports/bench_results.json plus one reports/BENCH_<name>.json per
module (the fig/table ordinal stripped), so every figure's numbers land
as a standalone artifact whether or not the module writes its own.

  PYTHONPATH=src python -m benchmarks.run [--only fig9 ...]
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import re
import time
import traceback

from repro.launch.compile_cache import enable_compile_cache

MODULES = [
    "fig2_layer_times",
    "fig4_estimation_error",
    "fig9_slo_maintenance",
    "fig10_memory_throughput",
    "table1_record",
    "fig11_interval_sweep",
    "fig12_contention",
    "fig13_large_models",
    "fig14_max_length",
    "fig15_kv_tiering",
    "fig16_prefix_dedup",
    "fig17_preemption",
    "fig18_disk_tier",
    "fig19_sustained_load",
    "fig20_fleet",
    "fig21_disagg",
    "roofline",
]


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None,
                    help="substring filter on module names")
    args = ap.parse_args(argv)
    enable_compile_cache()

    selected = [m for m in MODULES
                if not args.only or any(o in m for o in args.only)]
    results = []
    n_claims = n_pass = 0
    t00 = time.time()
    os.makedirs("reports", exist_ok=True)
    for name in selected:
        mod = importlib.import_module(f"benchmarks.{name}")
        t0 = time.time()
        try:
            res = mod.run()
        except Exception:  # noqa: BLE001 — report, keep going
            print(f"=== {name} === FAILED\n{traceback.format_exc()[-1500:]}")
            results.append({"name": name,
                            "error": traceback.format_exc()[-1500:]})
            continue
        dt = time.time() - t0
        print(res.render())
        print(f"  ({dt:.1f}s)\n")
        results.append(res.to_json())
        stem = re.sub(r"^(fig|table)\d+_", "", res.name)
        with open(f"reports/BENCH_{stem}.json", "w") as f:
            json.dump(res.to_json(), f, indent=1)
        for c in res.claims:
            n_claims += 1
            n_pass += int(c.ok)

    with open("reports/bench_results.json", "w") as f:
        json.dump(results, f, indent=1)
    print(f"benchmarks: {len(results)} modules, {n_pass}/{n_claims} paper "
          f"claims reproduced (DIFFs are documented modeling deviations), "
          f"{time.time() - t00:.0f}s -> reports/bench_results.json")
    failed = [r["name"] for r in results if "error" in r]
    if failed:
        raise SystemExit(f"benchmarks FAILED: {failed}")


if __name__ == "__main__":
    main()
