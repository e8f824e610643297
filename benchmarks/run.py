"""Benchmark harness: one module per paper table/figure + beyond-paper.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]

Prints ``name,us_per_call,derived`` CSV summary lines at the end.
"""

from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--quick", action="store_true",
                   help="smaller workloads (CI mode)")
    p.add_argument("--only", default=None)
    args = p.parse_args()

    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    from benchmarks import (
        batch_counts,
        compile_times,
        device_engine,
        poc_speedup,
        selection_overhead,
        serving_fusion,
    )

    suites = {
        "poc_speedup(Fig3)": poc_speedup,
        "compile_times(Fig4)": compile_times,
        "selection_overhead(SIV.B)": selection_overhead,
        "batch_counts(SIV.C)": batch_counts,
        "serving_fusion(beyond)": serving_fusion,
        "device_engine(beyond)": device_engine,
    }
    summary = []
    for name, mod in suites.items():
        if args.only and args.only not in name:
            continue
        print(f"\n===== {name} =====", flush=True)
        t0 = time.perf_counter()
        result = mod.main(quick=args.quick)
        dt = time.perf_counter() - t0
        derived = ""
        if name.startswith("poc_speedup") and result:
            best = max(r["speedup"] for r in result)
            derived = f"max_speedup={best:.2f}"
        elif name.startswith("selection") and result:
            derived = f"overhead={result['overhead_pct']:.1f}%"
        elif name.startswith("serving") and result:
            derived = f"fusion_speedup_k8={result[-1]['speedup_vs_k1']:.2f}"
        elif name.startswith("device_engine") and result:
            derived = (f"device_speedup={result['device_speedup']:.2f};"
                       f"sched_speedup={result['sched_speedup']:.2f}")
        summary.append((name, dt * 1e6, derived))
    print("\n===== summary =====")
    print("name,us_per_call,derived")
    for name, us, derived in summary:
        print(f"{name},{us:.0f},{derived}")


if __name__ == "__main__":
    main()
