"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig2,table1]

Prints ``name,us_per_call,derived`` CSV rows (stdout), one per measurement.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from benchmarks import (
    cohort_bench,
    faults_bench,
    round_bench,
    schedule_bench,
    fig2_breakdown,
    fig3_memory,
    fig6_dropout_sweep,
    fig10_memory_vs_ratio,
    fig11_12_energy_traffic,
    fig13_14_ablations,
    fig15_noniid,
    kernel_bench,
    serve_bench,
    table1_overhead,
    table3_time_to_accuracy,
)
from repro.launch.compile_cache import enable_compile_cache

BENCHES = {
    "cohort": cohort_bench.run,
    "faults": faults_bench.run,
    "round": round_bench.run,
    "schedule": schedule_bench.run,
    "serve": serve_bench.run,
    "table1": table1_overhead.run,
    "fig2": fig2_breakdown.run,
    "fig3": fig3_memory.run,
    "table3": table3_time_to_accuracy.run,
    "fig6": fig6_dropout_sweep.run,
    "fig10": fig10_memory_vs_ratio.run,
    "fig11_12": fig11_12_energy_traffic.run,
    "fig13_14": fig13_14_ablations.run,
    "fig15": fig15_noniid.run,
    "kernels": kernel_bench.run,
}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true", help="reduced rounds/sweeps")
    ap.add_argument("--only", default="", help="comma-separated bench names")
    args = ap.parse_args()
    enable_compile_cache()

    names = [n.strip() for n in args.only.split(",") if n.strip()] or list(BENCHES)
    print("name,us_per_call,derived")
    failures = []
    for name in names:
        t0 = time.time()
        try:
            BENCHES[name](quick=args.quick)
            print(f"# {name}: done in {time.time()-t0:.1f}s", file=sys.stderr)
        except AssertionError as e:
            failures.append(name)
            print(f"{name}/CLAIM_VIOLATION,0.0,{e}")
        except Exception as e:  # noqa: BLE001
            failures.append(name)
            print(f"{name}/ERROR,0.0,{type(e).__name__}: {e}")
            traceback.print_exc(file=sys.stderr)
    if failures:
        print(f"# FAILED: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
