"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. ``--full`` runs paper-scale
durations; default is the quick CI-sized pass.
"""
from __future__ import annotations

import argparse
import sys
import time


MODULES = [
    "table1_completion",
    "fig2_knee",
    "fig4_analytic",
    "fig7_efficacy",
    "fig9_schedulers",
    "fig10_fairness",
    "fig11_multiplex",
    "fig12_cluster",
    "roofline",
    "kernels_micro",
    "bench_decode",
    "bench_pool",
    "bench_gateway",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="",
                    help="comma-separated module subset")
    args, _ = ap.parse_known_args()
    only = set(args.only.split(",")) if args.only else None
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    print("name,us_per_call,derived")
    failures = 0
    for name in MODULES:
        if only and name not in only:
            continue
        mod = __import__(f"benchmarks.{name}", fromlist=["run"])
        t0 = time.time()
        try:
            rows = mod.run(quick=not args.full)
        except Exception as e:  # noqa: BLE001
            print(f"{name}/ERROR,0,{type(e).__name__}: {e}", flush=True)
            failures += 1
            continue
        for row_name, us, derived in rows:
            print(f"{row_name},{us:.1f},{derived}", flush=True)
        print(f"{name}/wall_s,{(time.time()-t0)*1e6:.0f},ok", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
