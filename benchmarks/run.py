# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
"""Benchmark harness.

    PYTHONPATH=src python -m benchmarks.run [--only PREFIX]

Each module reproduces one paper artifact (see DESIGN.md §8):
  activation_ratio → Tables 1–2   workload_shift → Fig 2
  demotion_curve   → Fig 3        quality        → Table 4
  serving_perf     → Figs 6–9     prompt_scaling → Fig 10
  kernels_bench    → (ours) Pallas kernel roofline check
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows = []

    def report(name, us_per_call, derived):
        rows.append((name, us_per_call, derived))
        print(f"{name},{us_per_call:.1f},{derived}")

    from benchmarks import (activation_ratio, demotion_curve, ep_scaling,
                            fault_tolerance, hierarchy, kernels_bench,
                            kv_reuse, obs_overhead, prompt_scaling, quality,
                            serving_perf, serving_sim, slo_serving,
                            spec_decode, workload_shift)
    suites = [
        ("activation_ratio", activation_ratio.run),
        ("workload_shift", workload_shift.run),
        ("demotion_curve", demotion_curve.run),
        ("quality", quality.run),
        ("serving_sim", serving_sim.run),
        ("serving_perf", serving_perf.run),
        ("slo_serving", slo_serving.run),
        ("kv_reuse", kv_reuse.run),
        ("ep_scaling", ep_scaling.run),
        ("hierarchy", hierarchy.run),
        ("fault_tolerance", fault_tolerance.run),
        ("spec_decode", spec_decode.run),
        ("obs_overhead", obs_overhead.run),
        ("prompt_scaling", prompt_scaling.run),
        ("kernels", kernels_bench.run),
        ("kernels_roofline", kernels_bench.run_roofline),
        ("kernels_flash", kernels_bench.run_flash),
    ]
    failures = 0
    print("name,us_per_call,derived")
    for name, fn in suites:
        if args.only and not name.startswith(args.only):
            continue
        t0 = time.perf_counter()
        try:
            fn(report)
            print(f"# {name}: done in {time.perf_counter() - t0:.1f}s",
                  file=sys.stderr)
        except Exception:
            failures += 1
            print(f"# {name}: FAILED", file=sys.stderr)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
