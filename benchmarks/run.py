# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark suite entry point: every paper table/figure + beyond-paper runs.

Usage:  PYTHONPATH=src python -m benchmarks.run [--only substring] [--smoke]

--smoke runs every suite in its reduced mode (smaller grids / horizons /
epoch counts — each module's ``run(smoke=True)``), the same modes the CI
bench-smoke job exercises; a full pass in minutes instead of hours.
Serving-side suites route through the unified engine (and its compiled
backend where the contender is table-static); solver-side suites route
through the batched sweep engine.
"""
from __future__ import annotations

import argparse
import inspect
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="substring filter on module names")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced mode for every benchmark (CI-sized)")
    args = ap.parse_args()

    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()

    from . import (
        appE_structure_breaks,
        degraded_frontier,
        perf_ablation,
        fig3_policies,
        fig4_cost,
        fig5_tradeoff,
        fig6_percentiles,
        fig7_ideal_parallel,
        fig8_log_energy,
        fig9_cov,
        fig10_abstract_cost,
        fleet_frontier,
        kernel_micro,
        mmpp_bursty,
        roofline_report,
        sweep_scaling,
        table3_iteration_algos,
        tpu_profile_scenario,
    )

    suites = [
        ("fig3_policies", fig3_policies.run),
        ("fig4_cost", fig4_cost.run),
        ("fig5_tradeoff", fig5_tradeoff.run),
        ("fig6_percentiles", fig6_percentiles.run),
        ("fig7_ideal_parallel", fig7_ideal_parallel.run),
        ("fig8_log_energy", fig8_log_energy.run),
        ("fig9_cov", fig9_cov.run),
        ("fig10_abstract_cost", fig10_abstract_cost.run),
        ("sweep_scaling", sweep_scaling.run),
        ("table3_iteration_algos", table3_iteration_algos.run),
        ("appE_structure_breaks", appE_structure_breaks.run),
        ("tpu_profile_scenario", tpu_profile_scenario.run),
        ("mmpp_bursty", mmpp_bursty.run),
        ("fleet_frontier", fleet_frontier.run),
        ("degraded_frontier", degraded_frontier.run),
        ("kernel_micro", kernel_micro.run),
        ("roofline_report", roofline_report.run),
        ("perf_ablation", perf_ablation.run),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in suites:
        if args.only and args.only not in name:
            continue
        kw = {}
        if args.smoke:
            if "smoke" not in inspect.signature(fn).parameters:
                raise SystemExit(
                    f"{name}.run() has no reduced mode; every benchmark "
                    "must accept smoke= (see --smoke)"
                )
            kw["smoke"] = True
        t0 = time.perf_counter()
        try:
            fn(**kw)
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{name},nan,ERROR:{type(e).__name__}:{e}", flush=True)
            traceback.print_exc(file=sys.stderr)
        print(
            f"# {name} finished in {time.perf_counter() - t0:.1f}s",
            file=sys.stderr,
            flush=True,
        )
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
