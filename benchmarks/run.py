"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig4,table1]

Each module prints its own CSV; this driver runs them all, times them, and
fails loudly if any paper-shape assertion breaks.
"""
import argparse
import importlib
import time
import traceback

from repro.launch.compile_cache import use_compile_cache

SUITES = [
    ("table1", "Table 1 — motivating sequence example"),
    ("fig4_cost_vs_tau", "Fig. 4 — τ vs migration cost (adhoc/SSM/MTM)"),
    ("fig5_ssm_runtime",
     "Fig. 5 — τ vs SSM planning time + numpy/jit backend scaling"),
    ("ssm_oracles", "Differential harness — all SSM solvers must agree"),
    ("fig6_pmc_time", "Fig. 6 — τ vs PMC precompute time"),
    ("fig7_tasks_m", "Fig. 7 — #tasks m vs cost & runtime"),
    ("fig8_window_response", "Fig. 8 — window size vs response time"),
    ("fig9_10_gamma", "Figs. 9/10 — γ vs cost & precompute"),
    ("fig11_live_migration", "Fig. 11 — live vs kill-restart"),
    ("fig12_fluid_vs_progressive",
     "Fig. 12 — five-strategy migration frontier incl. batched_fluid "
     "(m=10k, vectorized)"),
    ("fig13_controller",
     "Fig. 13 — closed-loop controller vs always/never-migrate"),
    ("migration_dryrun", "Dry-run — planner cost vs HLO collective bytes"),
    ("roofline_report", "Roofline — dry-run term table"),
]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    args = ap.parse_args(argv)
    use_compile_cache()
    only = None
    if args.only:
        only = {name for name in args.only.split(",") if name}
        known = {mod_name for mod_name, _ in SUITES}
        unknown = sorted(only - known)
        if unknown:
            raise SystemExit(
                f"--only: unknown suite(s) {unknown}; choose from "
                f"{sorted(known)}")
        if not only:
            raise SystemExit("--only: no suites selected")
    failures = []
    for mod_name, title in SUITES:
        if only and mod_name not in only:
            continue
        print(f"\n=== {title} [{mod_name}] " + "=" * 20)
        t0 = time.time()
        try:
            mod = importlib.import_module(f"benchmarks.{mod_name}")
            mod.main()
            print(f"--- {mod_name} ok in {time.time()-t0:.1f}s")
        except Exception as e:  # noqa: BLE001
            failures.append(mod_name)
            traceback.print_exc()
            print(f"--- {mod_name} FAILED in {time.time()-t0:.1f}s: {e}")
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")
    print("\nALL BENCHMARKS PASSED")


if __name__ == "__main__":
    main()
