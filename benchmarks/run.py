"""Run every benchmark; print ``name,us_per_call,derived`` CSV.

One module per paper figure/table (fig2a..fig9, table2, table5), the STREAM
Pallas kernels, the beyond-paper channelized-decode planner study, and the
roofline table derived from the dry-run artifacts.  A section that raises
is reported on stderr and the run exits 1 after the rest have run.
"""

import importlib
import sys
import traceback

MODULES = [
    "benchmarks.fig2a_load_latency",
    "benchmarks.fig2b_breakdown",
    "benchmarks.fig3_variance",
    "benchmarks.fig5_speedup",
    "benchmarks.fig6_distribution",
    "benchmarks.fig7_designs",
    "benchmarks.fig8_latency_sens",
    "benchmarks.fig9_utilization",
    "benchmarks.table2_designs",
    "benchmarks.table5_edp",
    "benchmarks.sweep_grid",
    "benchmarks.pareto_frontier",
    # lut_convergence resolves the shared default QueueLUT surface first,
    # so the LUT-backed sections after it (drift, harvest, serving,
    # designer) hit the bounded in-process layer instead of rebuilding.
    "benchmarks.lut_convergence",
    "benchmarks.drift_headline",
    "benchmarks.harvest_headline",
    "benchmarks.serving_capacity",
    "benchmarks.designer_opt",
    "benchmarks.memsim_speed",
    "benchmarks.stream_kernels",
    "benchmarks.channelized_decode",
    "benchmarks.roofline",
]

def main(argv=None) -> None:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module suffixes (e.g. "
                         "'fig2a_load_latency,table2_designs') -- the CI "
                         "smoke subset")
    args = ap.parse_args(argv)
    modules = MODULES
    if args.only:
        wanted = {m.strip() for m in args.only.split(",")}
        modules = [m for m in MODULES if m.split(".")[-1] in wanted]
        missing = wanted - {m.split(".")[-1] for m in modules}
        if missing:
            raise SystemExit(f"unknown benchmark modules: {sorted(missing)}")

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    print("name,us_per_call,derived")
    failures = 0
    for mod_name in modules:
        try:
            importlib.import_module(mod_name).main()
        except Exception:       # noqa: BLE001 -- report all benches
            failures += 1
            print(f"{mod_name},0.0,ERROR", file=sys.stderr)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
