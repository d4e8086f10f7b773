"""Run every benchmark; print ``name,us_per_call,derived`` CSV.

One module per paper figure/table (fig2a..fig9, table2, table5), the STREAM
Pallas kernels, the beyond-paper channelized-decode planner study, and the
roofline table derived from the dry-run artifacts.

Every run also writes a versioned ``BENCH_<rev>.json`` trajectory point
at the repo root (override with ``--bench-json``, disable with
``--no-bench-json``); dirty working trees get ``BENCH_<rev>-dirty<n>``
suffixes so iterating locally accumulates points instead of clobbering
one: per-section wall-clock, emitted-row and
DES jit-trace counts, every CSV row, and the environment knobs that shaped
the run (device count, ``REPRO_DES_STEPS``/``_ENGINE``/``_DEVICES``,
compile-cache dir).  ``report.py --section bench`` diffs the newest two
points, so benchmark trajectory -- speedups drifting, sections slowing,
trace counts creeping -- is a reviewable artifact, not a memory.
"""

import importlib
import json
import os
import subprocess
import sys
import time
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

#: Default home of the ``BENCH_<rev>.json`` history: the repo root, so
#: trajectory points are committed alongside the code they measure
#: (``benchmarks/results/`` was never checked in, so the history always
#: started empty there).
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def git_rev() -> str:
    """Short HEAD revision, or ``nogit`` outside a checkout."""
    try:
        out = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                             capture_output=True, text=True, check=True,
                             cwd=os.path.dirname(os.path.abspath(__file__)))
        return out.stdout.strip() or "nogit"
    except Exception:       # noqa: BLE001 -- any git failure means nogit
        return "nogit"


def git_dirty() -> bool:
    """True when the working tree differs from HEAD."""
    try:
        out = subprocess.run(["git", "status", "--porcelain"],
                             capture_output=True, text=True, check=True,
                             cwd=os.path.dirname(os.path.abspath(__file__)))
        return bool(out.stdout.strip())
    except Exception:       # noqa: BLE001 -- any git failure means clean
        return False


def bench_path(where: str, rev: str, dirty: bool = False) -> str:
    """Resolve ``--bench-json`` (a dir or a ``.json`` path) to a file.

    A clean rev maps to ``BENCH_<rev>.json`` (re-running the same
    commit legitimately refreshes its point); a dirty tree gets the
    first free ``BENCH_<rev>-dirty<n>.json`` so successive local edits
    accumulate trajectory points instead of overwriting one.
    """
    if where.endswith(".json"):
        return where
    if not dirty:
        return os.path.join(where, f"BENCH_{rev}.json")
    n = 1
    while os.path.exists(os.path.join(where,
                                      f"BENCH_{rev}-dirty{n}.json")):
        n += 1
    return os.path.join(where, f"BENCH_{rev}-dirty{n}.json")


def main(argv=None) -> None:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module suffixes (e.g. "
                         "'fig2a_load_latency,table2_designs') -- the CI "
                         "smoke subset")
    ap.add_argument("--bench-json", default=BENCH_DIR,
                    help="directory (or explicit .json path) for the "
                         "BENCH_<rev>.json trajectory point")
    ap.add_argument("--no-bench-json", action="store_true",
                    help="skip writing the trajectory point")
    args = ap.parse_args(argv)
    modules = MODULES
    if args.only:
        wanted = {m.strip() for m in args.only.split(",")}
        modules = [m for m in MODULES if m.split(".")[-1] in wanted]
        missing = wanted - {m.split(".")[-1] for m in modules}
        if missing:
            raise SystemExit(f"unknown benchmark modules: {sorted(missing)}")

    from benchmarks import common
    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    lut_cache = common.enable_lut_cache()

    import jax
    from repro.core import memsim

    print("name,us_per_call,derived")
    sections, all_rows = {}, []
    t_start = time.perf_counter()
    failures = 0
    for mod_name in modules:
        name = mod_name.split(".")[-1]
        common.ROWS = rows = []
        tr0 = {e: memsim.sim_trace_count(e) for e in memsim.ENGINES}
        t0 = time.perf_counter()
        try:
            mod = importlib.import_module(mod_name)
            mod.main()
            status = "ok"
        except Exception:       # noqa: BLE001 -- report all benches
            failures += 1
            status = "error"
            print(f"{mod_name},0.0,ERROR", file=sys.stderr)
            traceback.print_exc()
        finally:
            common.ROWS = None
        sections[name] = dict(
            status=status,
            seconds=round(time.perf_counter() - t0, 3),
            rows=len(rows),
            traces={e: memsim.sim_trace_count(e) - tr0[e]
                    for e in memsim.ENGINES})
        all_rows.extend(list(r) for r in rows)

    if not args.no_bench_json:
        rev = git_rev()
        path = bench_path(args.bench_json, rev, dirty=git_dirty())
        base = os.path.basename(path)
        if base.startswith("BENCH_") and base.endswith(".json"):
            rev = base[len("BENCH_"):-len(".json")]
        point = dict(
            rev=rev,
            unix_time=int(time.time()),
            env=dict(
                devices=len(jax.devices()),
                REPRO_DES_STEPS=os.environ.get("REPRO_DES_STEPS"),
                REPRO_DES_ENGINE=os.environ.get("REPRO_DES_ENGINE"),
                REPRO_DES_DEVICES=os.environ.get("REPRO_DES_DEVICES"),
                compile_cache=cache_dir,
                lut_cache=lut_cache,
                only=args.only),
            totals=dict(seconds=round(time.perf_counter() - t_start, 3),
                        rows=len(all_rows), failures=failures,
                        traces={e: memsim.sim_trace_count(e)
                                for e in memsim.ENGINES}),
            sections=sections,
            rows=all_rows)
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump(point, f, indent=1)
        print(f"bench json: {path}", file=sys.stderr)

    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
