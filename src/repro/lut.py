"""CLI: ``python -m repro.lut`` -- manage the persistent QueueLUT store.

Subcommands::

    python -m repro.lut prebuild [--harvest] [--engine event] [--refine]
    python -m repro.lut inspect
    python -m repro.lut gc [--older-than-days N | --all]

``prebuild`` resolves the default-grid surface(s) through the store
(``$REPRO_LUT_CACHE``; see :mod:`repro.core.lutstore`) and prints, per
surface, the resolution wall-clock and how many DES traces it cost -- a
warm read prints ``traces=0``.  Run it once in an image build or a CI
cache-seeding step and every later ``repro.designer`` /
``repro.serving.plan`` / test session starts warm.  ``--refine`` runs
:func:`repro.core.queuelut.refine_queue_lut` instead, printing the
round-by-round convergence trajectory (each round's grown grid is itself
stored, so refinement also seeds the store).

``inspect`` lists every stored surface with its build meta; ``gc`` drops
quarantined artifacts plus entries that are stale (fingerprint mismatch)
or older than ``--older-than-days`` (``--all`` empties the store).
"""

from __future__ import annotations

import argparse
import time

from repro.core import lutstore, memsim, queuelut


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m repro.lut",
        description="prebuild / inspect / gc the on-disk QueueLUT store")
    sub = p.add_subparsers(dest="cmd", required=True)

    pb = sub.add_parser("prebuild",
                        help="resolve default surfaces into the store")
    pb.add_argument("--engine", choices=memsim.ENGINES, action="append",
                    help="engine(s) to build for (default: event)")
    pb.add_argument("--steps", type=int, default=queuelut.DEFAULT_STEPS)
    pb.add_argument("--seed", type=int, default=0)
    pb.add_argument("--reps", type=int, default=queuelut.DEFAULT_REPS)
    pb.add_argument("--harvest", action="store_true",
                    help="also build the 5-axis harvesting surface")
    pb.add_argument("--refine", action="store_true",
                    help="run the adaptive refinement loop instead of "
                         "the fixed default grid")
    pb.add_argument("--tol", type=float, default=0.01,
                    help="refinement convergence tolerance (rel.)")

    sub.add_parser("inspect", help="list stored surfaces")

    g = sub.add_parser("gc", help="drop stale/quarantined entries")
    g.add_argument("--older-than-days", type=float, default=None)
    g.add_argument("--all", action="store_true",
                   help="empty the store entirely")
    return p


def _fmt_bytes(n: int) -> str:
    return f"{n / 1024:.0f} KiB" if n < 1 << 20 else f"{n / 1e6:.1f} MB"


def _prebuild(args) -> int:
    if lutstore.cache_dir() is None:
        print(f"WARNING: ${lutstore.ENV_VAR} is unset -- surfaces are "
              "built but not persisted")
    engines = tuple(dict.fromkeys(args.engine or ["event"]))
    harvests = (False, True) if args.harvest else (False,)
    if args.refine:
        for engine in engines:
            lut, hist = queuelut.refine_queue_lut(
                steps=args.steps, seed=args.seed, reps=args.reps,
                engine=engine, tol=args.tol)
            for r in hist:
                extra = ("" if "d_geomean" not in r else
                         f" d_gm={r['d_geomean']:.4f} "
                         f"d_p99={r['d_token_p99']:.4f}")
                print(f"refine[{engine}] round {r['round']}: "
                      f"shape={r['shape']} cells={r['cells']} "
                      f"gm={r['geomean_speedup']:.4f} "
                      f"tok99={r['token_p99_ms']:.1f}ms "
                      f"worst_err={r['worst_err']:.3f} "
                      f"{r['seconds']:.1f}s{extra}")
            print(f"refine[{engine}]: "
                  + ("converged" if hist[-1]["converged"]
                     else "round budget exhausted"))
        return 0
    for engine in engines:
        for harvest in harvests:
            t0, n0 = time.perf_counter(), memsim.sim_trace_count()
            lut = queuelut.default_queue_lut(
                steps=args.steps, seed=args.seed, reps=args.reps,
                engine=engine, harvest=harvest)
            dt = time.perf_counter() - t0
            traces = memsim.sim_trace_count() - n0
            import numpy as np
            shape = tuple(np.shape(np.asarray(lut.wait_ns)))
            print(f"prebuild engine={engine} harvest={harvest}: "
                  f"shape={shape} {dt:.2f}s traces={traces}"
                  + (" (warm)" if traces == 0 else ""))
    return 0


def _inspect() -> int:
    root = lutstore.cache_dir()
    if root is None:
        print(f"${lutstore.ENV_VAR} is unset -- no store")
        return 1
    rows = lutstore.entries()
    print(f"store {root}: {len(rows)} surface(s), fingerprint "
          f"{lutstore.mechanism_fingerprint()[:12]}")
    fp = lutstore.mechanism_fingerprint()
    for e in rows:
        stale = "" if e.get("fingerprint") == fp else "  [STALE]"
        print(f"  {e['path'].rsplit('/', 1)[-1]}  "
              f"{_fmt_bytes(e['bytes'])}  engine={e.get('engine', '?')} "
              f"steps={e.get('steps', '?')} shape={e.get('shape', '?')}"
              f"{stale}")
    return 0


def _gc(args) -> int:
    out = lutstore.gc(max_age_days=args.older_than_days,
                      everything=args.all)
    print(f"gc: removed {out['removed']} file(s), "
          f"freed {_fmt_bytes(out['bytes'])}")
    return 0


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.cmd == "prebuild":
        return _prebuild(args)
    if args.cmd == "inspect":
        return _inspect()
    return _gc(args)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
