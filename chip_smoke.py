"""Drive the DES -> QueueLUT -> planner path once on a TPU and check it.

    PYTHONPATH=src python chip_smoke.py            # one chip, phases 1-5
    PYTHONPATH=src python chip_smoke.py --chips 4  # the sharded LUT build

One process does everything; every phase calls the library or an entry
point's ``main(argv)`` in-process, so this process alone holds the chip.

1. device -- the first JAX device must be a TPU (there is no CPU
   fallback); the persistent compile cache is set up before any compile.
2. calibration -- ``coaxial.validate_calibration()`` at its defaults
   against the closed form of ``core/queueing.py``; ``ok`` must hold.
3. default LUT -- the default QueueLUT built cold on the chip, compared
   with a CPU build of a sub-grid in this process: both draw the same
   uniforms (the canonical stream contract), so only rounding differs.
   At every shared cell with a mean wait over 10 ns the mean wait must
   agree within 5% and the p90 wait within 10%, or within 3 standard
   errors (the ``coaxial.crosscheck_engines`` rule; the spread comes
   from CPU builds at other seeds); over those cells the median
   deviation must be within the same 5% / 10%.
4. headline solve -- the design sweep on both queue backends through the
   phase-3 LUT; the closed-form geomeans must match the published
   headline (4x 1.54, 2x 1.31, asym 1.81).
5. planner and designer -- ``repro.serving.plan`` and ``repro.designer``
   through their ``main(argv)``; both must return 0.

``--chips 4`` runs only the sharded check instead: the default LUT built
with ``devices=4`` and ``devices=1`` must give bitwise-equal tables.

Each phase prints its wall seconds, the DES lanes it ran, the requests
those lanes recorded and the device's ``peak_bytes_in_use``.
``REPRO_DES_STEPS`` caps every DES budget of this script.
The last line of standard output is one JSON object naming the device.
The script exits non-zero, with no such line, when JAX finds no TPU or
when any gate fails.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

#: Sub-grid of the default LUT grid rebuilt on the CPU as the reference:
#: 4 x 2 x 3 x 2 = 48 cells spanning every axis's range.
CPU_SUBGRID = dict(rho=(0.05, 0.45, 0.79, 0.93), kappa=(1.0, 3.2),
                   outstanding=(2.0, 24.0, 192.0), eta=(0.05, 1.0))
MEAN_TOL, P90_TOL, WAIT_FLOOR_NS = 0.05, 0.10, 10.0
#: Extra CPU seeds that measure each sub-grid cell's sampling spread.
SPREAD_SEEDS = 6
#: Published closed-form headline geomeans (two decimals).
HEADLINE = {"coaxial-4x": 1.54, "coaxial-2x": 1.31, "coaxial-asym": 1.81}
PLAN_ARGV = ["--arch", "stablelm-1.6b", "--slo-p99-ms", "500",
             "--trace", "synthetic-diurnal"]
DESIGNER_ARGV = ["--area-budget", "1.2", "--slo-ms", "500", "--iters", "3"]


def fail(why: str):
    print(f"chip_smoke: {why}", file=sys.stderr)
    raise SystemExit(1)


def gate(ok: bool, what: str) -> None:
    if not ok:
        fail(f"gate failed: {what}")


class Phases:
    """Times phases and counts the DES lanes and recorded requests each
    runs, by wrapping ``memsim.simulate_cells`` (every DES run of the
    library goes through it)."""

    def __init__(self, memsim, jax):
        self.jax, self.lanes, self.requests = jax, 0, 0.0
        inner = memsim.simulate_cells

        def counted(cha, *a, **kw):
            stats = inner(cha, *a, **kw)
            self.lanes += len(cha.rho) * int(kw.get("reps", 1))
            self.requests += float(stats.hist.sum())
            return stats

        memsim.simulate_cells = counted

    def run(self, name, fn, *args, **kwargs):
        self.lanes, self.requests = 0, 0.0
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        self.jax.block_until_ready(out)
        secs = time.perf_counter() - t0
        peak = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                for d in self.jax.local_devices()]
        print(f"phase {name}: seconds={secs} lanes={self.lanes} "
              f"requests={self.requests:.0f} peak_bytes_in_use="
              f"{peak[0] if len(peak) == 1 else peak}", flush=True)
        return out


def _budget(default: int) -> int:
    cap = os.environ.get("REPRO_DES_STEPS")
    return min(default, int(cap)) if cap else default


def calibration(ph, coaxial):
    val = ph.run("2 calibration", coaxial.validate_calibration,
                 steps=_budget(200_000))
    for a in val["anchors"]:
        print(f"  rho={a['rho']}: mean {a['des_mean_ns']:.2f} ns vs "
              f"{a['closed_mean_ns']:.2f} ({a['mean_err']:+.4f}), p90 "
              f"{a['des_p90_ns']:.2f} vs {a['closed_p90_ns']:.2f} "
              f"({a['p90_err']:+.4f})")
    print(f"  max |mean err| {val['max_abs_mean_err']:.4f} "
          f"(tol {val['mean_tol']}), max |p90 err| "
          f"{val['max_abs_p90_err']:.4f} (tol {val['p90_tol']}), "
          f"ok={val['ok']}")
    gate(val["ok"], "validate_calibration() is not ok")
    xc = ph.run("2b engine crosscheck rho=0.8", coaxial.crosscheck_engines,
                rhos=(0.8,), steps=_budget(200_000))
    a = xc["anchors"][0]
    print(f"  known C1 gap, not gated: event vs timestep at rho=0.8 mean "
          f"{a['mean_err']:+.4f}, p90 {a['p90_err']:+.4f} (ok={xc['ok']})")


def default_lut(ph, jax, np, queuelut, lutstore, se_k):
    os.environ.pop(lutstore.ENV_VAR, None)      # on-disk store off
    lutstore.clear_lut_cache()
    steps = _budget(queuelut.DEFAULT_STEPS)
    lut = ph.run("3 default LUT (cold)", queuelut.default_queue_lut,
                 steps=steps)
    gate(ph.lanes > 0, "the default LUT was not built by the DES")
    # Seed 0 draws the chip build's uniforms; the other seeds measure the
    # sampling spread of one table entry, which the comparison needs: a
    # rounding difference that moves one arrival across a 1-ns lattice
    # cell changes which candidates merge and re-draws their services,
    # so heavy-tailed cells decorrelate from the CPU's sample path.
    with jax.default_device(jax.devices("cpu")[0]):
        refs = ph.run("3b CPU reference sub-grid", lambda: [
            queuelut.build_queue_lut(**CPU_SUBGRID, steps=steps, seed=s)
            for s in range(1 + SPREAD_SEEDS)])
    grids = (queuelut.DEFAULT_RHO_GRID, queuelut.DEFAULT_KAPPA_GRID,
             queuelut.DEFAULT_OUTSTANDING_GRID, queuelut.DEFAULT_ETA_GRID)
    idx = np.ix_(*[[g.index(v) for v in sub]
                   for g, sub in zip(grids, CPU_SUBGRID.values())])
    cw = np.asarray(refs[0].wait_ns)
    mask = cw > WAIT_FLOOR_NS
    gate(mask.sum() >= 16, f"only {mask.sum()} sub-grid cells queue")
    coords = np.argwhere(mask)
    print(f"  TPU vs CPU over {int(mask.sum())} of {cw.size} cells with "
          f"mean wait > {WAIT_FLOOR_NS} ns")
    for stat, field, tol in (("mean", "wait_ns", MEAN_TOL),
                             ("p90", "p90_wait_ns", P90_TOL)):
        tpu = np.asarray(getattr(lut, field), np.float64)[idx][mask]
        cpu = np.asarray(getattr(refs[0], field), np.float64)[mask]
        # Standard error of the difference of two independent estimates.
        se = np.sqrt(2.0) * np.std(
            [np.asarray(getattr(r, field), np.float64)[mask]
             for r in refs[1:]], axis=0, ddof=1)
        rel = np.abs(tpu - cpu) / cpu
        z = np.abs(tpu - cpu) / np.maximum(se, 1e-9)
        print(f"  {stat}: cells bit-equal {int((tpu == cpu).sum())}, "
              f"max rel dev {rel.max():.6f}, median {np.median(rel):.6f}; "
              f"median CPU seed-to-seed rel spread "
              f"{np.median(se / np.sqrt(2.0) / cpu):.6f}")
        for i in np.argsort(rel)[::-1][:3]:
            c = [sub[j] for sub, j in zip(CPU_SUBGRID.values(), coords[i])]
            print(f"    rel dev {rel[i]:.6f} z {z[i]:.3f} at (rho, kappa, "
                  f"outstanding, eta)={tuple(c)}")
        bad = (rel > tol) & (z > se_k)
        gate(not bad.any(), f"TPU {stat} wait off the CPU by more than "
             f"{tol:.0%} and {se_k} standard errors at {int(bad.sum())} "
             f"cell(s)")
        gate(np.median(rel) <= tol, f"median TPU {stat} wait deviation "
             f"{np.median(rel):.6f} > {tol}")
    return lut


def headline(ph, coaxial, lut):
    spec = coaxial.sweep_spec(design=coaxial.all_designs(),
                              queue_model=("closed_form", "memsim"))
    sw = ph.run("4 headline solve", coaxial.solve_spec, spec, lut=lut)
    for name, want in HEADLINE.items():
        sys_ = coaxial.get_design(name)
        cf = sw.comparison(sys_, queue_model="closed_form").geomean_speedup
        ms = sw.comparison(sys_, queue_model="memsim").geomean_speedup
        print(f"  {name}: closed-form geomean {cf:.4f} (published {want}), "
              f"memsim geomean {ms:.4f}")
        gate(round(cf, 2) == want, f"{name} closed-form geomean {cf:.4f}")


def planner_designer(ph, queuelut):
    from repro import designer
    from repro.serving import capacity, plan
    rc = ph.run("5 capacity plan", plan.main, PLAN_ARGV + [
        "--steps", str(_budget(capacity.DEFAULT_STEPS))])
    gate(rc == 0, f"repro.serving.plan returned {rc}")
    rc = ph.run("5b designer", designer.main, DESIGNER_ARGV + [
        "--steps", str(_budget(queuelut.DEFAULT_STEPS))])
    gate(rc == 0, f"repro.designer returned {rc}")


def sharded(ph, np, queuelut):
    """devices=4 vs devices=1: bitwise-equal tables (each timed twice;
    the first build of each compiles)."""
    steps = _budget(queuelut.DEFAULT_STEPS)
    luts = {}
    for rnd in (1, 2):
        for ndev in (1, 4):
            luts[ndev] = ph.run(f"sharded LUT devices={ndev} run {rnd}",
                                queuelut.build_queue_lut, steps=steps,
                                devices=ndev)
    for f in ("wait_ns", "p90_wait_ns", "p99_wait_ns", "sigma_ns"):
        a, b = np.asarray(getattr(luts[1], f)), np.asarray(getattr(luts[4], f))
        equal = np.array_equal(a, b)
        print(f"  {f}: devices=4 == devices=1 bitwise: {equal}")
        gate(equal, f"devices=4 {f} differs from devices=1")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the devices=4 vs devices=1 LUT check")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no repro package under {ROOT / 'src'}: run from a checkout")
    sys.path.insert(0, str(ROOT / "src"))
    # Phase 3's reference build needs the CPU backend beside the chip.
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"needs a TPU; JAX found platform {dev.platform!r} "
             f"({dev.device_kind})")
    count = len(jax.devices())
    if count < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} devices, found "
             f"{count}")
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    warm = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"phase 1 device: platform={dev.platform} kind={dev.device_kind} "
          f"count={count} jax={jax.__version__} compile_cache={cache} "
          f"({warm} entries at start)", flush=True)

    import numpy as np
    from repro.core import coaxial, lutstore, memsim, queuelut
    ph = Phases(memsim, jax)
    if args.chips == 4:
        sharded(ph, np, queuelut)
    else:
        calibration(ph, coaxial)
        lut = default_lut(ph, jax, np, queuelut, lutstore,
                          coaxial.ENGINE_SE_K)
        headline(ph, coaxial, lut)
        planner_designer(ph, queuelut)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
