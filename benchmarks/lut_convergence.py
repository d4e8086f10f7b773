"""LUT store economics + grid convergence: the resolution endgame rows.

Three experiments on the persistent QueueLUT store
(:mod:`repro.core.lutstore`):

1. **Cold vs warm build** -- the default surface is built directly (one
   batched DES run, ``lut.build_cold_s``) and then re-resolved through
   the store (``lut.build_warm_s``); with ``$REPRO_LUT_CACHE`` set the
   warm read is a file load, bit-identical to the build
   (``lut.store_bitident``) and free of DES traces
   (``lut.warm_traces``).  Store regressions show up as the warm row
   drifting toward the cold one.

2. **Grid ladder** -- stride-coarsened versions of the default grids are
   resolved INCREMENTALLY off the full surface (every coarse cell is
   donated, zero DES) and judged two ways: interpolation error against
   one batched direct-DES probe run at interval midpoints, and
   fixed-point drift of the two headline metrics (fig7 geomean speedup,
   wave-model token p99) against the full-grid surface.

3. **Adaptive refinement** -- :func:`repro.core.queuelut.
   refine_queue_lut` from the coarse grids, reported round by round; the
   final round's metric deltas are the ISSUE's convergence criterion
   (< 1% on the last refinement step).  ``report --section lut`` renders
   the same trajectory as markdown.

All DES work honours ``REPRO_DES_STEPS``/``REPRO_DES_ENGINE``; because
this module resolves the SAME default-surface key the drift / harvest /
designer / serving sections use, running it first in ``benchmarks.run``
warms the in-process layer (and the store) for everything after it.
"""

import time

import numpy as np

from benchmarks.common import des_budget, des_engine, emit, emit_derived
from repro.core import hw, lutstore, memsim, queuelut

#: Grid-ladder strides over the default grids.  Stride 2 is plain
#: ``g[::2]`` -- the refinement loop's starting grids, so its surface is
#: shared (same store key); stride 4 keeps each axis's endpoints so the
#: hull does not shrink.
LADDER_STRIDES = (4, 2)


def _coarsen(grid: tuple, stride: int) -> tuple:
    if stride == 2:
        return tuple(grid[::2])
    sub = list(grid[::stride])
    if sub[-1] != grid[-1]:
        sub.append(grid[-1])
    return tuple(sub)


def ladder_grids(stride: int) -> dict:
    """Stride-coarsened default grids (stride 1 = the default surface)."""
    g = dict(rho=queuelut.DEFAULT_RHO_GRID,
             kappa=queuelut.DEFAULT_KAPPA_GRID,
             outstanding=queuelut.DEFAULT_OUTSTANDING_GRID,
             eta=queuelut.DEFAULT_ETA_GRID)
    if stride == 1:
        return g
    return {k: _coarsen(v, stride) for k, v in g.items()}


def bench_budget() -> tuple:
    """(steps, engine) of the shared bench default surface."""
    engine = des_engine(queuelut.DEFAULT_ENGINE)
    return des_budget(queuelut.DEFAULT_STEPS, engine), engine


def cold_warm() -> dict:
    """Cold direct build vs store-backed warm resolution of the default
    surface; returns the row dict (times s, traces, bit-identity)."""
    steps, engine = bench_budget()
    t0 = time.perf_counter()
    cold = queuelut.build_queue_lut(steps=steps, engine=engine)
    cold_s = time.perf_counter() - t0
    # Resolve through the store: persists the surface on first contact.
    queuelut.default_queue_lut(steps=steps, engine=engine)
    queuelut.clear_lut_cache()
    n0 = memsim.sim_trace_count()
    t0 = time.perf_counter()
    warm = queuelut.default_queue_lut(steps=steps, engine=engine)
    warm_s = time.perf_counter() - t0
    warm_traces = memsim.sim_trace_count() - n0
    bitident = all(np.array_equal(np.asarray(a), np.asarray(b))
                   for a, b in zip(cold, warm) if a is not None)
    return dict(cold_s=cold_s, warm_s=warm_s, warm_traces=warm_traces,
                bitident=bitident, lut=warm)


def _probe_points(stride: int = 2) -> list:
    """Interval-midpoint probes (anchored off-axis) over the default
    grids -- every ``stride``-th interval per axis, to bound the direct
    DES probe batch."""
    pts = []
    for axis, grid in ladder_grids(1).items():
        for j in range(0, len(grid) - 1, stride):
            c = dict(queuelut.PROBE_ANCHOR)
            c.pop("harvest_duty")
            c[axis] = queuelut._midpoint(axis, grid[j], grid[j + 1])
            pts.append(c)
    return pts


def ladder_rows(finest: "queuelut.QueueLUT", steps: int,
                engine: str) -> list:
    """One row per rung: cells, interpolation error vs direct DES,
    fixed-point drift of both headline metrics vs the finest surface."""
    probes = _probe_points()
    names = ("rho", "kappa", "outstanding", "eta")
    coords = np.asarray([[p[n] for n in names] for p in probes])
    cha = memsim.stack_channels(
        [memsim.ChannelConfig(**p) for p in probes])
    stats = memsim.simulate_cells(
        cha, steps=int(steps), seed=0, reps=queuelut.DEFAULT_REPS,
        engine=engine,
        stream_ids=queuelut.cell_stream_ids(names, coords),
        chunk=memsim.canonical_chunk(engine))
    des_wait = np.maximum(
        np.asarray(stats.mean_ns, np.float64) - hw.DRAM_SERVICE_NS,
        0.0)
    ref = queuelut.headline_metrics(finest)
    rows = []
    for stride in LADDER_STRIDES + (1,):
        lut = (finest if stride == 1 else queuelut.resolve_lut(
            **ladder_grids(stride), steps=steps, engine=engine,
            base_lut=finest))       # all cells donated: zero DES
        lut_wait = np.asarray([float(lut.wait(
            p["rho"], p["kappa"], p["outstanding"], p["eta"]))
            for p in probes])
        # Same normalization as refine_queue_lut: relative to the total
        # access latency the solver consumes.
        err = (np.abs(lut_wait - des_wait)
               / (des_wait + hw.DRAM_SERVICE_NS))
        m = queuelut.headline_metrics(lut)
        rows.append(dict(
            stride=stride,
            cells=int(np.prod([len(g) for g in
                               ladder_grids(stride).values()])),
            interp_err_max=float(err.max()),
            interp_err_mean=float(err.mean()),
            gm=m["geomean_speedup"], tok99_ms=m["token_p99_ms"],
            gm_drift_pct=100.0 * (m["geomean_speedup"]
                                  / ref["geomean_speedup"] - 1.0),
            tok99_drift_pct=100.0 * (m["token_p99_ms"]
                                     / ref["token_p99_ms"] - 1.0)))
    return rows


def refine_history(steps: int, engine: str) -> list:
    """The adaptive loop's round-by-round trajectory (ISSUE criterion:
    final-step metric deltas < 1%)."""
    _, hist = queuelut.refine_queue_lut(steps=steps, engine=engine,
                                        tol=0.01)
    return hist


def main():
    steps, engine = bench_budget()
    root = lutstore.cache_dir()
    emit_derived("lut.store", "disabled" if root is None else "enabled")
    cw = cold_warm()
    emit("lut.build_cold_s", cw["cold_s"] * 1e6, f"{cw['cold_s']:.3f}")
    emit("lut.build_warm_s", cw["warm_s"] * 1e6, f"{cw['warm_s']:.3f}")
    emit_derived("lut.warm_traces", cw["warm_traces"])
    emit_derived("lut.store_bitident", int(cw["bitident"]))
    for r in ladder_rows(cw["lut"], steps, engine):
        tag = f"lut.ladder.s{r['stride']}"
        emit_derived(f"{tag}.cells", r["cells"])
        emit_derived(f"{tag}.interp_err_max", f"{r['interp_err_max']:.4f}")
        emit_derived(f"{tag}.gm_drift_pct", f"{r['gm_drift_pct']:+.2f}")
        emit_derived(f"{tag}.tok99_drift_pct",
                     f"{r['tok99_drift_pct']:+.2f}")
    hist = refine_history(steps, engine)
    for r in hist:
        extra = ("" if "d_geomean" not in r else
                 f"|d_gm={r['d_geomean']:.4f}|d_p99={r['d_token_p99']:.4f}")
        emit_derived(
            f"lut.refine.round{r['round']}",
            f"cells={r['cells']}|gm={r['geomean_speedup']:.4f}"
            f"|tok99={r['token_p99_ms']:.1f}ms"
            f"|err={r['worst_err']:.3f}{extra}")
    final = hist[-1]
    emit_derived("lut.refine.final_d_gm_pct",
                 f"{100.0 * final.get('d_geomean', 0.0):.3f}")
    emit_derived("lut.refine.final_d_tok99_pct",
                 f"{100.0 * final.get('d_token_p99', 0.0):.3f}")
    emit_derived("lut.refine.converged", int(final["converged"]))


if __name__ == "__main__":
    main()
