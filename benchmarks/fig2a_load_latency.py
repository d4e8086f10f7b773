"""Fig 2a: DDR5-4800 load-latency curve -- parametric model vs DES memsim.

Paper anchors: 3x average latency at 50% load, 4x at 60%; p90 4.7x / 7.1x.

Both curves come out of ONE batched distribution sweep
(``coaxial.validate_calibration``), which also cross-checks the DES
against the closed form; the per-anchor deltas are emitted as
``fig2a.crosscheck.*`` rows so calibration drift surfaces in the CI
report.  ``REPRO_DES_ENGINE=event`` (the CI smoke setting) runs the
sweep on the per-request event engine, which raises the effective
sample count at unchanged CI time; the engine used is emitted as a row.
"""

from benchmarks.common import des_budget, des_engine, emit, time_call
from repro.core import coaxial, queueing


def main():
    rhos = (0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9)
    engine = des_engine()
    steps = des_budget(200_000, engine)
    us, val = time_call(
        lambda: coaxial.validate_calibration(
            rhos=rhos, steps=steps, engine=engine,
            reps=max(2, min(64, 9_600_000 // steps))),
        iters=1)
    per = us / len(rhos)
    emit("fig2a.engine", 0.0, engine)
    for a in val["anchors"]:
        r = a["rho"]
        emit(f"fig2a.rho{r:.1f}.param_mean_ns", per,
             f"{a['closed_mean_ns']:.1f}")
        emit(f"fig2a.rho{r:.1f}.des_mean_ns", per, f"{a['des_mean_ns']:.1f}")
        emit(f"fig2a.rho{r:.1f}.param_p90_ns", per,
             f"{a['closed_p90_ns']:.1f}")
        emit(f"fig2a.rho{r:.1f}.des_p90_ns", per, f"{a['des_p90_ns']:.1f}")
    # Cross-check rows: param-vs-DES relative deltas per anchor (percent).
    for a in val["anchors"]:
        r = a["rho"]
        emit(f"fig2a.crosscheck.rho{r:.1f}.mean_delta_pct", 0.0,
             f"{100.0 * a['mean_err']:.1f}")
        emit(f"fig2a.crosscheck.rho{r:.1f}.p90_delta_pct", 0.0,
             f"{100.0 * a['p90_err']:.1f}")
        emit(f"fig2a.crosscheck.rho{r:.1f}.stdev_delta_pct", 0.0,
             f"{100.0 * a['stdev_err']:.1f}")
    emit("fig2a.crosscheck.max_abs_mean_err_pct", 0.0,
         f"{100.0 * val['max_abs_mean_err']:.1f}")
    emit("fig2a.crosscheck.max_abs_p90_err_pct", 0.0,
         f"{100.0 * val['max_abs_p90_err']:.1f}")
    # NOTE: this bench sweeps rho up to 0.9 for the curve; the gated
    # cross-check envelope (val["ok"]) covers rho <= 0.8, so only the
    # per-anchor and max-delta rows are emitted here -- the gate itself
    # is enforced at the validated anchors in tests.
    emit("fig2a.crosscheck.max_abs_stdev_err_pct", 0.0,
         f"{100.0 * val['max_abs_stdev_err']:.1f}")
    emit("fig2a.anchor.3x_at_50pct", 0.0,
         f"{float(queueing.avg_latency_ns(0.5)) / 40.0:.2f}")
    emit("fig2a.anchor.4x_at_60pct", 0.0,
         f"{float(queueing.avg_latency_ns(0.6)) / 40.0:.2f}")


if __name__ == "__main__":
    main()
