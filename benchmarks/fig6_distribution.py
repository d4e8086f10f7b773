"""Fig 6: latency distributions -- group means/stdevs + streamcluster CDF
(baseline vs COAXIAL channel at matched per-channel load).

The 6b comparison slices ONE shared distribution sweep (a rho x
cxl_lat_ns grid, single compile) by named coordinate instead of an
ad-hoc config list.
"""

import numpy as np

from benchmarks.common import des_budget, emit, time_call
from repro.core import coaxial
from repro.core.workloads import WORKLOADS


def main():
    cmp = coaxial.evaluate(coaxial.COAXIAL_4X)
    suites = sorted({w.suite for w in WORKLOADS})
    for suite in suites:
        idx = [i for i, w in enumerate(WORKLOADS) if w.suite == suite]
        emit(f"fig6a.{suite}.base_mean_ns", 0.0,
             f"{np.mean(cmp.base.latency_ns[idx]):.1f}")
        emit(f"fig6a.{suite}.base_stdev_ns", 0.0,
             f"{np.mean(cmp.base.sigma_ns[idx]):.1f}")
        emit(f"fig6a.{suite}.coax_mean_ns", 0.0,
             f"{np.mean(cmp.res.latency_ns[idx]):.1f}")
        emit(f"fig6a.{suite}.coax_stdev_ns", 0.0,
             f"{np.mean(cmp.res.sigma_ns[idx]):.1f}")

    # Streamcluster CDF: DDR channel at its baseline rho vs a COAXIAL
    # channel at rho/4 with the 30ns premium -- two named cells of one
    # batched rho x cxl_lat_ns distribution sweep.
    i = [w.name for w in WORKLOADS].index("streamcluster")
    rho_b = float(cmp.base.rho[i])
    steps = des_budget(150_000)
    us, sw = time_call(lambda: coaxial.distribution_sweep(
        rho=(rho_b, rho_b / 4), cxl_lat_ns=(0.0, 30.0),
        steps=steps, reps=max(1, 600_000 // steps)), iters=1)
    cells = dict(ddr=sw.sel(rho=rho_b, cxl_lat_ns=0.0),
                 coaxial=sw.sel(rho=rho_b / 4, cxl_lat_ns=30.0))
    for tag, stats in cells.items():
        emit(f"fig6b.streamcluster.{tag}.p50_ns", us / 2,
             f"{float(stats.p50_ns):.0f}")
        emit(f"fig6b.streamcluster.{tag}.p90_ns", us / 2,
             f"{float(stats.p90_ns):.0f}")
        emit(f"fig6b.streamcluster.{tag}.stdev_ns", us / 2,
             f"{float(stats.stdev_ns):.0f}")


if __name__ == "__main__":
    main()
