"""The gradient-based designer as a benchmark section (repro.core.designer).

Runs one end-to-end optimize: start at the tail-aware Pareto knee,
projected-gradient-ascend (channels, LLC) under the default area budget
and the serving SLO, re-verify the optimum with one direct event-driven
DES run.  Emits the returned design, its cost/speedup/tail numbers, the
model-vs-DES verification error, and the one-trace invariant -- the
rows that show ascent-quality drift.  The LUT build honors
``REPRO_DES_STEPS`` like every other DES-backed section.
"""

from benchmarks.common import des_budget, des_engine, emit, emit_derived, \
    time_call
from repro.core import designer, queuelut

AREA_BUDGET = 1.2
SLO_MS = 500.0
ARCH = "stablelm-1.6b"


def main():
    engine = des_engine("event")
    steps = des_budget(queuelut.DEFAULT_STEPS, engine)
    us, res = time_call(
        lambda: designer.optimize_design(
            area_budget=AREA_BUDGET, slo_ms=SLO_MS, arch=ARCH,
            steps=steps, engine=engine),
        warmup=0, iters=1)
    emit("designer.optimize", us, res.iters)
    d = res.design
    emit_derived("designer.start", f"{res.start.name}@"
                 f"{res.start.llc_mb_per_core:g}MB")
    emit_derived("designer.opt.channels",
                 f"{float(d.dram_channels):.3f}")
    emit_derived("designer.opt.llc_mb", f"{float(d.llc_mb_per_core):.3f}")
    emit_derived("designer.opt.rel_area", f"{res.rel_area:.3f}")
    emit_derived("designer.opt.gm_speedup", f"{res.gm_speedup:.3f}")
    emit_derived("designer.opt.token_p99_ms", f"{res.token_p99_ms:.2f}")
    emit_derived("designer.meets", int(res.meets_budget and res.meets_slo))
    emit_derived("designer.converged", int(res.converged))
    emit_derived("designer.verify.rel_err",
                 f"{res.verify['rel_err']:+.4f}")
    emit_derived("designer.verify.ok", int(res.verify["ok"]))
    emit_derived("designer.traces", designer.designer_trace_count())


if __name__ == "__main__":
    main()
