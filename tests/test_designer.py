"""The projected-gradient designer and the tail-aware frontier.

Pins the optimizer's contracts:

  * the generic ``projected_ascent`` driver converges to the known
    optimum of an unconstrained toy problem (pure Python, no DES);
  * the projection NEVER lets an iterate leave the box or the
    area-budget feasible set (bisection back to the last feasible
    point), and an infeasible start is refused loudly;
  * end-to-end ``optimize_design`` returns a design inside the budget
    whose p99, re-verified by a direct ``engine="event"`` run at the
    returned point, meets the SLO within the calibration tolerance;
  * the whole ascent costs at most ONE jit trace of the objective
    (``designer_trace_count``), and a second run re-uses the cache;
  * ``SweepResult.pareto(tail=True)`` ranks by (area, mean speedup,
    p99) and refuses the closed form (whose tail is NaN);
  * the ``python -m repro.designer`` CLI exits 0 on a meeting design.
"""

import numpy as np
import pytest

from repro.core import coaxial, designer, queuelut
from repro.core.cpu_model import COAXIAL_2X, COAXIAL_4X, DDR_BASELINE
from repro.core.designer import make_projector, projected_ascent

#: Reduced DES budget for the shared LUT (structure identical to the
#: benchmark build).  Built through ``default_queue_lut`` with the SAME
#: keyword layout the designer uses, so the CLI smoke test below hits
#: the lru cache instead of building a second surface.
LUT_STEPS = 8_000


@pytest.fixture(scope="module")
def lut():
    return queuelut.default_queue_lut(steps=LUT_STEPS, engine="event")


class TestProjectedAscent:
    BOX = {"a": (0.0, 6.0), "b": (-2.0, 2.0)}
    WIDTHS = {"a": 1.0, "b": 1.0}

    @staticmethod
    def _toy_vg(x):
        # Concave quadratic with its unconstrained optimum at (3, 1),
        # strictly inside the box: the known knee of the toy problem.
        val = -((x["a"] - 3.0) ** 2) - (x["b"] - 1.0) ** 2
        g = {"a": -2.0 * (x["a"] - 3.0), "b": -2.0 * (x["b"] - 1.0)}
        return (val, {}), g

    def test_converges_to_known_optimum(self):
        clip = lambda x, prev: {k: float(np.clip(v, *self.BOX[k]))
                                for k, v in x.items()}
        x, traj, converged = projected_ascent(
            {"a": 0.5, "b": -1.5}, self._toy_vg, clip,
            widths=self.WIDTHS, lr=0.3, iters=100, tol=1e-5)
        assert converged
        assert x["a"] == pytest.approx(3.0, abs=1e-2)
        assert x["b"] == pytest.approx(1.0, abs=1e-2)
        # One objective evaluation per recorded iterate, start included.
        assert len(traj) >= 2
        assert traj[-1]["objective"] >= traj[0]["objective"]

    def test_projection_keeps_iterates_inside_budget_box(self):
        box = {"dram_channels": (1.0, 8.0), "llc_mb_per_core": (0.5, 4.0)}
        budget = 1.1
        project = make_projector(box, budget, float("inf"), tie=1.0,
                                 links0=0.0)
        # A gradient that always pushes toward the expensive corner.
        vg = lambda x: ((x["dram_channels"] + x["llc_mb_per_core"], {}),
                        {"dram_channels": 1.0, "llc_mb_per_core": 1.0})
        x, traj, _ = projected_ascent(
            {"dram_channels": 2.0, "llc_mb_per_core": 1.0}, vg, project,
            widths={k: hi - lo for k, (lo, hi) in box.items()},
            lr=0.5, iters=15, tol=1e-6)
        for t in traj:
            for k, (lo, hi) in box.items():
                assert lo - 1e-9 <= t[k] <= hi + 1e-9
            cost = coaxial.design_cost(t["dram_channels"],
                                       t["dram_channels"],
                                       t["llc_mb_per_core"])
            assert float(cost["rel_area"]) <= budget + 1e-6
        # The ascent actually reached the budget surface (it binds).
        final_cost = coaxial.design_cost(x["dram_channels"],
                                         x["dram_channels"],
                                         x["llc_mb_per_core"])
        assert float(final_cost["rel_area"]) == pytest.approx(budget,
                                                              abs=1e-3)

    def test_infeasible_start_refused(self):
        box = {"dram_channels": (1.0, 8.0), "llc_mb_per_core": (0.5, 4.0)}
        project = make_projector(box, 1.05, float("inf"), tie=1.0,
                                 links0=0.0)
        with pytest.raises(ValueError, match="infeasible start"):
            project({"dram_channels": 8.0, "llc_mb_per_core": 4.0}, None)


class TestOptimizeDesign:
    def test_end_to_end_budget_slo_verify_one_trace(self, lut):
        before = designer.designer_trace_count()
        res = designer.optimize_design(
            area_budget=1.2, slo_ms=500.0, iters=8, lut=lut,
            steps=LUT_STEPS, verify_steps=LUT_STEPS)
        # ONE compiled value-and-grad serves every iteration.
        assert designer.designer_trace_count() - before <= 1
        assert res.meets_budget and res.rel_area <= 1.2 + 1e-6
        assert res.meets_slo and res.token_p99_ms <= 500.0
        # The DES re-verification at the optimum agrees with the
        # in-loop model p99 within the calibration-style gate.
        assert res.verify["ok"]
        assert res.verify["engine"] == "event"
        # Returned fields stay inside the frontier box.
        assert 1.0 <= float(res.design.dram_channels) <= 8.0
        assert 0.5 <= float(res.design.llc_mb_per_core) <= 4.0
        assert res.gm_speedup > 1.0
        # Ascent is monotone-or-better end to end vs the knee start.
        assert (res.trajectory[-1]["objective"]
                >= res.trajectory[0]["objective"] - 1e-9)

        # A second run with the same shapes re-uses the compiled
        # objective: no new trace at all.
        before2 = designer.designer_trace_count()
        res2 = designer.optimize_design(
            area_budget=1.15, slo_ms=500.0, iters=2, lut=lut,
            steps=LUT_STEPS, verify_steps=LUT_STEPS)
        assert designer.designer_trace_count() == before2
        assert res2.rel_area <= 1.15 + 1e-6

    def test_slo_without_arch_refused(self, lut):
        with pytest.raises(ValueError, match="arch"):
            designer.optimize_design(slo_ms=10.0, arch=None, lut=lut)

    def test_impossible_budget_refused(self, lut):
        with pytest.raises(ValueError, match="no frontier point"):
            designer.optimize_design(area_budget=0.5, slo_ms=None,
                                     arch=None, lut=lut)


class TestParetoTail:
    @pytest.fixture(scope="class")
    def sw(self, lut):
        spec = coaxial.sweep_spec(
            design=(DDR_BASELINE, COAXIAL_2X, COAXIAL_4X))
        return coaxial.solve_spec(spec, queue_model="memsim", lut=lut)

    def test_points_carry_p99_and_sort_by_cost(self, sw):
        front = sw.pareto(tail=True)
        assert front, "tail frontier must not be empty"
        for p in front:
            assert np.isfinite(p["latency_p99_ns"])
            assert p["latency_p99_ns"] > 0
        costs = [p["rel_area"] for p in front]
        assert costs == sorted(costs)

    def test_tail_frontier_extends_the_2d_frontier(self, sw):
        # A third objective can only shrink the dominance relation, so
        # every 2-D-nondominated point survives and the frontier can
        # only grow.
        assert len(sw.pareto(tail=True)) >= len(sw.pareto())

    def test_closed_form_refused(self):
        sw = coaxial.solve_spec(
            coaxial.sweep_spec(design=(DDR_BASELINE, COAXIAL_4X)))
        with pytest.raises(ValueError, match="memsim"):
            sw.pareto(tail=True)


class TestCLI:
    def test_cli_smoke_exit_zero(self, lut, capsys):
        # ``lut`` warms the default_queue_lut cache at LUT_STEPS, so the
        # CLI at ``--steps LUT_STEPS`` reuses the surface.
        import repro.designer as cli
        rc = cli.main(["--iters", "6", "--steps", str(LUT_STEPS)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "DESIGN OK" in out
        assert "verify" in out
