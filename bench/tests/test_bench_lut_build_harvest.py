"""The ``lut_build_harvest`` job end to end at a tiny size on the CPU:
sound runs are correct, and the control and each planted fault of the
harvest pass are not; at duty 0 the harvest reference is des.py's."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import runner, spec
from bench.reference import des, harvest
from bench.tests import bench_tiny

CELL = "lut_build.ddr5_4800_harvest"
#: lut_build's tiny grid with two duties, at its tiny budget.
TINY = dict(steps=bench_tiny.TINY["lut_build"]["steps"], grid=dict(
    bench_tiny.TINY["lut_build"]["grid"], harvest=[0.0, 0.5]))


def tiny_cell() -> spec.Cell:
    cell = spec.load_cell(CELL)
    return dataclasses.replace(cell, config={**cell.config, **TINY})


def run() -> dict:
    return runner.run_cell(tiny_cell(), bench_tiny.SEED, 0.3, False,
                           require_chip=False)


def _unscaled(svc, gaps, t0, bounds, h_scale, lent):
    return svc, lent


def plant(monkeypatch, fault: str) -> None:
    """Break the program's harvest pass:

    - ``unscaled``: the pass returns the services as they came, so lent
      windows lend nothing;
    - ``even_windows``: the windows at even indices of each lane's table
      are taken as the lent ones (a zero bound put in front of the
      table turns every window's parity over).
    """
    from repro.core import memsim
    inner = memsim._event_harvest_scale_jit
    if fault == "unscaled":
        fn = jax.jit(_unscaled)
    elif fault == "even_windows":
        def fn(svc, gaps, t0, bounds, h_scale, lent):
            zero = jnp.zeros((bounds.shape[0], 1), bounds.dtype)
            return inner(svc, gaps, t0, jnp.concatenate([zero, bounds], 1),
                         h_scale, lent)
    else:
        raise ValueError(fault)
    monkeypatch.setattr(memsim, "_event_harvest_scale_jit", fn)


def test_sound_run_is_correct():
    out = run()
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"job_s", "setup_s"}
    assert set(out["checks"]) == {"wait_dev_max", "p90_dev_max",
                                  "p99_dev_max", "sigma_dev_max"}


def test_control_is_not_correct():
    cell = tiny_cell()
    job, answers = bench_tiny.answers(cell, (11, 12))
    assert answers[0]["wait"].shape == (3, 2, 2, 2, 2)
    assert all(c.ok for c in job.check(answers, 5, cell.limits))
    assert not all(c.ok for c in job.control(answers, 5, cell.limits))


@pytest.mark.parametrize("fault", ["unscaled", "even_windows"])
def test_planted_fault_is_not_correct(monkeypatch, fault):
    plant(monkeypatch, fault)
    out = run()
    assert out["correct"] is False, out["checks"]


def test_at_duty_zero_the_reference_is_des():
    """The same cells through both references: with nothing lent, the
    chain's salted draws move nothing, so histograms are equal bit for
    bit, and so are the four tables."""
    cell = tiny_cell()
    c, steps = cell.config, TINY["steps"]
    rhos = (0.05, 0.45, 0.93, 0.62)
    params = {f: [dict(c["channel"], rho=r, kappa=1.6, outstanding=24.0,
                       eta=0.6, harvest_duty=0.0).get(f) for r in rhos]
              for f in harvest.FIELDS}
    streams = [des.cell_stream_id(("rho",), (r,)) for r in rhos]
    seeds = [7, 7, 8, 8]
    got = harvest.simulate(params, streams, seeds, steps=steps,
                           chunk=c["chunk"])
    want = des.simulate({f: params[f] for f in des.FIELDS}, streams, seeds,
                        steps=steps, chunk=c["chunk"])
    np.testing.assert_array_equal(got, want)
    assert got.sum() > 0
    for k, v in des.stats(got).items():
        np.testing.assert_array_equal(v, des.stats(want)[k])


def test_the_reference_lends_at_positive_duty():
    """At duty 0.5 the chain shortens the waits of a loaded lane."""
    c = tiny_cell().config
    params = {f: [dict(c["channel"], rho=0.93, kappa=1.0, outstanding=192.0,
                       eta=1.0, harvest_duty=d).get(f) for d in (0.0, 0.5)]
              for f in harvest.FIELDS}
    hist = harvest.simulate(params, [3, 3], [9, 9], steps=TINY["steps"],
                            chunk=c["chunk"])
    mean = des.stats(hist)["mean_ns"]
    assert mean[1] < mean[0]
