"""The ``plan`` job end to end at a tiny size on the CPU: sound runs
are correct, and the control and each planted fault are not."""

import pytest

from bench.tests import bench_tiny

CELL = "plan.stablelm2_decode"


def test_sound_run_is_correct():
    out = bench_tiny.run(CELL)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"job_s", "setup_s"}
    assert list(out)[-1] == "checks"


def test_control_is_not_correct():
    cell = bench_tiny.tiny_cell(CELL)
    job, answers = bench_tiny.answers(cell, (11, 12))
    assert all(c.ok for c in job.check(answers, 5, cell.limits))
    assert not all(c.ok for c in job.control(answers, 5, cell.limits))


@pytest.mark.parametrize("fault",
                         bench_tiny.FAULTS + bench_tiny.PLAN_FAULTS)
def test_planted_fault_is_not_correct(monkeypatch, fault):
    bench_tiny.plant(monkeypatch, fault)
    out = bench_tiny.run(CELL)
    assert out["correct"] is False, out["checks"]


def test_a_lane_that_recorded_nothing_compares_on_one_bin():
    cell = bench_tiny.tiny_cell(CELL)
    job, answers = bench_tiny.answers(cell, (11,))
    whole, pairs = job.samples(answers, 5)
    got = job.program(answers, whole, pairs)
    ref = job.reference(answers, whole, pairs)
    got["mean"][0] = ref["mean"][0] = 0.0
    assert all(c.ok for c in job.compare(got, ref, cell.limits))
    got["mean"][0] = 4.0
    bad = {c.name for c in job.compare(got, ref, cell.limits) if not c.ok}
    assert bad == {"lane_mean_dev_max"}
