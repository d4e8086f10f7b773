"""The trace reduction and the roofline arithmetic, on a small synthetic
trace and a hand count."""

import pytest

from bench.harness import roofline
from bench.harness import trace as tr
from bench.harness.runner import Run
from bench.harness.spec import load_cell

E = tr.Event

#: Traced window [1000, 2000) ns.  Device 0 runs jit_run over [1000,
#: 1300) and [1250, 1400) (overlapping) and jit__event_arrivals over
#: [1700, 1900); device 1 runs jit_run over [900, 1100), from before the
#: window.
TRACE = tr.Trace(
    devices={
        "/device:TPU:0": [E("jit_run(7)", 1000, 300),
                          E("jit_run(7)", 1250, 150),
                          E("jit__event_arrivals(3)", 1700, 200)],
        "/device:TPU:1": [E("jit_run(7)", 900, 200)],
    },
    spans=[E("bench.traced", 1000, 1000), E("bench.job", 1000, 990),
           E("bench.des", 1350, 300), E("bench.des", 1900, 50)])


def test_window_is_the_benchmark_span():
    assert tr.window_of(TRACE) == (1000, 2000)


def test_busy_is_the_union_of_modules_clipped_to_the_window():
    busy = tr.device_busy(TRACE, 1000, 2000)
    assert busy == {"/device:TPU:0": 400 + 200, "/device:TPU:1": 100}
    assert tr.busiest(TRACE, 1000, 2000) == "/device:TPU:0"


def test_module_time_per_device_matches_the_name_at_the_start():
    ns = tr.module_ns(TRACE, 1000, 2000, r"jit_run\(")
    assert ns == {"/device:TPU:0": 450, "/device:TPU:1": 100}
    assert tr.module_ns(TRACE, 1000, 2000, r"jit__event_arrivals\(")[
        "/device:TPU:0"] == 200
    top = tr.top_modules(TRACE, 1000, 2000, "/device:TPU:0")
    assert [k for k, _ in top] == ["jit_run", "jit__event_arrivals"]
    assert [v for _, v in top] == pytest.approx([450e-9, 200e-9])


def test_idle_gaps_are_named_by_the_innermost_host_span():
    gaps = tr.idle_gaps(TRACE, 1000, 2000, "/device:TPU:0")
    # [1400, 1700) has its midpoint in a bench.des span; the midpoint
    # of [1900, 2000) is past the second bench.des span, in bench.job.
    assert [k for k, _ in gaps] == ["bench.des", "bench.job"]
    assert [v for _, v in gaps] == pytest.approx([300e-9, 100e-9])


def _run(chips=2):
    cell = load_cell("lut_build.ddr5_4800_paper")
    cell = type(cell)(**{**cell.__dict__, "chips": chips})
    return Run(cell=cell, setup_s=1.0, jobs=4, window_s=1.0,
               traced_jobs=2, job_seconds=[0.5, 0.5], des_calls=[],
               device_kind="TPU v5 lite", trace=TRACE,
               window_ns=(1000, 2000))


def test_idle_share_and_stage_readers():
    from bench.harness.spec import metric_reader
    run = _run()
    idle = metric_reader("device_idle_share")(run)
    assert idle == pytest.approx(100 * (1 - (600 + 100) / 2 / 1000))
    assert metric_reader("stage_b_ms")(run) == pytest.approx(450e-6 / 2)
    assert metric_reader("stage_a_ms")(run) == pytest.approx(200e-6 / 2)


def test_readers_find_nothing_without_a_trace():
    from bench.harness.spec import metric_reader
    run = _run()
    run.trace = None
    for name in ("device_idle_share", "stage_a_ms", "stage_b_ms",
                 "stage_b_roofline", "outside_des_share",
                 "des_mreq_per_s"):
        assert metric_reader(name)(run) is None


def test_stage_b_bytes_by_hand():
    # 1,024 requests x 4,032 lanes: gaps 4 B + services 4 B + record flag
    # 1 B + bin index 4 B per request; W in and out, bound and base
    # latency 4 B each per lane.
    assert roofline.stage_b_chunk_bytes(4032, 1024) == (
        1024 * 4032 * 13 + 4032 * 16)
    assert roofline.stage_b_chunk_bytes(1, 1) == 29


def test_unknown_device_kind_is_an_error():
    assert roofline.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError):
        roofline.peak("cpu", "hbm_bytes_per_s")
