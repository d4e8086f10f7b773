"""The harvest pass's readers (``harvest_ms``, ``harvest_roofline``,
``des_lent_share``) on a small synthetic trace and a hand count, and on
the spans of a program that does not name the pass."""

import pytest

from bench.harness import harvest_pass
from bench.harness import program_spans as ps
from bench.harness import runner
from bench.harness import trace as tr
from bench.harness.spec import load_cell, metric_reader

E, S = tr.Event, ps.Span
READERS = ("harvest_ms", "harvest_roofline", "des_lent_share")

#: Traced window [1000, 3000) ns, two traced jobs; one device runs the
#: window table, two scaling passes, stage A and stage B.
TRACE = tr.Trace(
    devices={"/device:TPU:0": [
        E("jit__event_harvest_tabs(4)", 1000, 100),
        E("jit__event_arrivals(3)", 1100, 200),
        E("jit__event_harvest_scale(5)", 1300, 300),
        E("jit_run(7)", 1600, 50),
        E("jit__event_harvest_scale(5)", 1700, 300)]},
    spans=[E("bench.traced", 1000, 2000)])


def _s(name, start, dur, **args):
    return S(name, start, dur, line="/host:CPU#0", args=args)


#: A harvesting call, one that ran no pass, and one from a program whose
#: spans do not name the pass.
CALLS = dict(lanes=8, chunks=3, chunk=1024)
SPANS = [
    _s("memsim.simulate", 1000, 600, call=1, harvest=1, windows=64,
       **CALLS),
    _s("memsim.stats", 1500, 50, call=1, candidates=24576, recorded=9000,
       lent=6144),
    _s("memsim.simulate", 1700, 300, call=2, harvest=0, windows=0,
       **CALLS),
    _s("memsim.stats", 1900, 50, call=2, candidates=24576, recorded=9000,
       lent=0),
    _s("memsim.simulate", 2100, 300, call=3, **CALLS),
    _s("memsim.stats", 2300, 50, call=3, candidates=24576, recorded=9000),
]


def _run(trace=TRACE):
    return runner.Run(cell=load_cell("lut_build.ddr5_4800_harvest"),
                      setup_s=1.0, jobs=4, window_s=1.0, traced_jobs=2,
                      job_seconds=[0.5] * 4, des_calls=[],
                      device_kind="TPU v5 lite", trace=trace,
                      window_ns=(1000, 3000))


@pytest.fixture
def spans(monkeypatch):
    def use(chosen):
        monkeypatch.setattr(ps, "load", lambda directory: list(chosen))
    use(SPANS)
    return use


def test_call_bytes_by_hand():
    # 3 chunks x 8 lanes x (1,024 x 12 + 8 + 4 x 64) + 8 x 4 x 64.
    assert harvest_pass.call_bytes(8, 3, 1024, 64) == (
        3 * 8 * (1024 * 12 + 8 + 256) + 8 * 256)


def test_harvest_ms_is_the_pass_modules_per_job(spans):
    assert metric_reader("harvest_ms")(_run()) == pytest.approx(
        (100 + 300 + 300) / 2 * 1e-6)


def test_roofline_counts_the_harvesting_calls_alone(spans):
    least_s = (harvest_pass.call_bytes(8, 3, 1024, 64) / 2 / 819e9)
    assert metric_reader("harvest_roofline")(_run()) == pytest.approx(
        100.0 * least_s / (700 / 2 * 1e-9))


def test_lent_share_counts_the_harvesting_calls_alone(spans):
    assert metric_reader("des_lent_share")(_run()) == pytest.approx(
        100.0 * 6144 / 24576)


@pytest.mark.parametrize("name", ["harvest_roofline", "des_lent_share"])
def test_readers_find_nothing_where_no_call_names_the_pass(spans, name):
    spans([s for s in SPANS if s.args["call"] == 3])
    assert metric_reader(name)(_run()) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_without_a_trace(spans, name):
    assert metric_reader(name)(_run(trace=None)) is None


def test_no_harvest_modules_no_harvest_ms(spans):
    trace = tr.Trace(devices={"/device:TPU:0": [
        E("jit__event_arrivals(3)", 1100, 200)]}, spans=TRACE.spans)
    assert metric_reader("harvest_ms")(_run(trace)) is None
    assert metric_reader("harvest_roofline")(_run(trace)) is None
