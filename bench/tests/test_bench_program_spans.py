"""The readers of the program's own spans, on a small synthetic trace and
a hand count; and the span loader on a real (CPU) profiler trace."""

import jax
import pytest

from bench.harness import program_spans as ps
from bench.harness import runner
from bench.harness import trace as tr
from bench.harness.spec import load_cell, metric_reader

E, S = tr.Event, ps.Span
READERS = ("des_host_ms", "des_idle_ms", "des_record_share",
           "consumer_host_ms")

#: Traced window [1000, 3000) ns, two traced jobs.  Device 0 runs modules
#: over [1000, 1300) (two overlapping), [1800, 1900) and [2500, 2600);
#: device 1 over [1000, 2000).
TRACE = tr.Trace(
    devices={
        "/device:TPU:0": [E("jit__event_arrivals(3)", 1000, 200),
                          E("jit_run(7)", 1150, 150),
                          E("jit__event_arrivals(3)", 1800, 100),
                          E("jit__solve_cells(9)", 2500, 100)],
        "/device:TPU:1": [E("jit__event_arrivals(3)", 1000, 1000)],
    },
    spans=[E("bench.traced", 1000, 2000)])


def _s(name, start, dur, **args):
    return S(name, start, dur, line="/host:CPU#0", args=args)


#: Three DES calls: the first starts before the window, the third ends
#: after it (its statistics fall outside); one ``stack_channels`` span
#: outside any call; planner spans, one overlapping another and one past
#: the window.
SPANS = [
    _s("memsim.simulate", 900, 700, call=1),
    _s("memsim.prepare", 900, 150, call=1),
    _s("memsim.fetch", 1100, 300, call=1),
    _s("memsim.stats", 1500, 90, call=1, candidates=1000, recorded=600),
    _s("memsim.prepare", 1650, 50),
    _s("memsim.simulate", 1700, 500, call=2),
    _s("memsim.fetch", 1750, 100, call=2),
    _s("memsim.stats", 2100, 90, call=2, candidates=3000, recorded=900),
    _s("memsim.simulate", 2900, 200, call=3),
    _s("memsim.stats", 3050, 40, call=3, candidates=5000, recorded=5000),
    _s("planner.lanes", 1600, 50),
    _s("planner.solve", 1620, 70),
    _s("planner.compose", 2200, 200),
    _s("planner.lanes", 3000, 100),
]


def _run(chips=2, trace=TRACE):
    cell = load_cell("plan.stablelm2_decode")
    cell = type(cell)(**{**cell.__dict__, "chips": chips})
    return runner.Run(cell=cell, setup_s=1.0, jobs=4, window_s=1.0,
                      traced_jobs=2, job_seconds=[0.5, 0.5], des_calls=[],
                      device_kind="TPU v5 lite", trace=trace,
                      window_ns=(1000, 3000))


@pytest.fixture
def spans(monkeypatch):
    read = []

    def load(directory):
        read.append(directory)
        return list(SPANS)

    monkeypatch.setattr(ps, "load", load)
    return read


def test_spans_are_clipped_to_the_window_and_read_once(spans):
    run = _run()
    got = ps.of_run(run)
    assert ps.of_run(run) is got
    assert spans == [runner.CACHE / "trace" / run.cell.name]
    first = got[0]
    assert (first.name, first.start_ns, first.end_ns) == (
        "memsim.simulate", 1000, 1600)
    assert first.args == {"call": 1}
    assert [s.end_ns for s in got if s.name == "memsim.simulate"] == [
        1600, 2200, 3000]
    assert not [s for s in got if s.start_ns >= 3000]
    assert len(got) == len(SPANS) - 2


def test_overlap_of_merged_intervals():
    assert ps.overlap_ns([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert ps.overlap_ns([(0, 10)], []) == 0
    assert ps.overlap_ns([(0, 100)], [(10, 20), (30, 40), (90, 120)]) == 30


def test_des_host_time_leaves_out_the_fetches(spans):
    # memsim.* union in the window: [1000, 1600) + [1650, 2200) +
    # [2900, 3000) = 1,250 ns; fetches 300 + 100.
    assert metric_reader("des_host_ms")(_run()) == pytest.approx(
        (1250 - 400) / 2 * 1e-6)


@pytest.mark.parametrize("chips,idle_ns", [
    # memsim.simulate in the window: [1000, 1600), [1700, 2200),
    # [2900, 3000), 1,200 ns.  Device 0 is busy over 400 of them: its
    # idle gap [1300, 1800) straddles the end of the first call and the
    # start of the second, so 300 + 100 of it count.  Device 1 is busy
    # over 900.
    (1, 1200 - 400),
    (2, ((1200 - 400) + (1200 - 900)) / 2),
])
def test_des_idle_is_device_idle_inside_the_calls(spans, chips, idle_ns):
    assert metric_reader("des_idle_ms")(_run(chips)) == pytest.approx(
        idle_ns / 2 * 1e-6)


def test_record_share_counts_the_window_statistics(spans):
    assert metric_reader("des_record_share")(_run()) == pytest.approx(
        100.0 * (600 + 900) / (1000 + 3000))


def test_consumer_host_time_is_the_union_of_planner_spans(spans):
    # [1600, 1690) + [2200, 2400); the last lanes span is past the window.
    assert metric_reader("consumer_host_ms")(_run()) == pytest.approx(
        (90 + 200) / 2 * 1e-6)


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_without_a_trace(spans, name):
    assert metric_reader(name)(_run(trace=None)) is None
    assert spans == []


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_without_program_spans(monkeypatch, name):
    monkeypatch.setattr(ps, "load", lambda directory: [])
    assert metric_reader(name)(_run()) is None


def test_load_reads_program_spans_and_their_args(tmp_path):
    assert ps.load(tmp_path) == []
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.job"):
        with jax.profiler.TraceAnnotation("memsim.stats", call=7,
                                          candidates=10, recorded=4):
            pass
        with jax.profiler.TraceAnnotation("planner.compose"):
            pass
    jax.profiler.stop_trace()
    got = {s.name: s for s in ps.load(tmp_path)}
    assert sorted(got) == ["memsim.stats", "planner.compose"]
    assert got["memsim.stats"].args == dict(call=7, candidates=10,
                                            recorded=4)
    assert got["planner.compose"].args == {}
    assert got["memsim.stats"].line.startswith("/host:")
