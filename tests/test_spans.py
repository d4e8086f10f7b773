"""The DES call's and the capacity planner's host spans and counters, read
back from a profiler trace as the benchmark reads them
(``jax.profiler.ProfileData``: the spans' keyword arguments are the
events' stats)."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import memsim
from repro.serving import capacity
from repro.serving.traffic import get_trace

#: Every span the program records, and the span it nests in (None: the
#: caller's).
PARENT = {"memsim.simulate": None, "memsim.prepare": "memsim.simulate",
          "memsim.dispatch": "memsim.simulate",
          "memsim.fetch": "memsim.simulate",
          "memsim.bincount": "memsim.simulate",
          "memsim.jitter": "memsim.simulate",
          "memsim.stats": "memsim.simulate",
          "planner.lanes": "test.plan", "planner.solve": "test.plan",
          "planner.compose": "test.plan"}
CHUNK = 1024


def _spans(directory):
    """``[(name, start, end, line, args)]`` of the host planes' events."""
    f = max(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True), key=os.path.getmtime)
    out = []
    for plane in ProfileData.from_file(f).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                out.extend((e.name, int(e.start_ns),
                            int(e.start_ns + e.duration_ns),
                            (plane.name, i), dict(e.stats))
                           for e in line.events
                           if e.name.startswith(("memsim.", "planner.",
                                                 "test.")))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One event-engine call (5 lanes on 2 devices, so one padding lane),
    one timestep call and one tiny plan, under the profiler; the
    pre-jitter histogram of each DES call kept beside its id."""
    cfgs = [memsim.ChannelConfig(rho=r, kappa=k)
            for r, k in ((0.2, 1.0), (0.5, 2.0), (0.8, 1.0), (0.9, 3.0),
                         (0.95, 2.0))]
    raw = {}
    inner = memsim._accumulate_chunks

    def keep(dispatch, n_chunks, n):
        hist = inner(dispatch, n_chunks, n)
        raw[memsim._CALL["id"]] = hist.copy()
        return hist

    def calls():
        ids = {}
        memsim.simulate_cells(memsim.stack_channels(cfgs), steps=8000,
                              engine="event", chunk=CHUNK, devices=2)
        ids["event"] = memsim._CALL["id"]
        memsim.simulate_cells(memsim.stack_channels(cfgs[:3]), steps=2048,
                              engine="timestep", chunk=CHUNK, reps=2)
        ids["timestep"] = memsim._CALL["id"]
        with jax.profiler.TraceAnnotation("test.plan"):
            capacity.plan_capacity(
                ("stablelm-1.6b",), get_trace("synthetic-diurnal"),
                slo_p99_ms=10_000.0, batch=32, context=2048,
                channels=(2,), premium_ns=(30.0,), tier_splits=(0.0,),
                include_registry=False, include_measured=False,
                peak_util=0.5, steps=4000, engine="event")
        ids["plan"] = memsim._CALL["id"]
        return ids

    calls()                              # compile outside the trace
    d = str(tmp_path_factory.mktemp("trace"))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(memsim, "_accumulate_chunks", keep)
        jax.profiler.start_trace(d)
        try:
            ids = calls()
        finally:
            jax.profiler.stop_trace()
    return dict(spans=_spans(d), ids=ids, raw=raw)


def _of(traced, name, call=None):
    return [s for s in traced["spans"] if s[0] == name
            and (call is None or s[4].get("call") == call)]


def test_every_span_is_on_a_host_plane(traced):
    names = {s[0] for s in traced["spans"]}
    assert set(PARENT) <= names


def test_spans_nest_in_their_parent_on_one_thread(traced):
    for name, start, end, line, args in traced["spans"]:
        parent = PARENT.get(name)
        if parent is None:
            continue
        if name == "memsim.prepare" and "call" not in args:
            parent = "test.plan"        # stack_channels in simulate()
        cover = [p for p in _of(traced, parent, args.get("call"))
                 if p[3] == line and p[1] <= start and end <= p[2]]
        assert cover, (name, args)


def test_the_plan_des_call_lies_outside_the_planner_spans(traced):
    (sim,) = _of(traced, "memsim.simulate", traced["ids"]["plan"])
    (solve,) = _of(traced, "planner.solve")
    (compose,) = _of(traced, "planner.compose")
    assert solve[2] <= sim[1] and sim[2] <= compose[1]


@pytest.mark.parametrize("engine,lanes,devices,chunks", [
    ("event", 5, 2, 3), ("timestep", 6, 1, 2)])
def test_chunk_spans_share_the_call(traced, engine, lanes, devices,
                                    chunks):
    call = traced["ids"][engine]
    (sim,) = _of(traced, "memsim.simulate", call)
    lookup = {"seg_lookup": "bsearch"} if engine == "event" else {}
    assert sim[4] == dict(call=call, engine=engine, lanes=lanes,
                          devices=devices, chunks=chunks, chunk=CHUNK,
                          **lookup)
    ks = [s[4]["k"] for s in _of(traced, "memsim.dispatch", call)]
    assert sorted(ks) == list(range(chunks))
    for name in ("memsim.fetch", "memsim.bincount"):
        assert len(_of(traced, name, call)) == chunks
    assert len(_of(traced, "memsim.jitter", call)) == (engine == "event")


@pytest.mark.parametrize("engine", ["event", "timestep", "plan"])
def test_simulate_names_the_segment_lookup(traced, engine):
    """The event engine's stage A runs its CPU branch here, the binary
    search; the timestep engine has no segment lookup."""
    (sim,) = _of(traced, "memsim.simulate", traced["ids"][engine])
    want = None if engine == "timestep" else "bsearch"
    assert sim[4].get("seg_lookup") == want
    assert memsim._seg_lookup("cpu") == "bsearch"
    assert memsim._seg_lookup("tpu") == "count"


@pytest.mark.parametrize("engine", ["event", "timestep", "plan"])
def test_counters_are_the_slots_and_the_pre_jitter_histogram(traced,
                                                             engine):
    call = traced["ids"][engine]
    (sim,) = _of(traced, "memsim.simulate", call)
    (stats,) = _of(traced, "memsim.stats", call)
    a = sim[4]
    assert stats[4]["candidates"] == a["lanes"] * a["chunks"] * a["chunk"]
    assert stats[4]["recorded"] == int(traced["raw"][call].sum())
    assert 0 < stats[4]["recorded"] < stats[4]["candidates"]


def _lowered_module(fn, *args):
    return fn.lower(*args).as_text().split("module @", 1)[1].split()[0]


def test_stage_b_alone_is_named_run():
    """The benchmark matches the event engine's stage B as ``jit_run`` on
    the device trace; the timestep kernel must not share the name."""
    n, c = 3, 8
    cha = memsim.stack_channels([memsim.ChannelConfig(rho=0.5)] * n)
    ov = memsim._nan_overrides(n)
    f32 = jnp.zeros((c, n), jnp.float32)
    ev = memsim._event_kernel.__wrapped__(1, n, n, c)
    assert ev.__name__ == "run"
    assert _lowered_module(ev, memsim._scan_terms_jit(cha, ov),
                           jnp.zeros(n), f32, f32,
                           jnp.zeros((c, n), bool)) == "jit_run"
    ts = memsim._ts_kernel.__wrapped__(1, n, n)
    terms = {**memsim._scan_terms_jit(cha, ov),
             **memsim._harvest_scan_terms_jit(cha, ov)}
    state = (jnp.zeros(n), jnp.ones(n), jnp.zeros(n))
    name = _lowered_module(ts, terms, state, f32, f32, f32, f32, f32,
                           jnp.zeros(c, jnp.float32))
    assert name.startswith("jit_") and name != "jit_run"


def test_spans_leave_the_histograms_as_they_were():
    """The call ids advance, the results do not move: two identical calls
    give bitwise-equal histograms, and the overflow count is each call's
    own."""
    cfgs = [memsim.ChannelConfig(rho=0.6)] * 2
    a = memsim.simulate(cfgs, steps=4000, engine="event", seed=3)
    first = memsim._CALL["id"]
    b = memsim.simulate(cfgs, steps=4000, engine="event", seed=3)
    assert memsim._CALL["id"] == first + 1
    np.testing.assert_array_equal(a.hist, b.hist)
