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


#: A harvesting batch: three duties at the reference lent bandwidth and
#: one lane that lends nothing (duty 0).
HARVEST = [memsim.ChannelConfig(rho=r, kappa=k, harvest_duty=d,
                                harvest_bw_gbps=38.4)
           for r, k, d in ((0.3, 1.0, 0.25), (0.7, 2.0, 0.5),
                           (0.9, 1.6, 0.75), (0.6, 1.0, 0.0))]


def _harvest_call(**kw):
    return memsim.simulate_cells(memsim.stack_channels(HARVEST), steps=8000,
                                 engine="event", chunk=CHUNK, seed=5, **kw)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One event-engine call (5 lanes on 2 devices, so one padding lane),
    one timestep call, one tiny plan, a harvesting event call and the
    same batch at duty 0, under the profiler; the pre-jitter histogram of
    each DES call kept beside its id, and the harvest pass's inputs
    (gaps, first arrival, window bounds) of each chunk."""
    cfgs = [memsim.ChannelConfig(rho=r, kappa=k)
            for r, k in ((0.2, 1.0), (0.5, 2.0), (0.8, 1.0), (0.9, 3.0),
                         (0.95, 2.0))]
    raw, scaled = {}, []
    inner = memsim._accumulate_chunks
    scale = memsim._event_harvest_scale_jit

    def keep(dispatch, n_chunks, n):
        hist = inner(dispatch, n_chunks, n)
        raw[memsim._CALL["id"]] = hist.copy()
        return hist

    def keep_scale(svc, gaps, t0, bounds, h_scale, lent):
        scaled.append(tuple(np.asarray(x) for x in (gaps, t0, bounds)))
        return scale(svc, gaps, t0, bounds, h_scale, lent)

    def calls():
        ids = {}
        memsim.simulate_cells(memsim.stack_channels(cfgs), steps=8000,
                              engine="event", chunk=CHUNK, devices=2)
        ids["event"] = memsim._CALL["id"]
        _harvest_call()
        ids["harvest"] = memsim._CALL["id"]
        _harvest_call(overrides={"harvest_duty": np.zeros(len(HARVEST))})
        ids["duty0"] = memsim._CALL["id"]
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
        mp.setattr(memsim, "_event_harvest_scale_jit", keep_scale)
        jax.profiler.start_trace(d)
        try:
            ids = calls()
        finally:
            jax.profiler.stop_trace()
    return dict(spans=_spans(d), ids=ids, raw=raw, scaled=scaled)


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
    lookup = ({"seg_lookup": "bsearch", "harvest": 0, "windows": 0}
              if engine == "event" else {})
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


def test_lent_is_the_slots_that_arrived_in_an_odd_window(traced):
    """The harvest pass's ``lent`` counter against a direct count, from
    the pass's own inputs, of the slots whose arrival time lies in an
    odd (lent) window of the lane's table."""
    call = traced["ids"]["harvest"]
    (sim,) = _of(traced, "memsim.simulate", call)
    (stats,) = _of(traced, "memsim.stats", call)
    windows = max(64, sim[4]["chunks"] * CHUNK // 48)
    assert (sim[4]["harvest"], sim[4]["windows"]) == (1, windows)
    assert len(traced["scaled"]) == sim[4]["chunks"]
    lent = 0
    for gaps, t0, bounds in traced["scaled"]:
        assert bounds.shape == (len(HARVEST), windows)
        arr = t0[None, :].astype(np.float64) + np.cumsum(
            gaps.astype(np.float64), axis=0)
        for lane in range(len(HARVEST)):
            idx = np.searchsorted(bounds[lane].astype(np.float64),
                                  arr[:, lane], side="left")
            lent += int(np.sum(idx % 2 == 1))
    assert stats[4]["lent"] == lent
    assert 0 < lent < stats[4]["candidates"]


@pytest.mark.parametrize("engine", ["event", "duty0"])
def test_lent_is_zero_where_nothing_is_lent(traced, engine):
    call = traced["ids"][engine]
    (sim,) = _of(traced, "memsim.simulate", call)
    (stats,) = _of(traced, "memsim.stats", call)
    assert (sim[4]["harvest"], sim[4]["windows"]) == (0, 0)
    assert stats[4]["lent"] == 0


def test_timestep_stats_carry_no_lent(traced):
    (stats,) = _of(traced, "memsim.stats", traced["ids"]["timestep"])
    assert "lent" not in stats[4]


def test_lent_share_of_one_lane_is_its_duty():
    """One lane at duty 0.5 over a long budget lends about half its
    slots.  A 2,000-ns-mean alternating chain over a horizon ``T`` of
    about ``steps`` ns (rho 0.5) has a lent-time share of standard error
    ``sqrt(2000 / (4 T))``: 0.011 here, and four of them is the
    tolerance."""
    steps = 4_000_000
    cha = memsim.stack_channels([memsim.ChannelConfig(
        rho=0.5, harvest_duty=0.5, harvest_bw_gbps=38.4)])
    memsim.simulate_cells(cha, steps=steps, engine="event", seed=11)
    budget = memsim.events_for_steps(steps)
    chunk = memsim._event_chunk_len(1)
    candidates = -(-budget // chunk) * chunk
    share = memsim._CALL["lent"] / candidates
    assert abs(share - 0.5) < 4 * np.sqrt(2000.0 / (4 * steps))


def _uncounted_scale(svc, gaps, t0, bounds, h_scale, lent_sum):
    """The harvest pass as it was before it counted (its body verbatim),
    with the count left as it came."""
    arr_t = t0[None, :] + jnp.cumsum(gaps, axis=0)        # (C, n)
    idx = jax.vmap(jnp.searchsorted, in_axes=(0, 0))(bounds, arr_t.T)
    lent = (idx % 2 == 1).T
    return jnp.where(lent, svc * h_scale[None, :], svc), lent_sum


def test_the_lent_counter_leaves_the_histograms_as_they_were(monkeypatch):
    """A harvesting batch's histograms are the same bytes with the
    counter as through the pass without it.  (A fixed sha256 would pin
    one host's rounding of stage A's transcendentals: on this batch,
    captured before the counter, 1613db9a...ababd on one CPU host.)"""
    import hashlib

    def sha(st):
        return hashlib.sha256(np.ascontiguousarray(st.hist, np.float64)
                              .tobytes()).hexdigest()

    counted = sha(_harvest_call())
    assert memsim._CALL["lent"] > 0
    monkeypatch.setattr(memsim, "_event_harvest_scale_jit",
                        jax.jit(_uncounted_scale))
    assert sha(_harvest_call()) == counted


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
