"""The two-engine contract of memsim.

  * the timestep engine (lane-keyed streams + scan-emitted
    ``(latency, mask)`` + one post-scan histogram) is BIT-IDENTICAL to
    an in-scan-scatter reference that re-derives the stream contract
    (chunk keys split from the seed, one threefry stream per lane via
    ``fold_in``) and every law from scratch -- pinned by comparing
    histograms exactly;
  * the event engine reproduces exactly per seed, costs one kernel
    trace per flattened cell count (its own counter, independent of the
    timestep engine's), honours the closed-loop ``outstanding`` bound,
    and shifts with the CXL premium;
  * the engines agree statistically: event vs timestep mean within 10%
    and p90 within 15% at every ``validate_calibration`` rho anchor
    (``coaxial.crosscheck_engines``), and the event engine passes the
    SAME closed-form mean/p90/stdev gates as the timestep engine;
  * the shared ns-budget knob is engine-neutral and validated
    (``benchmarks.common.des_budget`` / ``des_engine``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import coaxial, memsim
from repro.core.memsim import ChannelConfig


class TestTimestepMicroOpt:
    """Satellite: the emission-based timestep engine vs the old scatter."""

    @staticmethod
    def _old_scatter_sim(configs, steps, seed, warmup):
        """The in-scan-scatter reference core: per-step histogram scatter
        carried through one scan per chunk (the historical accumulation
        scheme), re-deriving the production stream contract and every
        law from scratch."""
        c = memsim.stack_channels(configs)
        n = int(c.rho.shape[0])
        # Derived terms spelled out verbatim (NOT via memsim helpers), so
        # a drift anywhere in the production laws fails this pin.
        rate_avg = c.rho / c.t_xfer_ns
        rate_hi = jnp.minimum(c.kappa * rate_avg, 0.98)
        rate_lo = jnp.maximum(
            (rate_avg - c.burst_duty * rate_hi) / (1.0 - c.burst_duty), 0.0)
        p_leave = 1.0 / c.burst_sojourn_ns
        p_enter = p_leave * c.burst_duty / (1.0 - c.burst_duty)
        sn, xb = c.stall_ns, c.stall_break_ns
        a1, a2, cap = c.stall_alpha, c.stall_alpha2, c.stall_max_ns
        q_b = (sn / xb) ** a1
        p_stall = jnp.clip(c.stall_prob * c.eta, 0.0, 0.999)

        def pareto_seg(ratio, a):
            d = a - 1.0
            near_one = jnp.abs(d) < 1e-4
            safe = jnp.where(near_one, 1.0, d)
            return jnp.where(near_one, -jnp.log(ratio),
                             (1.0 - ratio ** safe) / safe)

        stall_mean = (sn + sn * pareto_seg(sn / xb, a1) +
                      q_b * xb * pareto_seg(xb / cap, a2))
        s_small = ((c.t_xfer_ns - p_stall * stall_mean) /
                   (1.0 - p_stall))
        s_small = jnp.maximum(s_small, memsim.MIN_SERVICE_NS)
        bound = c.outstanding * c.t_xfer_ns
        lat0 = c.service_ns + 2.0 + c.cxl_lat_ns

        # The stream contract, re-derived: one chunk key per emission
        # chunk (split from the seed), ONE threefry stream per lane
        # (fold_in of the lane index), five uniforms per step per lane.
        chunk = memsim._ts_chunk_len(n)
        n_chunks = -(-steps // chunk)
        ckeys = jax.random.split(jax.random.PRNGKey(seed), n_chunks)
        record = np.zeros(n_chunks * chunk, np.float32)
        record[warmup:steps] = 1.0

        @jax.jit
        def run_chunk(state, key, rec):
            lanes = jnp.arange(n, dtype=jnp.int32)
            lane_keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(lanes)
            u5 = jax.vmap(lambda k: jax.random.uniform(k, (chunk, 5))
                          )(lane_keys)
            switch_u, arrive_u, jitter_u, svc_u, size_u = \
                jnp.moveaxis(u5, -1, 0)                 # each (n, chunk)
            jitter = (jitter_u * 2.0 - 1.0) * c.service_jitter_ns[:, None]
            u = jnp.maximum(size_u, 1e-7)
            stall = jnp.where(u > q_b[:, None],
                              sn[:, None] * u ** (-1.0 / a1[:, None]),
                              xb[:, None] * (q_b[:, None] / u)
                              ** (1.0 / a2[:, None]))
            stall = jnp.minimum(stall, cap[:, None])
            svc = jnp.where(svc_u < p_stall[:, None], stall,
                            s_small[:, None])

            def step(carry, xs):
                sw, au, jit_ns, s, rec1 = xs
                backlog, in_burst, hist = carry
                in_burst = jnp.where(
                    in_burst > 0.5,
                    jnp.where(sw < p_leave, 0.0, 1.0),
                    jnp.where(sw < p_enter, 1.0, 0.0))
                rate = jnp.where(in_burst > 0.5, rate_hi, rate_lo)
                arrive = (au < rate).astype(jnp.float32)
                arrive = arrive * (backlog <= bound).astype(jnp.float32)
                latency = backlog + lat0 + jit_ns
                bin_idx = jnp.clip(
                    (latency / memsim.BIN_NS).astype(jnp.int32),
                    0, memsim.N_BINS - 1)
                hist = hist.at[jnp.arange(n), bin_idx].add(arrive * rec1)
                backlog = jnp.maximum(backlog + arrive * s - 1.0, 0.0)
                return (backlog, in_burst, hist), None

            return jax.lax.scan(
                step, state,
                (switch_u.T, arrive_u.T, jitter.T, svc.T, rec))[0]

        state = (jnp.zeros(n), jnp.ones(n), jnp.zeros((n, memsim.N_BINS)))
        for k in range(n_chunks):
            state = run_chunk(state, ckeys[k],
                              jnp.asarray(record[k * chunk:(k + 1) * chunk]))
        return np.asarray(state[2], np.float64)

    def test_before_after_histograms_bit_identical(self):
        configs = [ChannelConfig(rho=0.35),
                   ChannelConfig(rho=0.75, kappa=2.0, cxl_lat_ns=30.0),
                   ChannelConfig(rho=0.8, outstanding=8.0)]
        for steps, seed in ((20_000, 5), (30_000, 11)):
            old = self._old_scatter_sim(configs, steps, seed, steps // 10)
            new = memsim.simulate(configs, steps=steps, seed=seed)
            np.testing.assert_array_equal(old, new.hist)

    def test_nonchunk_aligned_steps(self):
        # steps that are not a multiple of the emission chunk exercise
        # the padded tail (dummy keys, zero record): still bit-identical.
        configs = [ChannelConfig(rho=0.6)]
        steps = 10_000  # < one chunk
        old = self._old_scatter_sim(configs, steps, 3, steps // 10)
        new = memsim.simulate(configs, steps=steps, seed=3)
        np.testing.assert_array_equal(old, new.hist)


class TestEventEngine:
    def test_exact_seed_reproducibility(self):
        a = memsim.simulate([ChannelConfig(rho=0.6)], steps=30_000, seed=9,
                            engine="event")
        b = memsim.simulate([ChannelConfig(rho=0.6)], steps=30_000, seed=9,
                            engine="event")
        np.testing.assert_array_equal(a.hist, b.hist)
        c = memsim.simulate([ChannelConfig(rho=0.6)], steps=30_000, seed=10,
                            engine="event")
        assert not np.array_equal(a.hist, c.hist)

    def test_one_trace_per_grid_per_engine(self):
        # A fresh flattened cell count forces one trace of the EVENT
        # kernel; the timestep counter must not move.
        spec = coaxial.distribution_spec(rho=(0.25, 0.45, 0.65),
                                         kappa=(1.0, 1.9),
                                         cxl_lat_ns=(0.0, 25.0),
                                         stall_ns=(37.0,))
        before_ev = memsim.sim_trace_count("event")
        before_ts = memsim.sim_trace_count("timestep")
        sw = coaxial.distribution_sweep(spec, steps=25_000, engine="event")
        assert sw.shape == (3, 2, 2, 1)
        assert sw.engine == "event"
        assert memsim.sim_trace_count("event") == before_ev + 1
        assert memsim.sim_trace_count("timestep") == before_ts
        # Same flattened size + budget, different axis values: cache hit.
        coaxial.distribution_sweep(
            coaxial.distribution_spec(rho=(0.15, 0.3, 0.7),
                                      kappa=(1.2, 2.4),
                                      stall_prob=(0.01, 0.02),
                                      outstanding=(64.0,)),
            steps=25_000, engine="event")
        assert memsim.sim_trace_count("event") == before_ev + 1

    def test_outstanding_monotone_closed_loop(self):
        sw = coaxial.distribution_sweep(
            rho=(0.8,), outstanding=(4.0, 1e9), steps=120_000, reps=4,
            engine="event")
        tight = float(sw.cell(rho=0.8, outstanding=4.0).mean_ns)
        open_ = float(sw.cell(rho=0.8, outstanding=1e9).mean_ns)
        assert tight < open_
        # The tight bound caps the admitted backlog at ~outstanding
        # requests' worth of work (plus service terms).
        assert tight < 4.0 * 1.67 + 40.0 + 3 * memsim.BIN_NS

    def test_cxl_premium_shifts_distribution(self):
        s = memsim.simulate(
            [ChannelConfig(rho=0.3), ChannelConfig(rho=0.3, cxl_lat_ns=30.0)],
            steps=150_000, seed=1, reps=8, engine="event")
        assert (s.mean_ns[1] - s.mean_ns[0]
                == pytest.approx(30.0, abs=2.5 * memsim.BIN_NS))

    def test_extreme_jitter_width_clamps_into_edge_bins(self):
        # A jitter wider than the histogram span must clamp (like the
        # timestep engine's bin clip), not crash the convolution.
        s = memsim.simulate(
            [ChannelConfig(rho=0.2, service_jitter_ns=5000.0)],
            steps=20_000, seed=0, engine="event")
        assert np.isfinite(s.hist).all()
        assert s.hist.sum() > 0

    def test_engine_and_budget_validation(self):
        with pytest.raises(ValueError, match="unknown engine"):
            memsim.simulate([ChannelConfig(rho=0.5)], steps=1_000,
                            engine="warp")
        with pytest.raises(ValueError, match="event-engine budget"):
            memsim.simulate_cells(
                memsim.stack_channels([ChannelConfig(rho=0.5)]),
                steps=1_000, events=500, engine="timestep")
        with pytest.raises(ValueError, match="unknown engine"):
            memsim.sim_trace_count("warp")

    def test_jitter_convolution_mass_and_spread(self):
        # The event engine convolves the uniform jitter into the
        # histogram; mass is conserved and the spread matches a sampled
        # jitter within binning.
        narrow = memsim.simulate(
            [ChannelConfig(rho=0.1, service_jitter_ns=0.0)],
            steps=80_000, seed=2, engine="event")
        wide = memsim.simulate(
            [ChannelConfig(rho=0.1, service_jitter_ns=13.5)],
            steps=80_000, seed=2, engine="event")
        assert wide.hist.sum() == pytest.approx(narrow.hist.sum())
        assert float(wide.stdev_ns[0]) > float(narrow.stdev_ns[0])
        assert float(wide.mean_ns[0]) == pytest.approx(
            float(narrow.mean_ns[0]), abs=memsim.BIN_NS)


class TestEngineAgreement:
    """Event vs timestep at the closed-form anchors (the statistical
    counterpart of the timestep engine's bit-identity pin)."""

    @pytest.fixture(scope="class")
    def cc(self):
        return coaxial.crosscheck_engines(steps=200_000, seed=0, reps=64)

    def test_mean_within_10pct_at_every_anchor(self, cc):
        for a in cc["anchors"]:
            assert abs(a["mean_err"]) <= 0.10, (
                f"rho={a['rho']}: event mean {a['event_mean_ns']:.1f} vs "
                f"timestep {a['timestep_mean_ns']:.1f} "
                f"({a['mean_err']:+.1%})")

    def test_p90_within_15pct_at_every_anchor(self, cc):
        for a in cc["anchors"]:
            assert abs(a["p90_err"]) <= 0.15, (
                f"rho={a['rho']}: event p90 {a['event_p90_ns']:.1f} vs "
                f"timestep {a['timestep_p90_ns']:.1f} "
                f"({a['p90_err']:+.1%})")

    def test_ok_flag(self, cc):
        assert cc["ok"]
        assert cc["max_abs_mean_err"] <= cc["mean_tol"]
        assert cc["max_abs_p90_err"] <= cc["p90_tol"]
        assert cc["sweeps"]["event"].engine == "event"

    def test_event_passes_closed_form_gates(self):
        # Same gates as the timestep engine's cross-validation
        # (tests/test_distribution_sweep.py): mean 15%, p90 20%,
        # stdev 125% against queueing.closed_form_stats per anchor.
        val = coaxial.validate_calibration(engine="event", steps=200_000,
                                           seed=3, reps=48)
        assert val["engine"] == "event"
        assert val["ok"], (val["max_abs_mean_err"], val["max_abs_p90_err"],
                           val["max_abs_stdev_err"])


class TestBudgetHelpers:
    def test_des_budget_caps_both_engines(self, monkeypatch):
        from benchmarks import common
        monkeypatch.delenv("REPRO_DES_STEPS", raising=False)
        assert common.des_budget(120_000) == 120_000
        monkeypatch.setenv("REPRO_DES_STEPS", "40000")
        assert common.des_budget(120_000, engine="timestep") == 40_000
        assert common.des_budget(120_000, engine="event") == 40_000
        with pytest.raises(ValueError, match="unknown engine"):
            common.des_budget(120_000, engine="warp")

    def test_des_engine_env_override(self, monkeypatch):
        from benchmarks import common
        monkeypatch.delenv("REPRO_DES_ENGINE", raising=False)
        assert common.des_engine() == "timestep"
        assert common.des_engine("event") == "event"
        monkeypatch.setenv("REPRO_DES_ENGINE", "event")
        assert common.des_engine() == "event"
        monkeypatch.setenv("REPRO_DES_ENGINE", "warp")
        with pytest.raises(ValueError, match="not an engine"):
            common.des_engine()

    def test_events_for_steps_reference_rate(self):
        assert memsim.events_for_steps(200_000) == pytest.approx(
            200_000 * memsim.EVENTS_PER_NS, rel=0.01)
