"""Device-parallel DES: the sharded lane axis is a pure wall-clock knob.

The contract (see ``repro.core.shardsim``): lane-keyed threefry streams,
unpadded-width chunk budgets, and global-lane histogram slots make the
``shard_map`` path BIT-IDENTICAL to the single-device path per cell --
for both engines, at any device count, divisible or not.  These tests
run under the 4 forced host devices the root ``conftest.py`` sets up.
"""

import jax
import numpy as np
import pytest

from repro.core import coaxial, memsim, queuelut, shardsim
from repro.core.memsim import ChannelConfig

NDEV = len(jax.devices())

needs_multi = pytest.mark.skipif(
    NDEV < 4, reason="needs >= 4 (forced) host devices")

#: Five heterogeneous cells: a non-divisible width on 4 devices, so the
#: NaN-padding path is exercised, plus a kappa/outstanding/eta spread.
CELLS = [ChannelConfig(rho=0.3),
         ChannelConfig(rho=0.6, kappa=2.0),
         ChannelConfig(rho=0.8, outstanding=8.0),
         ChannelConfig(rho=0.5, cxl_lat_ns=60.0),
         ChannelConfig(rho=0.7, eta=0.3)]

#: The retired environment knob: the library must ignore it.
RETIRED_ENV = "REPRO_DES_DEVICES"


class TestResolveDevices:
    def test_default_is_one(self):
        assert shardsim.resolve_devices() == 1

    def test_environment_is_not_read(self, monkeypatch):
        monkeypatch.setenv(RETIRED_ENV, "2")
        assert shardsim.resolve_devices() == 1

    def test_explicit_values(self):
        assert shardsim.resolve_devices(2) == 2
        assert shardsim.resolve_devices("auto") == NDEV

    def test_rejects_bad_values(self):
        with pytest.raises(ValueError, match=">= 1"):
            shardsim.resolve_devices(0)
        with pytest.raises(ValueError, match="exceeds"):
            shardsim.resolve_devices(len(jax.devices()) + 1)
        with pytest.raises(ValueError, match="int, 'auto' or None"):
            shardsim.resolve_devices("fast")

    def test_pad_width(self):
        assert shardsim.pad_width(5, 4) == 3
        assert shardsim.pad_width(8, 4) == 0
        assert shardsim.pad_width(1, 1) == 0


@needs_multi
class TestBitIdentical:
    @pytest.mark.parametrize("engine", memsim.ENGINES)
    def test_nondivisible_cells(self, engine):
        # 5 lanes over 4 devices: 3 NaN pad lanes, still bit-identical.
        a = memsim.simulate(CELLS, steps=40_000, seed=7, engine=engine,
                            devices=1)
        b = memsim.simulate(CELLS, steps=40_000, seed=7, engine=engine,
                            devices=4)
        np.testing.assert_array_equal(a.hist, b.hist)
        np.testing.assert_array_equal(a.mean_ns, b.mean_ns)

    @pytest.mark.parametrize("engine", memsim.ENGINES)
    def test_divisible_with_reps(self, engine):
        # 4 cells x 3 reps = 12 lanes: divides evenly, reps-tiled lanes
        # keep their global indices, merged stats match bitwise.
        cfgs = CELLS[:4]
        a = memsim.simulate(cfgs, steps=40_000, seed=3, reps=3,
                            engine=engine, devices=1)
        b = memsim.simulate(cfgs, steps=40_000, seed=3, reps=3,
                            engine=engine, devices=4)
        np.testing.assert_array_equal(a.hist, b.hist)

    def test_keep_reps_matches_per_replica(self):
        a = memsim.simulate_cells(
            memsim.stack_channels(CELLS[:2]), steps=40_000, seed=5,
            reps=2, keep_reps=True, devices=1)
        b = memsim.simulate_cells(
            memsim.stack_channels(CELLS[:2]), steps=40_000, seed=5,
            reps=2, keep_reps=True, devices=4)
        np.testing.assert_array_equal(a.hist, b.hist)

    def test_devices_none_is_one_device(self, monkeypatch):
        monkeypatch.setenv(RETIRED_ENV, "4")
        a = memsim.simulate(CELLS[:3], steps=30_000, seed=1)
        b = memsim.simulate(CELLS[:3], steps=30_000, seed=1, devices=1)
        np.testing.assert_array_equal(a.hist, b.hist)


@needs_multi
class TestEntryPoints:
    def test_distribution_sweep_device_invariant(self):
        kw = dict(rho=(0.3, 0.7), outstanding=(8.0, 256.0),
                  steps=30_000, reps=2)
        a = coaxial.distribution_sweep(devices=1, **kw)
        b = coaxial.distribution_sweep(devices=4, **kw)
        np.testing.assert_array_equal(a.stats.hist, b.stats.hist)
        np.testing.assert_array_equal(a.stats.mean_ns, b.stats.mean_ns)

    def test_build_queue_lut_device_invariant(self):
        kw = dict(rho=(0.3, 0.7), kappa=(1.0, 2.0),
                  outstanding=(8.0, 256.0), eta=(0.3, 1.0), steps=20_000)
        a = queuelut.build_queue_lut(devices=1, **kw)
        b = queuelut.build_queue_lut(devices=4, **kw)
        np.testing.assert_array_equal(np.asarray(a.wait_ns),
                                      np.asarray(b.wait_ns))
        np.testing.assert_array_equal(np.asarray(a.sigma_ns),
                                      np.asarray(b.sigma_ns))

    def test_validate_calibration_device_invariant(self):
        a = coaxial.validate_calibration(rhos=(0.4,), steps=30_000,
                                         reps=4, devices=1)
        b = coaxial.validate_calibration(rhos=(0.4,), steps=30_000,
                                         reps=4, devices=4)
        assert a["anchors"][0]["des_mean_ns"] == \
            b["anchors"][0]["des_mean_ns"]

    def test_crosscheck_reports_se_columns(self):
        cc = coaxial.crosscheck_engines(rhos=(0.4,), steps=30_000,
                                        reps=4, devices=NDEV)
        a = cc["anchors"][0]
        for col in ("mean_se_ns", "mean_z", "p90_se_ns", "p90_z"):
            assert col in a and np.isfinite(a[col])
        assert cc["se_k"] == coaxial.ENGINE_SE_K


@needs_multi
class TestTracePins:
    @pytest.mark.parametrize("engine", memsim.ENGINES)
    def test_one_trace_per_width_and_devices(self, engine):
        # A fresh (width, devices) pair traces the engine body exactly
        # once; repeating it is a pure cache hit.  Width 7 is unused by
        # any other test in this module.
        cfgs = [ChannelConfig(rho=0.1 * i + 0.2) for i in range(7)]
        memsim.simulate(cfgs, steps=4_000, seed=0, engine=engine,
                        devices=4)                     # warm the cache
        before = memsim.sim_trace_count(engine)
        memsim.simulate(cfgs, steps=4_000, seed=1, engine=engine,
                        devices=4)
        assert memsim.sim_trace_count(engine) == before  # cache hit
