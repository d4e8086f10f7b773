"""The persistent QueueLUT store and its canonical stream contract.

Three layers, each pinned BITWISE (float32 tables under the default jax
config, so equality is exact, not approximate):

* **Canonical streams** -- with caller-owned ``stream_ids`` and the
  width-pinned ``canonical_chunk``, a cell's DES histogram is a pure
  function of (its channel values, its stream id, seed, budget, engine):
  a subset batch reproduces the superset's cells exactly.  This is the
  empirical-but-pinned contract everything else stands on (like the
  sharding bit-identity gate in ``test_shardsim.py``).
* **Incremental builds** -- ``build_queue_lut(base_lut=...)`` simulating
  only the missing cells equals the from-scratch build of the union
  grid, both engines, with and without the harvest axis.
* **The store** -- warm reads are bit-identical and run zero DES
  (``build_queue_lut`` is monkeypatched to explode, and the jit trace
  count is pinned flat); a fingerprint change misses (never serves a
  stale surface); a truncated artifact is quarantined and rebuilt, not
  crashed on.
"""

import numpy as np
import pytest

from repro.core import lutstore, memsim, queuelut
from repro.core.memsim import ChannelConfig

#: Tiny build parameters -- the contract is bitwise, not statistical, so
#: the budget only needs to exercise the code paths.
STEPS, SEED, REPS = 3_000, 0, 1
GRID = dict(rho=(0.2, 0.5, 0.8), kappa=(1.0, 2.0),
            outstanding=(8.0, 64.0), eta=(0.3, 1.0))
SUBGRID = dict(rho=(0.2, 0.8), kappa=(1.0, 2.0),
               outstanding=(8.0, 64.0), eta=(0.3, 1.0))


def lut_equal(a: queuelut.QueueLUT, b: queuelut.QueueLUT) -> bool:
    return all((x is None) == (y is None)
               and (x is None or np.array_equal(np.asarray(x),
                                                np.asarray(y)))
               for x, y in zip(a, b))


@pytest.fixture()
def store(tmp_path, monkeypatch):
    """Fresh on-disk store + empty in-process layer for every test."""
    monkeypatch.setenv(lutstore.ENV_VAR, str(tmp_path / "lut"))
    lutstore.clear_lut_cache()
    yield tmp_path / "lut"
    lutstore.clear_lut_cache()


class TestCanonicalStreams:
    @pytest.mark.parametrize("engine", memsim.ENGINES)
    def test_subset_batch_reproduces_superset_cells(self, engine):
        cfgs = [ChannelConfig(rho=r, kappa=k)
                for r in (0.3, 0.6, 0.85) for k in (1.0, 2.2)]
        names = ("rho", "kappa")
        coords = np.asarray([[c.rho, c.kappa] for c in cfgs])
        sids = queuelut.cell_stream_ids(names, coords)
        chunk = memsim.canonical_chunk(engine)
        kw = dict(steps=STEPS, seed=SEED, reps=2, engine=engine,
                  chunk=chunk)
        full = memsim.simulate_cells(memsim.stack_channels(cfgs),
                                     stream_ids=sids, **kw)
        pick = np.asarray([1, 4, 5])
        sub = memsim.simulate_cells(
            memsim.stack_channels([cfgs[i] for i in pick]),
            stream_ids=sids[pick], **kw)
        assert np.array_equal(np.asarray(sub.hist),
                              np.asarray(full.hist)[pick])

    def test_stream_ids_shape_checked(self):
        cfgs = [ChannelConfig(rho=0.3), ChannelConfig(rho=0.6)]
        with pytest.raises(ValueError, match="stream_ids"):
            memsim.simulate_cells(memsim.stack_channels(cfgs),
                                  steps=STEPS,
                                  stream_ids=np.zeros(3, np.uint32))

    def test_cell_ids_keyed_by_coordinates_not_order(self):
        names = ("rho", "kappa")
        a = queuelut.cell_stream_ids(names, [[0.2, 1.0], [0.5, 2.0]])
        b = queuelut.cell_stream_ids(names, [[0.5, 2.0], [0.2, 1.0]])
        assert a[0] == b[1] and a[1] == b[0]
        assert a[0] != a[1]


class TestIncrementalBuild:
    @pytest.mark.parametrize("engine", memsim.ENGINES)
    @pytest.mark.parametrize("harvest", [None, (0.0, 0.5)])
    def test_merge_equals_scratch_union(self, engine, harvest):
        kw = dict(steps=STEPS, seed=SEED, reps=REPS, engine=engine,
                  harvest=harvest)
        scratch = queuelut.build_queue_lut(**GRID, **kw)
        base = queuelut.build_queue_lut(**SUBGRID, **kw)
        grown = queuelut.build_queue_lut(**GRID, **kw, base_lut=base)
        assert lut_equal(scratch, grown)

    def test_axis_count_mismatch_rejected(self):
        base = queuelut.build_queue_lut(**SUBGRID, steps=STEPS, reps=REPS,
                                        engine="event")
        with pytest.raises(ValueError, match="harvest"):
            queuelut.build_queue_lut(**GRID, harvest=(0.0, 0.5),
                                     steps=STEPS, reps=REPS,
                                     engine="event", base_lut=base)


class TestStoreRoundTrip:
    @pytest.mark.parametrize("engine", memsim.ENGINES)
    @pytest.mark.parametrize("harvest", [None, (0.0, 0.5)])
    def test_warm_read_bit_identical_zero_des(self, store, monkeypatch,
                                              engine, harvest):
        kw = dict(steps=STEPS, seed=SEED, reps=REPS, engine=engine,
                  harvest=harvest)
        cold = queuelut.resolve_lut(**GRID, **kw)
        lutstore.clear_lut_cache()
        # A warm read may neither build nor trace the simulator.
        monkeypatch.setattr(
            queuelut, "build_queue_lut",
            lambda *a, **k: pytest.fail("warm read ran the DES"))
        n0 = memsim.sim_trace_count()
        warm = queuelut.resolve_lut(**GRID, **kw)
        assert memsim.sim_trace_count() == n0
        assert lut_equal(cold, warm)
        assert (warm.harvest_grid is None) == (harvest is None)

    def test_mem_layer_serves_without_disk(self, store):
        kw = dict(steps=STEPS, seed=SEED, reps=REPS, engine="event")
        lut = queuelut.resolve_lut(**GRID, **kw)
        for p in store.glob("qlut-*.npz"):
            p.unlink()
        assert queuelut.resolve_lut(**GRID, **kw) is lut

    def test_fingerprint_mismatch_forces_rebuild(self, store,
                                                 monkeypatch):
        kw = dict(steps=STEPS, seed=SEED, reps=REPS, engine="event")
        lut = queuelut.resolve_lut(**GRID, **kw)
        lutstore.clear_lut_cache()
        monkeypatch.setattr(lutstore, "_fingerprint_memo",
                            "f" * 64)
        builds = []
        real = queuelut.build_queue_lut

        def counting(*a, **k):
            builds.append(1)
            return real(*a, **k)

        monkeypatch.setattr(queuelut, "build_queue_lut", counting)
        rebuilt = queuelut.resolve_lut(**GRID, **kw)
        assert builds, "stale-fingerprint surface was served"
        assert lut_equal(lut, rebuilt)   # the DES itself is unchanged

    def test_other_platform_misses_cpu_surface(self, store, monkeypatch):
        kw = dict(steps=STEPS, seed=SEED, reps=REPS, engine="event")
        queuelut.resolve_lut(**GRID, **kw)
        lutstore.clear_lut_cache()
        monkeypatch.setattr(lutstore, "device_tag", lambda: dict(
            platform="tpu", device_kind="TPU v5 lite"))
        builds, real = [], queuelut.build_queue_lut
        monkeypatch.setattr(queuelut, "build_queue_lut",
                            lambda *a, **k: builds.append(1) or real(*a, **k))
        queuelut.resolve_lut(**GRID, **kw)
        assert builds == [1], "a CPU-built surface served another platform"
        assert len(list(store.glob("qlut-*.npz"))) == 2

    def test_corrupt_artifact_quarantined_not_crashed(self, store):
        kw = dict(steps=STEPS, seed=SEED, reps=REPS, engine="event")
        lut = queuelut.resolve_lut(**GRID, **kw)
        lutstore.clear_lut_cache()
        (path,) = store.glob("qlut-*.npz")
        path.write_bytes(path.read_bytes()[:100])     # truncate
        rebuilt = queuelut.resolve_lut(**GRID, **kw)
        assert lut_equal(lut, rebuilt)
        assert list(store.glob("*.corrupt"))
        assert lutstore.gc()["removed"] >= 1          # quarantine swept

    def test_gc_drops_stale_and_aged(self, store, monkeypatch):
        kw = dict(steps=STEPS, seed=SEED, reps=REPS, engine="event")
        queuelut.resolve_lut(**GRID, **kw)
        assert lutstore.gc()["removed"] == 0          # fresh entry kept
        assert lutstore.gc(max_age_days=-1.0)["removed"] == 1
        queuelut.resolve_lut(**SUBGRID, **kw)
        monkeypatch.setattr(lutstore, "_fingerprint_memo", "e" * 64)
        assert lutstore.gc()["removed"] == 1          # stale fingerprint

    def test_store_disabled_still_builds(self, monkeypatch):
        monkeypatch.delenv(lutstore.ENV_VAR, raising=False)
        lutstore.clear_lut_cache()
        lut = queuelut.resolve_lut(**SUBGRID, steps=STEPS, reps=REPS,
                                   engine="event")
        assert lut.wait_ns.shape == (2, 2, 2, 2)


class TestBoundedMemCache:
    def test_bounded_and_clearable(self):
        lutstore.clear_lut_cache()
        for i in range(lutstore.MEM_CACHE_MAX + 3):
            lutstore.cache_put(f"k{i}", object())
        assert len(lutstore._mem_cache) == lutstore.MEM_CACHE_MAX
        assert lutstore.cache_get("k0") is None       # LRU-evicted
        newest = f"k{lutstore.MEM_CACHE_MAX + 2}"
        assert lutstore.cache_get(newest) is not None
        lutstore.clear_lut_cache()
        assert lutstore.cache_get(newest) is None

    def test_default_queue_lut_no_lru_cache(self):
        # The historical unbounded functools.lru_cache is gone.
        assert not hasattr(queuelut.default_queue_lut, "cache_clear")
