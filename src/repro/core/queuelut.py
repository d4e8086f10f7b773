"""DES-derived queue-wait lookup surface: the mechanism as a solver backend.

``cpu_model``'s fixed point needs, per workload and per iteration, the
DRAM-side queue wait at an operating point (utilization ``rho``, burstiness
``kappa``, closed-loop population ``outstanding``, DRAM-sensitivity ``eta``).
The closed form (``queueing.effective_queue_wait_ns``) answers that
analytically; this module answers it *mechanistically*: one batched
``coaxial.distribution_sweep`` runs the DES (``memsim``) over a
(rho, kappa, outstanding, eta) grid -- ``outstanding`` is a real simulated
field (the finite in-flight population that caps the FIFO backlog) and
``eta`` scales the blocking-episode probability at fixed mean service
time (the per-workload DRAM-sensitivity knob, now INSIDE the mechanism
instead of a post-hoc multiplier on the wait) -- and the resulting
latency distributions are reduced to four tables (mean wait / p90 wait /
p99 wait / latency stdev).

:class:`QueueLUT` is a pytree of those tables plus their grids, with
**differentiable multilinear interpolation**: the lookup is piecewise
linear in the query point (quadrilinear over the 4-D grid, with the
``outstanding`` axis located in LOG space -- its grid is geometric, so
log-space fractions interpolate the curvature instead of chord-cutting
it), clamped to the grid hull, and pure ``jnp`` -- so ``cpu_model`` can
pass a LUT straight into its jitted cell solver (any named-axis grid
still lowers to ONE trace per flattened cell count) and
``design_gradient`` can differentiate through the fixed point *and* the
table.  Passing ``lut=None`` to the solver selects the closed form; the
pytree-structure difference is what keys the jit cache, no static flags
needed.

Build cost: the default surface (14 x 6 x 6 x 4 grid) is one batched run
of the per-request event engine -- the 4th axis is what the
device-parallel DES (``memsim``'s ``devices`` argument,
``core/shardsim``) buys; pass ``devices=`` to shard the build,
bit-identically.  :func:`default_queue_lut` caches it per (steps, seed,
reps, engine), so a whole session pays for it once.
"""

from __future__ import annotations

import hashlib
import time
from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from repro.core import hw, lutstore
from repro.core.lutstore import clear_lut_cache  # noqa: F401 -- re-export

#: Default utilization grid: denser near saturation, where the open-loop
#: hyperbola is steep and linear interpolation would otherwise smear the
#: knee of the load-latency curve.  One notch finer than the original
#: 12-point grid (extra knee points at 0.62..0.91) -- affordable because
#: the default build engine is the per-request event engine, the first
#: step of the ROADMAP's LUT-resolution study.
DEFAULT_RHO_GRID = (0.05, 0.15, 0.25, 0.35, 0.45, 0.55, 0.62, 0.68,
                    0.74, 0.79, 0.84, 0.88, 0.91, 0.93)
#: Default burstiness grid (covers the Table-4 suite values 1.3..1.6 and
#: the synthetic-sweep range up to 3.2; 2.7 fills the former 2.2 -> 3.2
#: gap).
DEFAULT_KAPPA_GRID = (1.0, 1.3, 1.6, 2.2, 2.7, 3.2)
#: Default closed-loop population grid: ``n_active * MAX_MLP /
#: dram_channels`` spans ~2 (8 channels, 1 core) to 192 (the 12-core,
#: 1-channel DDR baseline); GEOMETRIC spacing -- the lookup interpolates
#: this axis in log space, where these points are near-uniform.
DEFAULT_OUTSTANDING_GRID = (2.0, 4.0, 8.0, 24.0, 64.0, 192.0)
#: Default DRAM-sensitivity grid: the Table-4 suite's eta spans ~0.05
#: (cache-friendly codes barely touch the far tail) to 1.0 (stream-like
#: codes take every blocking episode); the surface is near-linear in eta
#: (the wait is dominated by the episode-probability term), so four
#: points carry it.
DEFAULT_ETA_GRID = (0.05, 0.30, 0.60, 1.0)
#: Optional 5th axis: lent-time fraction of the idle-I/O harvesting chain
#: (arXiv 2511.12349 on top of CoaXiaL).  The table is built at the
#: REFERENCE lent bandwidth :data:`HARVEST_REF_BW_GBPS` -- one DDR5
#: channel's worth, which is also what one lent CXL x8 link contributes
#: (26 + 13 GB/s goodput) -- so the axis coordinate is "fraction of time
#: one extra channel's bandwidth is present".  Queries at other lent
#: bandwidths map through ``duty_eff = duty * bw / ref`` (see
#: ``cpu_model._latency_terms``).  Chosen over a plain effective-rho
#: mapping, which violates the designer's 35%/4ns verification envelope
#: by up to 227% in the bursty open-loop corner (see
#: docs/ARCHITECTURE.md "Harvesting").
DEFAULT_HARVEST_GRID = (0.0, 0.25, 0.5, 0.75)
HARVEST_REF_BW_GBPS = hw.DDR5_CH_BW_GBPS
#: Default DES budget per cell (ns simulated) and replicas per cell.
DEFAULT_STEPS = 120_000
DEFAULT_REPS = 2
#: Default build engine: the per-request event engine (the timestep
#: reference builds the same surface several times slower --
#: ``benchmarks/memsim_speed.py`` times both and cross-checks the
#: tables).
DEFAULT_ENGINE = "event"


class QueueLUT(NamedTuple):
    """DES-measured queue-wait surface over (rho, kappa, outstanding, eta).

    A pytree of nine array leaves: four ascending coordinate grids and
    four ``(R, K, O, E)`` tables -- mean queue wait, p90 queue wait, p99
    queue wait, and latency standard deviation (all ns).  :meth:`lookup`
    interpolates all four multilinearly (clamped at the hull; the
    ``outstanding`` axis in log space), vectorizes over any broadcastable
    query shapes, works inside ``jit``, and is differentiable in the
    query point.  The p99 table is what makes the solver's tail path
    mechanistic: the event engine records every request exactly, so the
    99th percentile costs nothing extra at build time, and downstream the
    designer's SLO constraint differentiates straight through it.

    Example (a hand-built two-point surface; real tables come from
    :func:`build_queue_lut`)::

        >>> import jax.numpy as jnp
        >>> from repro.core.queuelut import QueueLUT
        >>> z = jnp.zeros((2, 2, 2, 2))
        >>> lut = QueueLUT(rho_grid=jnp.array([0.0, 1.0]),
        ...                kappa_grid=jnp.array([1.0, 2.0]),
        ...                outstanding_grid=jnp.array([1.0, 100.0]),
        ...                eta_grid=jnp.array([0.0, 1.0]),
        ...                wait_ns=z.at[1].set(80.0),
        ...                p90_wait_ns=z, p99_wait_ns=z, sigma_ns=z)
        >>> float(lut.wait(0.5, 1.0, 1.0, 1.0))  # halfway up the rho edge
        40.0
        >>> float(lut.wait(2.0, 1.0, 1.0, 1.0))  # clamped at the grid hull
        80.0
        >>> float(lut.wait(0.5, 1.0, 10.0, 1.0))  # log-space outstanding:
        40.0
    """

    rho_grid: jnp.ndarray          # (R,) ascending
    kappa_grid: jnp.ndarray        # (K,) ascending
    outstanding_grid: jnp.ndarray  # (O,) ascending, positive
    eta_grid: jnp.ndarray          # (E,) ascending
    wait_ns: jnp.ndarray           # (R, K, O, E[, H]) mean queue wait
    p90_wait_ns: jnp.ndarray       # (R, K, O, E[, H]) p90 queue wait
    p99_wait_ns: jnp.ndarray       # (R, K, O, E[, H]) p99 queue wait
    sigma_ns: jnp.ndarray          # (R, K, O, E[, H]) latency stdev
    #: Optional 5th axis (None => 4-D tables): lent-time fraction of the
    #: idle-I/O harvesting chain at the reference lent bandwidth.
    harvest_grid: jnp.ndarray | None = None

    def lookup(self, rho, kappa, outstanding, eta=1.0, harvest=0.0):
        """Interpolated ``(mean wait, p90 wait, p99 wait, sigma)``.

        Queries broadcast together; out-of-grid coordinates clamp to the
        nearest hull face (constant extrapolation -- the DES was not run
        there, so the table refuses to invent a steeper law).  The
        ``outstanding`` fraction is computed in log space: its grid is
        geometric, and a query like 96 on a (64, 192) cell should sit
        near the geometric midpoint, not 1/4 from the top.

        ``harvest`` queries the optional 5th axis; on a 4-D surface
        (``harvest_grid is None``) it is IGNORED -- callers that need the
        harvested mechanism must build with ``harvest=`` (``cpu_model``
        resolves the right surface and raises on a mismatch).  A
        ``harvest=0.0`` query on a 5-D surface lands exactly on the
        duty-0 grid plane (the grid starts at 0), so unharvested lookups
        interpolate the same cells either way.
        """
        q = (rho, kappa, outstanding, eta)
        logs = (False, False, True, False)
        grids = (self.rho_grid, self.kappa_grid, self.outstanding_grid,
                 self.eta_grid)
        if self.harvest_grid is not None:
            q += (harvest,)
            logs += (False,)
            grids += (self.harvest_grid,)
        pts = jnp.broadcast_arrays(*(jnp.asarray(x, self.wait_ns.dtype)
                                     for x in q))
        loc = [_locate(g, p, log=lg)
               for g, p, lg in zip(grids, pts, logs)]
        return tuple(_blend(t, loc) for t in
                     (self.wait_ns, self.p90_wait_ns, self.p99_wait_ns,
                      self.sigma_ns))

    def wait(self, rho, kappa, outstanding, eta=1.0, harvest=0.0):
        """Interpolated mean queue wait alone (ns)."""
        return self.lookup(rho, kappa, outstanding, eta, harvest)[0]


def _locate(grid, x, log: bool = False):
    """(lower index, fraction) of ``x`` on an ascending grid, clamped.

    The fraction is what gradients flow through (piecewise linear); the
    index is integer and carries none, which is exactly the derivative a
    multilinear surface has.  ``log=True`` computes the fraction between
    the bracketing points in log space -- true geometric interpolation
    for geometrically spaced grids (the grid must be positive).
    """
    x = jnp.clip(x, grid[0], grid[-1])
    i = jnp.clip(jnp.searchsorted(grid, x, side="right") - 1,
                 0, grid.shape[0] - 2)
    lo, hi = grid[i], grid[i + 1]
    if log:
        t = jnp.log(x / lo) / jnp.log(hi / lo)
    else:
        t = (x - lo) / (hi - lo)
    return i, jnp.clip(t, 0.0, 1.0)


def _blend(table, loc):
    """Multilinear blend of the ``2**d`` corner cells around a located
    point (``d = len(loc)`` grid axes)."""
    out = 0.0
    for corner in range(2 ** len(loc)):
        w = 1.0
        idx = []
        for d, (i, t) in enumerate(loc):
            hi = (corner >> d) & 1
            w = w * (t if hi else 1.0 - t)
            idx.append(i + hi)
        out = out + w * table[tuple(idx)]
    return out


def _check_grid(name, grid, positive: bool = False):
    g = np.asarray(grid, np.float64)
    if g.ndim != 1 or g.size < 2:
        raise ValueError(f"{name} grid needs >= 2 points, got {g.shape}")
    if not np.all(np.diff(g) > 0):
        raise ValueError(f"{name} grid must be strictly ascending: "
                         f"{g.tolist()}")
    if positive and g[0] <= 0:
        raise ValueError(f"{name} grid must be positive (it interpolates "
                         f"in log space): {g.tolist()}")
    return tuple(float(v) for v in g)


#: Salt of the per-cell stream-id hash (bump to re-draw every surface).
_CELL_SALT = b"qlut-cell-v1:"


def cell_stream_ids(names, coords) -> np.ndarray:
    """Per-cell uint32 stream ids keyed by the cell's COORDINATES.

    ``names`` are the axis field names, ``coords`` an ``(N, d)`` float64
    coordinate matrix; the id is the first 32 bits of a sha256 over the
    exact (hex-formatted) coordinate values.  Keying streams by
    coordinates instead of batch position -- together with the pinned
    chunk schedule (``memsim.canonical_chunk``) -- makes every LUT cell's
    DES result independent of which other cells share the batch: a grid
    grown incrementally (``build_queue_lut(base_lut=...)``) is bit-
    identical to the same grid built from scratch, and a refinement
    probe re-simulating one cell reproduces the table entry exactly.
    """
    names = tuple(names)
    coords = np.asarray(coords, np.float64)
    ids = np.empty(coords.shape[0], np.uint32)
    for i, row in enumerate(coords):
        body = ";".join(f"{n}={float(v).hex()}"
                        for n, v in zip(names, row))
        h = hashlib.sha256(_CELL_SALT + body.encode()).digest()
        ids[i] = int.from_bytes(h[:4], "little")
    return ids


def _grid_axes(rho, kappa, outstanding, eta, harvest):
    """Validate grids; returns the ordered axes dict (+ checked grids)."""
    rho = _check_grid("rho", rho)
    kappa = _check_grid("kappa", kappa)
    outstanding = _check_grid("outstanding", outstanding, positive=True)
    eta = _check_grid("eta", eta)
    axes = dict(rho=rho, kappa=kappa, outstanding=outstanding, eta=eta)
    if harvest is not None:
        harvest = _check_grid("harvest", harvest)
        if harvest[0] < 0.0 or harvest[-1] >= 1.0:
            raise ValueError(f"harvest (duty) grid must lie in [0, 1): "
                             f"{list(harvest)}")
        axes["harvest_duty"] = harvest
    return axes, harvest


def _cell_coords(axes: dict) -> np.ndarray:
    """(N, d) float64 coordinates of the C-order flattened grid --
    exactly the flat cell order of ``coaxial.distribution_sweep``."""
    mesh = np.meshgrid(*(np.asarray(g, np.float64) for g in axes.values()),
                       indexing="ij")
    return np.stack([m.ravel() for m in mesh], axis=-1)


def _base_cell_map(axes: dict, base_lut: QueueLUT):
    """(present mask, base flat indices) of target cells found in a base.

    A target cell is PRESENT when every coordinate matches a base grid
    point exactly (compared in float32 -- the dtype the grids live at in
    the pytree).  Returns the boolean ``(N,)`` mask and, for the present
    cells, their flat C-order indices into the base tables.
    """
    base_grids = [np.asarray(g) for g in
                  (base_lut.rho_grid, base_lut.kappa_grid,
                   base_lut.outstanding_grid, base_lut.eta_grid)]
    if base_lut.harvest_grid is not None:
        base_grids.append(np.asarray(base_lut.harvest_grid))
    if len(base_grids) != len(axes):
        raise ValueError(
            "base_lut axis count does not match the target grid: "
            f"{len(base_grids)} vs {len(axes)} (harvest mismatch?)")
    shape = tuple(len(g) for g in axes.values())
    maps = []
    for tgt, bg in zip(axes.values(), base_grids):
        tgt32 = np.asarray(tgt, np.float32)
        m = np.full(len(tgt32), -1, np.int64)
        for j, v in enumerate(tgt32):
            hit = np.flatnonzero(bg == v)
            if hit.size:
                m[j] = hit[0]
        maps.append(m)
    idx = np.stack(np.meshgrid(*(np.arange(s) for s in shape),
                               indexing="ij"), -1).reshape(-1, len(shape))
    base_pos = np.stack([maps[a][idx[:, a]] for a in range(len(shape))],
                        axis=-1)
    present = (base_pos >= 0).all(axis=-1)
    base_shape = tuple(len(g) for g in base_grids)
    flat = (np.ravel_multi_index(base_pos[present].T, base_shape)
            if present.any() else np.empty(0, np.int64))
    return present, flat


def build_queue_lut(*, rho=DEFAULT_RHO_GRID, kappa=DEFAULT_KAPPA_GRID,
                    outstanding=DEFAULT_OUTSTANDING_GRID,
                    eta=DEFAULT_ETA_GRID, harvest=None,
                    harvest_bw_gbps: float = HARVEST_REF_BW_GBPS,
                    steps: int = DEFAULT_STEPS, seed: int = 0,
                    reps: int = DEFAULT_REPS, base=None,
                    engine: str = DEFAULT_ENGINE,
                    devices=None, base_lut: QueueLUT | None = None
                    ) -> QueueLUT:
    """Run ONE batched distribution sweep and reduce it to a QueueLUT.

    The whole (rho x kappa x outstanding x eta) grid lowers to one jitted
    simulation (``coaxial.distribution_sweep``); the wait tables are
    the DES latency means/p90s minus the unloaded DRAM service time, and
    the sigma table is the DES latency stdev verbatim -- the measured
    replacement for ``queueing.stdev_latency_ns``'s heuristic.
    ``engine`` picks the memsim engine; ``devices`` shards the build's
    flattened cell batch over host devices (``None`` means 1) -- the
    default 4-D grid is what the sharded DES buys, and the tables are
    bit-identical at any device count.

    Every build runs under the CANONICAL stream contract: each cell's
    threefry streams are keyed by its coordinates
    (:func:`cell_stream_ids`) and the chunk schedule is width-pinned
    (``memsim.canonical_chunk``), so a cell's tables are a pure function
    of its coordinates + (steps, seed, reps, engine, base channel) --
    never of the surrounding grid.  That is what makes builds
    INCREMENTAL: ``base_lut`` (a surface previously built with the SAME
    build parameters) donates every cell whose coordinates it already
    covers; only the missing cells are simulated (one batched run) and
    the tables merged -- bit-identical to building the whole grid from
    scratch (pinned by ``tests/test_lutstore.py``).

    ``harvest`` (a duty grid in [0, 1), e.g.
    :data:`DEFAULT_HARVEST_GRID`) grows the optional 5th axis: the sweep
    gains a ``harvest_duty`` dimension and the base channel lends
    ``harvest_bw_gbps`` while lent (default: the reference one-channel
    bandwidth, see :data:`HARVEST_REF_BW_GBPS`).

    Example (tiny grid, doctest-sized budget)::

        >>> from repro.core.queuelut import build_queue_lut
        >>> lut = build_queue_lut(rho=(0.2, 0.6), kappa=(1.0, 2.0),
        ...                       outstanding=(8.0, 192.0),
        ...                       eta=(0.1, 1.0), steps=4000, reps=1)
        >>> lut.wait_ns.shape
        (2, 2, 2, 2)
        >>> bool(lut.wait(0.6, 1.0, 192.0, 1.0) >
        ...      lut.wait(0.2, 1.0, 192.0, 1.0))
        True
        >>> hlut = build_queue_lut(rho=(0.2, 0.6), kappa=(1.0, 2.0),
        ...                        outstanding=(8.0, 192.0),
        ...                        eta=(0.1, 1.0), harvest=(0.0, 0.5),
        ...                        steps=4000, reps=1)
        >>> hlut.wait_ns.shape
        (2, 2, 2, 2, 2)
    """
    from repro.core import coaxial, memsim  # runtime: import cycle
    axes, harvest = _grid_axes(rho, kappa, outstanding, eta, harvest)
    if harvest is not None and base is None:
        base = memsim.ChannelConfig(
            rho=0.5, harvest_bw_gbps=float(harvest_bw_gbps))
    coords = _cell_coords(axes)
    sids = cell_stream_ids(axes.keys(), coords)
    chunk = memsim.canonical_chunk(engine)
    shape = tuple(len(g) for g in axes.values())
    grids = tuple(axes.values())

    def stat_arrays(stats):
        return (np.maximum(np.asarray(stats.mean_ns, np.float64)
                           - hw.DRAM_SERVICE_NS, 0.0),
                np.maximum(np.asarray(stats.p90_ns, np.float64)
                           - hw.DRAM_SERVICE_NS, 0.0),
                np.maximum(np.asarray(stats.p99_ns, np.float64)
                           - hw.DRAM_SERVICE_NS, 0.0),
                np.asarray(stats.stdev_ns, np.float64))

    if base_lut is None:
        sw = coaxial.distribution_sweep(
            **axes, base=base, steps=int(steps), seed=int(seed),
            reps=int(reps), engine=engine, devices=devices,
            stream_ids=sids, chunk=chunk)
        tables = stat_arrays(sw.stats)
    else:
        present, base_flat = _base_cell_map(axes, base_lut)
        missing = np.flatnonzero(~present)
        spec = coaxial.distribution_spec(**axes)
        flat = coaxial.build_flat_memsim(spec, base=base)
        fresh = None
        if missing.size:
            cha = memsim.ChannelArrays(
                *(np.asarray(leaf)[missing] for leaf in flat["cha"]))
            ov = {f: np.asarray(v)[missing]
                  for f, v in flat["overrides"].items()}
            stats = memsim.simulate_cells(
                cha, overrides=ov, steps=int(steps), seed=int(seed),
                warmup=memsim.default_warmup(int(steps)),
                reps=int(reps), engine=engine, devices=devices,
                stream_ids=sids[missing], chunk=chunk)
            fresh = stat_arrays(stats)
        base_tables = (base_lut.wait_ns, base_lut.p90_wait_ns,
                       base_lut.p99_wait_ns, base_lut.sigma_ns)
        tables = []
        for t, bt in enumerate(base_tables):
            full = np.empty(coords.shape[0], np.float64)
            # float32 -> float64 -> float32 round-trips exactly, so
            # donated cells keep the base surface's bits.
            full[present] = np.asarray(bt, np.float64).ravel()[base_flat]
            if fresh is not None:
                full[missing] = fresh[t]
            tables.append(full)

    to_j = lambda x: jnp.asarray(np.asarray(x, np.float64))
    wait, p90, p99, sigma = (t.reshape(shape) for t in tables)
    return QueueLUT(
        rho_grid=to_j(grids[0]), kappa_grid=to_j(grids[1]),
        outstanding_grid=to_j(grids[2]), eta_grid=to_j(grids[3]),
        wait_ns=to_j(wait), p90_wait_ns=to_j(p90),
        p99_wait_ns=to_j(p99), sigma_ns=to_j(sigma),
        harvest_grid=None if harvest is None else to_j(harvest))


def _store_params(axes: dict, harvest, harvest_bw_gbps, steps, seed,
                  reps, engine, base) -> dict:
    """The canonical JSON-able param dict behind a store key.

    ``devices`` is deliberately absent: tables are device-count
    invariant (``tests/test_shardsim.py`` pins it), so any device layout
    may share one entry.
    """
    import dataclasses
    base_fields = (None if base is None
                   else {k: float(v) for k, v in
                         sorted(dataclasses.asdict(base).items())})
    return dict(schema="queue_lut",
                axes={n: list(g) for n, g in axes.items()},
                harvest_bw_gbps=(float(harvest_bw_gbps)
                                 if harvest is not None else None),
                steps=int(steps), seed=int(seed), reps=int(reps),
                engine=str(engine), base=base_fields)


def resolve_lut(*, rho=DEFAULT_RHO_GRID, kappa=DEFAULT_KAPPA_GRID,
                outstanding=DEFAULT_OUTSTANDING_GRID,
                eta=DEFAULT_ETA_GRID, harvest=None,
                harvest_bw_gbps: float = HARVEST_REF_BW_GBPS,
                steps: int = DEFAULT_STEPS, seed: int = 0,
                reps: int = DEFAULT_REPS, base=None,
                engine: str = DEFAULT_ENGINE, devices=None,
                base_lut: QueueLUT | None = None) -> QueueLUT:
    """Store-backed :func:`build_queue_lut`: memory -> disk -> simulate.

    The resolution order is (1) the bounded in-process layer, (2) the
    ``$REPRO_LUT_CACHE`` on-disk store (bit-identical read, zero DES
    traces), (3) a fresh build -- which is then persisted.  The store key
    covers every build input plus the mechanism fingerprint (see
    :mod:`repro.core.lutstore`), so simulator changes rebuild
    automatically and a warm read can never serve a stale surface.

    ``base_lut`` only matters on a full miss: the build grows the base
    incrementally instead of starting from scratch (the refinement
    loop's round-over-round warm start).
    """
    from repro.core import memsim
    axes, harvest = _grid_axes(rho, kappa, outstanding, eta, harvest)
    if harvest is not None and base is None:
        base = memsim.ChannelConfig(
            rho=0.5, harvest_bw_gbps=float(harvest_bw_gbps))
    key = lutstore.store_key(_store_params(
        axes, harvest, harvest_bw_gbps, steps, seed, reps, engine, base))
    lut = lutstore.cache_get(key)
    if lut is None:
        lut = lutstore.load(key)
        if lut is None:
            lut = build_queue_lut(
                rho=axes["rho"], kappa=axes["kappa"],
                outstanding=axes["outstanding"], eta=axes["eta"],
                harvest=harvest, harvest_bw_gbps=harvest_bw_gbps,
                steps=steps, seed=seed, reps=reps, base=base,
                engine=engine, devices=devices, base_lut=base_lut)
            lutstore.save(key, lut, meta=dict(
                engine=str(engine), steps=int(steps), seed=int(seed),
                reps=int(reps),
                shape=list(np.shape(np.asarray(lut.wait_ns))),
                harvest=harvest is not None))
        lutstore.cache_put(key, lut)
    return lut


def default_queue_lut(steps: int = DEFAULT_STEPS, seed: int = 0,
                      reps: int = DEFAULT_REPS,
                      engine: str = DEFAULT_ENGINE,
                      harvest: bool = False) -> QueueLUT:
    """The shared default-grid surface, resolved through the LUT store.

    This is what ``cpu_model.solve(..., queue_model="memsim")`` uses when
    no explicit LUT is passed (``harvest=True`` when any solved design
    harvests -- the tables gain the :data:`DEFAULT_HARVEST_GRID` axis).
    Resolution goes memory -> ``$REPRO_LUT_CACHE`` -> DES build (see
    :func:`resolve_lut`); the historical unbounded ``lru_cache`` is gone
    -- the in-process layer is bounded (``lutstore.MEM_CACHE_MAX``) and
    :func:`clear_lut_cache` empties it.  The build runs on one device,
    and the tables are device-count-invariant, so the key need not
    include the count.
    """
    return resolve_lut(steps=steps, seed=seed, reps=reps, engine=engine,
                       harvest=DEFAULT_HARVEST_GRID if harvest else None)


# ---------------------------------------------------------------------------
# Adaptive grid refinement: the ROADMAP's LUT-resolution endgame.
# ---------------------------------------------------------------------------

#: The LLM serving anchor whose wave-model token p99 tracks refinement
#: (the same arch the designer CLI and the drift section anchor on).
REFINE_ARCH = "mistral-large-123b"

#: Probe anchor: the off-axis coordinates each midpoint is probed at --
#: a mid-load bursty operating point near the headline designs' fixed
#: points, where interpolation error actually moves the answers.
PROBE_ANCHOR = dict(rho=0.74, kappa=1.6, outstanding=24.0, eta=0.60,
                    harvest_duty=0.0)

#: Intervals whose probe error is below this floor are never bisected --
#: DES sampling noise, not interpolation error.
REFINE_ERR_FLOOR = 0.02


def headline_metrics(lut: QueueLUT) -> dict:
    """The two convergence metrics of :func:`refine_queue_lut`.

    ``geomean_speedup``: CoaXiaL-4x over the DDR baseline, geomean over
    the Table-4 suite, both solved on the MEMSIM backend through ``lut``
    (the fig7 headline).  ``token_p99_ms``: the capacity planner's
    wave-model token p99 for :data:`REFINE_ARCH` on CoaXiaL-4x, composed
    from the solved ``latency_p99_ns``/``ipc`` exactly as the designer's
    in-loop SLO does.  Both are pure LUT-backed fixed-point solves -- no
    DES runs, so a refinement round costs two solves plus the probe
    batch.
    """
    from repro.core import cpu_model
    from repro.core.designer import _wave_geometry
    from repro.serving.demand import (DEFAULT_BATCH, DEFAULT_CONTEXT,
                                      llm_workload)
    wls = tuple(cpu_model.WORKLOADS) + (llm_workload(REFINE_ARCH),)
    res = cpu_model.solve(cpu_model.COAXIAL_4X, queue_model="memsim",
                          lut=lut, workloads=wls)
    ref = cpu_model.solve(cpu_model.DDR_BASELINE, queue_model="memsim",
                          lut=lut, workloads=wls)
    n_suite = len(cpu_model.WORKLOADS)
    sp = (np.asarray(res.ipc, np.float64)[:n_suite]
          / np.asarray(ref.ipc, np.float64)[:n_suite])
    waves, model_coef = _wave_geometry(REFINE_ARCH, DEFAULT_BATCH,
                                       DEFAULT_CONTEXT)
    tok99_s = max(waves * float(res.latency_p99_ns[-1]) * 1e-9,
                  model_coef / float(res.ipc[-1]))
    return dict(geomean_speedup=float(np.exp(np.mean(np.log(sp)))),
                token_p99_ms=tok99_s * 1e3)


def _midpoint(axis: str, lo: float, hi: float) -> float:
    """Interval midpoint in the axis's interpolation space (geometric
    for the log-interpolated ``outstanding`` axis, arithmetic else)."""
    if axis == "outstanding":
        return float(np.sqrt(lo * hi))
    return 0.5 * (lo + hi)


def refine_queue_lut(*, rho=None, kappa=None, outstanding=None,
                     eta=None, harvest=None,
                     harvest_bw_gbps: float = HARVEST_REF_BW_GBPS,
                     steps: int = DEFAULT_STEPS, seed: int = 0,
                     reps: int = DEFAULT_REPS,
                     engine: str = DEFAULT_ENGINE, devices=None,
                     tol: float = 0.01, max_rounds: int = 4,
                     metrics=headline_metrics):
    """Adaptively refine the LUT grid until the headlines stop moving.

    Starting from the given grids (default: every-other-point
    coarsenings of the default grids, so the loop has real work), each
    round:

    1. resolves the current grid through the store
       (:func:`resolve_lut`), growing the previous round's surface
       INCREMENTALLY -- only new cells run the DES;
    2. evaluates the convergence metrics (default
       :func:`headline_metrics`: fig7 geomean speedup + wave-model token
       p99) and STOPS when both moved less than ``tol`` (relative)
       against the previous round;
    3. otherwise probes every interval midpoint per axis (off-axis
       coordinates pinned at :data:`PROBE_ANCHOR`) against ONE batched
       direct DES run, and bisects the worst-error interval of each axis
       whose error clears :data:`REFINE_ERR_FLOOR`.

    This operationalizes the ROADMAP's "push the grid finer until the
    interpolated fixed point is insensitive" as a testable criterion.
    Returns ``(lut, history)`` -- one history dict per round with the
    grids' shape, cell count, metric values, relative deltas, worst
    probe error, and wall-clock; ``history[-1]["converged"]`` says
    whether the loop stopped on the criterion (vs running out of
    rounds).  ``report --section lut`` renders the trajectory.
    """
    from repro.core import memsim  # runtime: import cycle
    grids = dict(
        rho=tuple(rho) if rho is not None else DEFAULT_RHO_GRID[::2],
        kappa=(tuple(kappa) if kappa is not None
               else DEFAULT_KAPPA_GRID[::2]),
        outstanding=(tuple(outstanding) if outstanding is not None
                     else DEFAULT_OUTSTANDING_GRID[::2]),
        eta=tuple(eta) if eta is not None else DEFAULT_ETA_GRID[::2])
    if harvest is not None:
        grids["harvest_duty"] = tuple(harvest)
    history: list[dict] = []
    lut, prev = None, None
    for rnd in range(int(max_rounds)):
        t0 = time.perf_counter()
        lut = resolve_lut(
            rho=grids["rho"], kappa=grids["kappa"],
            outstanding=grids["outstanding"], eta=grids["eta"],
            harvest=grids.get("harvest_duty"),
            harvest_bw_gbps=harvest_bw_gbps, steps=steps, seed=seed,
            reps=reps, engine=engine, devices=devices, base_lut=lut)
        m = metrics(lut)
        row = dict(round=rnd,
                   shape=tuple(len(g) for g in grids.values()),
                   cells=int(np.prod([len(g) for g in grids.values()])),
                   converged=False, worst_err=0.0,
                   seconds=round(time.perf_counter() - t0, 3), **m)
        if prev is not None:
            row["d_geomean"] = abs(m["geomean_speedup"]
                                   / prev["geomean_speedup"] - 1.0)
            row["d_token_p99"] = abs(m["token_p99_ms"]
                                     / prev["token_p99_ms"] - 1.0)
            if (row["d_geomean"] < tol and row["d_token_p99"] < tol):
                row["converged"] = True
                history.append(row)
                break
        prev = m

        # Probe every interval midpoint, one batched DES run (canonical
        # streams: the probes are reproducible cell-for-cell).
        probes, owners = [], []
        for axis, grid in grids.items():
            for j in range(len(grid) - 1):
                c = dict(PROBE_ANCHOR)
                if "harvest_duty" not in grids:
                    c.pop("harvest_duty")
                c[axis] = _midpoint(axis, grid[j], grid[j + 1])
                probes.append(c)
                owners.append((axis, j))
        names = tuple(grids)
        coords = np.asarray([[p[n] for n in names] for p in probes])
        extra = ({"harvest_bw_gbps": float(harvest_bw_gbps)}
                 if "harvest_duty" in grids else {})
        cha = memsim.stack_channels(
            [memsim.ChannelConfig(**p, **extra) for p in probes])
        stats = memsim.simulate_cells(
            cha, steps=int(steps), seed=int(seed), reps=int(reps),
            engine=engine, devices=devices,
            stream_ids=cell_stream_ids(names, coords),
            chunk=memsim.canonical_chunk(engine))
        des_wait = np.maximum(
            np.asarray(stats.mean_ns, np.float64) - hw.DRAM_SERVICE_NS,
            0.0)
        lut_wait = np.asarray([float(lut.wait(
            p["rho"], p["kappa"], p["outstanding"], p["eta"],
            p.get("harvest_duty", 0.0))) for p in probes])
        # Error relative to the TOTAL access latency (wait + service):
        # that is what the solver consumes, and it keeps low-rho cells'
        # few-ns waits from turning DES noise into huge relative errors.
        err = (np.abs(lut_wait - des_wait)
               / (des_wait + hw.DRAM_SERVICE_NS))
        row["worst_err"] = float(err.max()) if len(err) else 0.0
        history.append(row)

        # Bisect each axis's worst interval (if it clears the floor).
        grew = False
        for axis in names:
            cand = [(err[i], owners[i][1]) for i in range(len(owners))
                    if owners[i][0] == axis]
            if not cand:
                continue
            worst, j = max(cand)
            if worst <= REFINE_ERR_FLOOR:
                continue
            g = list(grids[axis])
            g.insert(j + 1, _midpoint(axis, g[j], g[j + 1]))
            grids[axis] = tuple(g)
            grew = True
        if not grew:
            # Nothing left to bisect: the next round's metrics cannot
            # move, so record the (exactly zero) deltas and stop.
            m2 = metrics(lut)
            history.append(dict(
                round=rnd + 1, shape=row["shape"], cells=row["cells"],
                converged=True, worst_err=row["worst_err"], seconds=0.0,
                d_geomean=0.0, d_token_p99=0.0, **m2))
            break
    return lut, history
