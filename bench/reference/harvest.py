"""Plain reference of the event-engine memory-channel DES with the
idle-I/O harvesting chain (arXiv:2511.12349).

Everything :mod:`bench.reference.des` states holds here, and its
functions are reused; like it, this imports nothing of ``repro``.  Per
lane it adds the lent/reclaimed chain:

1. alternating exponential sojourns starting in the RECLAIMED state,
   with leave rate ``1 / harvest_sojourn_ns`` and enter rate
   ``leave * duty / (1 - duty)`` (at duty 0 the first sojourn is
   infinite), :data:`WINDOW_DIV` candidates to a window and at least
   :data:`MIN_WINDOWS` windows, kept as cumulative window bounds;
2. a request is lent iff its arrival time falls in an odd window (a
   binary search per request over the lane's bounds), and a lent
   request's service is scaled to ``base_bw / (base_bw +
   harvest_bw_gbps)`` of itself, ``base_bw = CACHE_LINE_B / t_xfer_ns``;
   the Lindley recursion then runs on the scaled services.

Stream contract of the chain: its uniforms are drawn like the MMPP
sojourns' (des.py), from the run's phase key folded first with
:data:`HARVEST_SALT` and then with the lane's stream id, in [1e-12, 1).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import des

#: Salt folded into the phase key before the lane's stream id: the
#: chain's draws never share a stream with the arrivals or services.
HARVEST_SALT = 0x48415256
#: One window of the chain per this many candidates of the budget ...
WINDOW_DIV = des.SOJOURN_DIV
#: ... and never fewer windows than this.
MIN_WINDOWS = 64
#: Bytes a request moves: the base bandwidth is this over ``t_xfer_ns``.
CACHE_LINE_B = 64.0

#: Per-lane fields: des.py's, then the chain's.
FIELDS = des.FIELDS + ("harvest_duty", "harvest_bw_gbps",
                       "harvest_sojourn_ns")


def n_windows(steps: int, chunk: int) -> int:
    """Windows of the chain for a budget of ``steps`` ns in chunks of
    ``chunk`` candidates."""
    n_chunks = -(-des.event_budget(steps) // chunk)
    return max(MIN_WINDOWS, n_chunks * chunk // WINDOW_DIV)


def _windows(p, phase_keys, streams, n: int, dt):
    """Per-lane cumulative bounds of the lent/reclaimed windows
    ``(lanes, n)``: window ``i`` ends at bound ``i`` and is lent iff
    ``i`` is odd."""
    keys = jax.vmap(lambda k: jax.random.fold_in(k, HARVEST_SALT))(
        phase_keys)
    su = des._uniforms(keys, streams, (n,)).astype(dt)
    leave = 1.0 / p["harvest_sojourn_ns"]
    enter = leave * p["harvest_duty"] / (1.0 - p["harvest_duty"])
    lent = (jnp.arange(n) % 2 == 1)[:, None]
    soj = -jnp.log(su) * jnp.where(lent, 1.0 / leave, 1.0 / enter)
    return jnp.cumsum(soj, axis=0).T


def _chunk(p, tabs, bounds, carry, keys, streams, warmup, chunk: int, dt):
    """des.py's chunk with the chain between the draws and the scan:
    arrivals and services, lent services scaled, then the Lindley scan.
    Returns the new carry, the latencies and the record mask."""
    t = des._terms(p, dt)
    T, L, rate = tabs
    u_last, t_last, W = carry
    u = des._uniforms(keys, streams, (2, chunk)).astype(dt)
    lg = jnp.log(u)
    upos = u_last[None, :] + jnp.cumsum(-lg[0], axis=0)          # (C, n)
    seg = jax.vmap(lambda Lr, q: jnp.searchsorted(Lr, q, side="left"))(
        L, upos.T) - 1
    seg = jnp.clip(seg, 0, L.shape[1] - 1)
    take = lambda x: jnp.take_along_axis(x, seg, axis=1)
    arr = jnp.ceil(take(T) + (upos.T - take(L))
                   / jnp.maximum(take(rate), 1e-12)).T
    gaps = jnp.diff(jnp.concatenate([t_last[None, :], arr]), axis=0)
    real = gaps > 0.5
    lu = lg[1] - jnp.log(t["p_stall"])
    log_stall = jnp.where(
        u[1] > t["q_b"] * t["p_stall"],
        jnp.log(p["stall_ns"]) - lu / p["stall_alpha"],
        jnp.log(p["stall_break_ns"]) + (jnp.log(t["q_b"]) - lu)
        / p["stall_alpha2"])
    svc = jnp.where(u[1] < t["p_stall"],
                    jnp.minimum(jnp.exp(log_stall), p["stall_max_ns"]),
                    t["s_small"])
    svc = jnp.where(real, svc, jnp.zeros((), dt))
    # The chain: the window each arrival falls in, by binary search.
    win = jax.vmap(lambda b, q: jnp.searchsorted(b, q, side="left"))(
        bounds, arr.T).T
    base_bw = CACHE_LINE_B / p["t_xfer_ns"]
    scale = base_bw / (base_bw + p["harvest_bw_gbps"])
    svc = jnp.where(win % 2 == 1, svc * scale, svc)

    def step(w, xs):
        gap, s = xs
        w = jnp.maximum(w - gap, jnp.zeros((), dt))
        return w + jnp.where(w <= t["bound"], s, jnp.zeros((), dt)), w

    W, wait = jax.lax.scan(step, W, (gaps, svc))
    rec = real & (arr > warmup + 0.5) & (wait <= t["bound"])
    return (upos[-1], arr[-1], W), wait + t["lat0"], rec


_windows_jit = jax.jit(_windows, static_argnames=("n", "dt"))
_chunk_jit = jax.jit(_chunk, static_argnames=("chunk", "dt"))


def simulate(params: dict, streams, seeds, *, steps: int, chunk: int,
             dtype=jnp.float32) -> np.ndarray:
    """Jitter-convolved latency histograms ``(lanes, des.N_BINS)``, as
    :func:`des.simulate` with the chain: ``params`` maps every name of
    :data:`FIELDS` to a ``(lanes,)`` array."""
    dt = jnp.dtype(dtype)
    streams = jnp.asarray(np.asarray(streams, np.uint64).astype(np.uint32))
    seeds = np.asarray(seeds, np.int64)
    n = streams.shape[0]
    n_chunks = -(-des.event_budget(steps) // chunk)
    warmup = steps // 10
    phase, chunk_keys = {}, {}
    for s in np.unique(seeds):
        ph, root = jax.random.split(jax.random.PRNGKey(int(s)))
        phase[s], chunk_keys[s] = ph, jax.random.split(root, n_chunks)
    phase_k = jnp.stack([phase[s] for s in seeds])
    chunk_k = jnp.stack([chunk_keys[s] for s in seeds], axis=1)
    p = {f: jnp.asarray(np.asarray(params[f], np.float32)).astype(dt)
         for f in FIELDS}
    windows = n_windows(steps, chunk)
    tabs = des._tables_jit(p, phase_k, streams, n_sojourns=windows, dt=dt)
    bounds = _windows_jit(p, phase_k, streams, n=windows, dt=dt)
    carry = (jnp.zeros(n, dt), jnp.zeros(n, dt), jnp.zeros(n, dt))
    counts = np.zeros(n * des.N_BINS, np.int64)
    rows = np.arange(n, dtype=np.int64)[None, :] * des.N_BINS
    for k in range(n_chunks):
        carry, lat, rec = _chunk_jit(p, tabs, bounds, carry, chunk_k[k],
                                     streams, jnp.asarray(warmup, dt),
                                     chunk=chunk, dt=dt)
        lat = np.asarray(lat.astype(jnp.float32), np.float32)
        rec = np.asarray(rec)
        bins = np.clip((lat * np.float32(1.0 / des.BIN_NS)).astype(
            np.int32), 0, des.N_BINS - 1)
        counts += np.bincount((rows + bins)[rec],
                              minlength=n * des.N_BINS)
    hist = counts.reshape(n, des.N_BINS).astype(np.float64)
    return des._jitter(hist, np.asarray(params["service_jitter_ns"],
                                        np.float64))
