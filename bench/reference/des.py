"""Plain reference of the event-engine memory-channel DES.

A straightforward implementation of the semantics the benchmark's
configurations state, written without the program: it imports nothing of
``repro`` and takes nothing the program has made.  Per lane (one
simulated memory channel) it

1. simulates the two-state MMPP modulating chain once (alternating
   exponential sojourns, burst first) as a piecewise-linear cumulative
   intensity table;
2. draws, per chunk of candidate requests, unit-exponential increments of
   cumulative intensity and inverts them through that table (a binary
   search per request) to continuous arrival times, ceiled onto the 1-ns
   lattice (same-cell candidates merge), and one service draw each from
   the two-slope truncated-Pareto law;
3. runs the Lindley recursion ``W <- max(W - gap, 0); admit iff W <=
   outstanding * t_xfer; W <- W + S`` over the requests, recording the
   latency ``W + service + 2 + cxl`` of admitted requests past the warmup;
4. histograms the latencies in 4-ns bins, convolves the uniform DRAM
   jitter into the histogram and reads mean, stdev and quantiles.

Random numbers follow the stated stream contract: threefry keys from the
run seed (``split`` into a phase key and per-chunk keys), one stream per
lane keyed by the lane's stream id (``fold_in``), replicas mixed into the
id by the golden-ratio constant.  ``dtype`` sets the precision of the
arithmetic after the uniforms are drawn: ``float32`` is what the
configurations state, ``bfloat16`` is the lower-precision control.
"""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import numpy as np

BIN_NS = 4.0
N_BINS = 1024
EVENTS_PER_NS = 0.35667
EV_CHUNK_MIN, EV_CHUNK_MAX, EV_CHUNK_ELEMS = 1024, 16384, 5_000_000
SOJOURN_DIV = 48
REP_MIX = 0x9E3779B9
CELL_SALT = b"qlut-cell-v1:"

#: Per-lane channel fields, in the order the reference consumes them.
FIELDS = ("rho", "kappa", "outstanding", "eta", "t_xfer_ns", "service_ns",
          "cxl_lat_ns", "burst_duty", "burst_sojourn_ns", "stall_prob",
          "stall_ns", "stall_alpha", "stall_break_ns", "stall_alpha2",
          "stall_max_ns", "service_jitter_ns")


def event_budget(steps: int) -> int:
    """Candidate requests for ``steps`` ns of simulated time."""
    return max(EV_CHUNK_MIN, int(round(steps * EVENTS_PER_NS)))


def adaptive_chunk(lanes: int) -> int:
    """Chunk length of a batch of ``lanes`` without a pinned schedule."""
    c = EV_CHUNK_MIN
    while c < EV_CHUNK_MAX and c * 2 * lanes <= EV_CHUNK_ELEMS:
        c *= 2
    return c


def cell_stream_id(names, coords) -> int:
    """32-bit stream id of one grid cell, keyed by its coordinates."""
    body = ";".join(f"{n}={float(v).hex()}" for n, v in zip(names, coords))
    digest = hashlib.sha256(CELL_SALT + body.encode()).digest()
    return int.from_bytes(digest[:4], "little")


def rep_stream(stream_id: int, rep: int) -> int:
    return (int(stream_id) + int(rep) * REP_MIX) & 0xFFFFFFFF


def _terms(p: dict, dt):
    """Derived per-lane laws: MMPP rates, switching, blocking tail, and
    the small-service level that keeps the mean service at ``t_xfer``."""
    one = jnp.asarray(1.0, dt)
    rate_avg = p["rho"] / p["t_xfer_ns"]
    rate_hi = jnp.minimum(p["kappa"] * rate_avg, jnp.asarray(0.98, dt))
    rate_lo = jnp.maximum((rate_avg - p["burst_duty"] * rate_hi)
                          / (one - p["burst_duty"]), jnp.asarray(0.0, dt))
    p_leave = one / p["burst_sojourn_ns"]
    p_enter = p_leave * p["burst_duty"] / (one - p["burst_duty"])
    sn, xb = p["stall_ns"], p["stall_break_ns"]
    a1, a2, cap = p["stall_alpha"], p["stall_alpha2"], p["stall_max_ns"]

    def seg_mean(ratio, a):
        # integral of (x0/x)**a from x0 to x1, over x0; a -> 1 is -log.
        d = a - one
        near = jnp.abs(d) < 1e-4
        safe = jnp.where(near, one, d)
        return jnp.where(near, -jnp.log(ratio), (one - ratio ** safe) / safe)

    q_b = (sn / xb) ** a1
    stall_mean = sn + sn * seg_mean(sn / xb, a1) + q_b * xb * seg_mean(
        xb / cap, a2)
    p_stall = jnp.clip(p["stall_prob"] * p["eta"], 0.0, 0.999).astype(dt)
    s_small = jnp.maximum((p["t_xfer_ns"] - p_stall * stall_mean)
                          / (one - p_stall), jnp.asarray(0.05, dt))
    lam = lambda r: -jnp.log1p(-r)
    return dict(p_leave=p_leave, p_enter=p_enter, q_b=q_b, p_stall=p_stall,
                s_small=s_small, lam_hi=lam(rate_hi), lam_lo=lam(rate_lo),
                lam_avg=lam(jnp.minimum(rate_avg, jnp.asarray(0.98, dt))),
                bound=p["outstanding"] * p["t_xfer_ns"],
                lat0=p["service_ns"] + 2.0 + p["cxl_lat_ns"])


def _uniforms(keys, streams, shape):
    """Per-lane f32 uniforms in [1e-12, 1): ``shape + (lanes,)``."""
    lane_keys = jax.vmap(jax.random.fold_in)(keys, streams)
    u = jax.vmap(lambda k: jax.random.uniform(k, shape, minval=1e-12))(
        lane_keys)
    return jnp.moveaxis(u, 0, -1)


def _tables(p, phase_keys, streams, n_sojourns: int, dt):
    """Per-lane sojourn table: boundary times ``T``, cumulative
    intensity ``L`` (both ``(lanes, M+1)``) and segment rates."""
    t = _terms(p, dt)
    su = _uniforms(phase_keys, streams, (n_sojourns,)).astype(dt)
    burst = (jnp.arange(n_sojourns) % 2 == 0)[:, None]
    soj = -jnp.log(su) * jnp.where(burst, 1.0 / t["p_leave"],
                                   1.0 / t["p_enter"])
    rate = jnp.where(burst, t["lam_hi"], t["lam_lo"])
    zero = jnp.zeros((1, soj.shape[1]), dt)
    T = jnp.concatenate([zero, jnp.cumsum(soj, axis=0)])
    L = jnp.concatenate([zero, jnp.cumsum(rate * soj, axis=0)])
    rate = jnp.concatenate([rate, jnp.maximum(t["lam_avg"], 1e-9)[None]])
    return T.T, L.T, rate.T


def _chunk(p, tabs, carry, keys, streams, warmup, chunk: int, dt):
    """One chunk of requests: arrivals, services, then the Lindley scan.
    Returns the new carry, the latencies and the record mask."""
    t = _terms(p, dt)
    T, L, rate = tabs
    u_last, t_last, W = carry
    u = _uniforms(keys, streams, (2, chunk)).astype(dt)
    lg = jnp.log(u)
    upos = u_last[None, :] + jnp.cumsum(-lg[0], axis=0)          # (C, n)
    # Segment of each request: the last boundary strictly below it.
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

    def step(w, xs):
        gap, s = xs
        w = jnp.maximum(w - gap, jnp.zeros((), dt))
        return w + jnp.where(w <= t["bound"], s, jnp.zeros((), dt)), w

    W, wait = jax.lax.scan(step, W, (gaps, svc))
    rec = real & (arr > warmup + 0.5) & (wait <= t["bound"])
    return (upos[-1], arr[-1], W), wait + t["lat0"], rec


_tables_jit = jax.jit(_tables, static_argnames=("n_sojourns", "dt"))
_chunk_jit = jax.jit(_chunk, static_argnames=("chunk", "dt"))


def _jitter(hist: np.ndarray, width: np.ndarray) -> np.ndarray:
    """Convolve each lane's histogram with its uniform(-w, w) jitter:
    the weight of a shift by ``k`` bins is the overlap of
    ``[k*BIN - BIN/2, k*BIN + BIN/2)`` with the jitter's support; mass
    shifted past either end stays in the edge bin."""
    out = np.zeros(hist.shape, np.float64)
    nb = hist.shape[1]
    for lane in range(hist.shape[0]):
        w = float(width[lane])
        if w < 1e-9:
            out[lane] = hist[lane]
            continue
        taps = int(np.ceil(w / BIN_NS)) + 1
        for k in range(-taps, taps + 1):
            lo = max(k * BIN_NS - BIN_NS / 2, -w)
            hi = min(k * BIN_NS + BIN_NS / 2, w)
            weight = max(hi - lo, 0.0) / (2.0 * w)
            if weight > 0:
                dest = np.clip(np.arange(nb) + k, 0, nb - 1)
                np.add.at(out[lane], dest, weight * hist[lane])
    return out


def stats(hist: np.ndarray) -> dict:
    """Mean, stdev, p90 and p99 (bin centres) of per-lane histograms."""
    centres = (np.arange(hist.shape[-1]) + 0.5) * BIN_NS
    p = hist / np.maximum(hist.sum(-1, keepdims=True), 1.0)
    mean = (p * centres).sum(-1)
    var = (p * (centres - mean[..., None]) ** 2).sum(-1)
    cum = np.cumsum(p, -1)
    q = lambda x: (np.argmax(cum >= x, axis=-1) + 0.5) * BIN_NS
    return dict(mean_ns=mean, stdev_ns=np.sqrt(var), p90_ns=q(0.9),
                p99_ns=q(0.99))


def simulate(params: dict, streams, seeds, *, steps: int, chunk: int,
             dtype=jnp.float32) -> np.ndarray:
    """Jitter-convolved latency histograms ``(lanes, N_BINS)``.

    ``params`` maps every name of :data:`FIELDS` to a ``(lanes,)`` array;
    ``streams`` are the lanes' 32-bit stream ids and ``seeds`` their run
    seeds (lanes of one run share a seed).  ``steps`` is the simulated
    budget in ns, of which the first tenth is not recorded (warmup);
    ``chunk`` is the requests per chunk.
    """
    dt = jnp.dtype(dtype)
    streams = jnp.asarray(np.asarray(streams, np.uint64).astype(np.uint32))
    seeds = np.asarray(seeds, np.int64)
    n = streams.shape[0]
    events = event_budget(steps)
    n_chunks = -(-events // chunk)
    n_sojourns = max(64, n_chunks * chunk // SOJOURN_DIV)
    warmup = steps // 10
    phase, chunk_keys = {}, {}
    for s in np.unique(seeds):
        ph, root = jax.random.split(jax.random.PRNGKey(int(s)))
        phase[s], chunk_keys[s] = ph, jax.random.split(root, n_chunks)
    phase_k = jnp.stack([phase[s] for s in seeds])
    chunk_k = jnp.stack([chunk_keys[s] for s in seeds], axis=1)
    p = {f: jnp.asarray(np.asarray(params[f], np.float32)).astype(dt)
         for f in FIELDS}
    tabs = _tables_jit(p, phase_k, streams, n_sojourns=n_sojourns, dt=dt)
    carry = (jnp.zeros(n, dt), jnp.zeros(n, dt), jnp.zeros(n, dt))
    counts = np.zeros(n * N_BINS, np.int64)
    rows = np.arange(n, dtype=np.int64)[None, :] * N_BINS
    for k in range(n_chunks):
        carry, lat, rec = _chunk_jit(p, tabs, carry, chunk_k[k], streams,
                                     jnp.asarray(warmup, dt), chunk=chunk,
                                     dt=dt)
        lat = np.asarray(lat.astype(jnp.float32), np.float32)
        rec = np.asarray(rec)
        bins = np.clip((lat * np.float32(1.0 / BIN_NS)).astype(np.int32),
                       0, N_BINS - 1)
        counts += np.bincount((rows + bins)[rec], minlength=n * N_BINS)
    hist = counts.reshape(n, N_BINS).astype(np.float64)
    return _jitter(hist, np.asarray(params["service_jitter_ns"],
                                    np.float64))
