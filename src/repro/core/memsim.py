"""Mechanistic discrete-event simulator of a channelized memory system.

This is the DRAMsim-ish half of the reproduction: where ``queueing.py`` is a
*calibrated closed form*, memsim is an *independent mechanism* -- a
simulation of request arrivals, FIFO bus queues, DRAM service and CXL
interface delays, implemented as jitted ``jax.lax.scan`` loops over an
arbitrary batch of channel configurations.  It produces full latency
*distributions* (mean / p50 / p90 / p99 / stdev / CDF), which back Fig 2a's
load-latency curve and Fig 6b's CDF comparison.

TWO ENGINES share one mechanism (same arrival, service and admission laws):

  * ``engine="timestep"`` (the reference): a 1-ns time-stepped scan.  Per
    nanosecond it advances the two-state MMPP, flips a Bernoulli arrival
    coin, and drains the backlog by 1 ns.  Each emission chunk's five
    uniforms per step come from ONE threefry stream per lane, keyed by
    the logical lane index (``fold_in(chunk_key, lane)``) and generated
    up front outside the scan -- the lane-keyed stream contract (below)
    that makes every lane's draws independent of batch width, padding
    and device count.
  * ``engine="event"`` (the fast engine): one scan iteration per
    **request** -- the Lindley recursion ``W_{k+1} = max(W_k + S_k - A_k,
    0)`` over per-request inter-arrival gaps and service draws, roughly
    ``t_xfer / rho`` fewer iterations than the per-nanosecond loop and no
    idle steps at low utilization.  Inter-arrival gaps are sampled from
    the SAME two-state MMPP, with in-gap phase switching handled exactly:
    the modulating chain is simulated once per call (alternating
    exponential sojourns), and arrival times come from inverting the
    piecewise-linear cumulative intensity at unit-exponential increments
    (the Cox-process construction -- the vectorized equivalent of
    phase-type gap sampling).  Service uses the same two-slope
    truncated-Pareto law, the closed-loop ``outstanding`` bound gates the
    same backlog quantity, and per-request latencies are emitted as scan
    outputs and histogrammed once post-scan.  The uniform DRAM jitter is
    additive observation noise (it never feeds the queue), so the event
    engine convolves its exact distribution into the histogram instead of
    sampling it -- one fewer uniform per request and strictly lower
    variance.  The engines agree statistically, not bitwise;
    ``coaxial.crosscheck_engines`` gates mean/p90 agreement at the
    closed-form rho anchors.

Model per channel (both engines):
  * arrivals: two-state MMPP (burst/idle); the burst-state rate is
    ``kappa`` times the average, idle fills the rest;
  * closed loop: a finite in-flight population ``outstanding`` (MSHR/ROB
    bound per channel) gates ADMISSION -- while the backlog exceeds
    ``outstanding * t_xfer_ns`` of queued work the cores' miss buffers
    are full, so no new request enters the queue (the core stalls
    instead).  The default is unbounded (``inf``), the open loop;
    ``core/queuelut.py`` sweeps this axis to build the closed-loop wait
    surface ``cpu_model`` consumes;
  * service: the channel serializes one 64B line per ``t_xfer`` ns *on
    average* (38.4 GB/s -> 1.67 ns), but with small probability the
    controller blocks for a two-slope power-law (truncated-Pareto)
    duration spanning the bank-conflict scale through tFAW windows up to
    refresh.  The blocking-size law is what the paper's own Fig-2a closed
    forms demand: inverting mean and p90 through Pollaczek-Khinchine
    yields a service-excess tail P(S > w) ~ w**-1.8.  Calibration keeps
    E[S] = t_xfer (so rho keeps its meaning as bus utilization) and
    matches the M/G/1 mean-wait anchor W(0.5) ~= 80 ns
    (``coaxial.validate_calibration`` checks mean AND p90 per anchor,
    for either engine);
  * DRAM access: base latency plus uniform bank/row-state jitter;
  * CXL: a fixed interface premium plus the link-traversal time;
  * harvesting (arXiv 2511.12349): for a fraction ``harvest_duty`` of
    the time the channel borrows an idle CXL I/O link and transfers at
    ``base + harvest_bw_gbps``.  Lent/reclaimed windows alternate
    through a second two-state modulating chain sharing the MMPP's 1-ns
    lattice (mean window ``harvest_sojourn_ns``); a request admitted
    during a lent window enqueues its work scaled by ``base_bw /
    (base_bw + harvest_bw)``.  The chain's randomness comes from a
    SEPARATE salted stream per lane, so ``harvest_duty = 0`` (the
    default) is bit-identical to the unharvested simulator on both
    engines -- the arrival/service streams never shift.

Every calibration constant is also a per-channel *field* of
:class:`ChannelConfig` / :class:`ChannelArrays` (the module-level constants
are just the defaults), so any of them can be a named sweep axis:
``sweepspec.distribution_spec(rho=..., kappa=..., stall_ns=...)`` lowers to
ONE jitted simulation over the flattened cell batch, with NaN-masked
overrides applied branch-free in-trace exactly like ``cpu_model``'s design
overrides.

The first ``warmup`` ns of simulated time (default ``steps // 10``) are
excluded from the histogram: the simulation starts with an empty queue, so
without a warmup window the cold-start transient biases means and low-rho
quantiles down.

Budgets are engine-neutral: ``steps`` is the simulated-time budget in ns.
The event engine converts it to a request budget with
:func:`events_for_steps` (``EVENTS_PER_NS`` requests per ns -- the arrival
rate of the rho = 0.5 reference channel, the repo's calibration anchor),
so one ``steps`` argument throttles both engines coherently.

All randomness is threefry-derived from an explicit seed, with one stream
per LANE keyed by the logical lane index: ``fold_in(chunk_key, lane)``
where the chunk keys are split from the seed.  Runs are exactly
reproducible per engine (the two engines draw different streams), and --
because no draw ever depends on the batch width or the device layout --
a lane simulates identically whether it runs alone, inside a wider batch
(at equal chunk schedule), on one device or on many.

DEVICE PARALLELISM: lanes are independent chains, so both engines
optionally shard the lane axis across host devices via
:mod:`repro.core.shardsim` (``devices=`` on every entry point;
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` splits one CPU
into N devices).  The batch is NaN-padded to a multiple of the device
count and the SAME chunk kernels run per shard; the lane-keyed streams plus
global-lane histogram indices make the sharded result bit-identical to
the unsharded one -- ``devices`` changes wall-clock, never a single
count.

HOST SPANS: every call records ``jax.profiler.TraceAnnotation`` spans on
the profiler's host plane, on the clock of the device's module line --
``memsim.simulate`` around the whole call and, inside it,
``memsim.prepare`` (host set-up before the first chunk),
``memsim.dispatch`` (enqueueing one chunk's stages), ``memsim.fetch``
(the host blocked on a chunk's bin indices, plus the copy),
``memsim.bincount`` (folding them), ``memsim.jitter`` and
``memsim.stats``, which carries the call's ``candidates`` (request slots
of real lanes) and ``recorded`` (slots that reached the histograms).
Each names its call by a running ``call`` id; an event-engine
``memsim.simulate`` also names ``seg_lookup``, the lookup that both stage
A's segments and the harvest pass's windows take (``"bsearch"`` on the
CPU, ``"count"`` elsewhere), whether the harvest pass runs
(``harvest``, 1 or 0) and its window-table length (``windows``, 0 when
it does not), and the event engine's ``memsim.stats`` carries ``lent``,
the candidate slots whose arrival fell in a lent window (counted on the
device by the harvest pass, 0 when it does not run).  Inside the jitted
stages,
``jax.named_scope`` names the ops ``memsim.stage_a``, ``memsim.harvest``
and ``memsim.stage_b``.  With the profiler off a span costs about a
microsecond.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hw, shardsim

#: Host spans (see the module docstring).
_span = jax.profiler.TraceAnnotation

#: The running :func:`simulate_cells` call: ``id`` numbers the calls (the
#: ``call`` argument of their spans), ``unrecorded`` is the overflow
#: slot of the call's :func:`_accumulate_chunks` histogram -- every
#: unrecorded, padding and warm-up emission -- so the ``recorded`` count
#: costs no pass over the histograms, and ``lent`` is the event engine's
#: count of candidate slots that arrived in a lent harvest window.
_CALL = {"id": 0, "unrecorded": 0, "lent": 0}

#: Histogram binning for latency distributions.
BIN_NS = 4.0
N_BINS = 1024         # covers 0 .. 4096 ns

#: DRAM access latency jitter (bank/row-buffer state), uniform half-width.
SERVICE_JITTER_NS = 13.5
#: Fraction of time the MMPP spends in the burst state.
BURST_DUTY = 0.3
#: Mean sojourn time in each MMPP state (ns).
BURST_SOJOURN_NS = 2000.0
#: Controller blocking episodes (the heavy service tail): with probability
#: ``STALL_PROB`` per request the controller blocks for a two-slope
#: power-law (Pareto) duration -- slope ``STALL_ALPHA`` from the
#: bank-conflict scale (``STALL_NS``) to the tFAW / turnaround-train
#: scale (``STALL_BREAK_NS``), rolling off at slope ``STALL_ALPHA2`` out
#: to the refresh/tRFC scale, capped at ``STALL_MAX_NS``.  The power law
#: is not a modeling whim: inverting the paper's two Fig-2a closed forms
#: (mean 40 + 80x, p90 40 + 148*x**1.232, x = rho/(1-rho)) through the
#: M/G/1 Pollaczek-Khinchine relation forces the service-excess tail to
#: follow P(S > w) ~ w**-1.8 across the whole 30..800 ns range -- a
#: straight line in log-log that no small discrete mixture can track --
#: and the exact stationary solve of the simulator's own Lindley chain
#: fixes the two slopes so that multi-event compounding lands the DES on
#: BOTH closed forms at every load anchor (mean within ~9%, p90 within
#: ~12%, for rho in [0.1, 0.8]).  E[S] stays exactly t_xfer (so rho
#: keeps its meaning as bus utilization) and E[S^2] ~= 267 ns^2 keeps
#: the mean-wait anchor W(0.5) ~= 80 ns;
#: ``coaxial.validate_calibration`` pins mean AND p90 per anchor.
STALL_PROB = 0.01923
STALL_NS = 37.0
STALL_ALPHA = 2.138
STALL_BREAK_NS = 353.6
STALL_ALPHA2 = 1.3495
STALL_MAX_NS = 1903.7
#: Floor on the non-penalized per-request service time (ns).
MIN_SERVICE_NS = 0.05

#: Idle-I/O bandwidth harvesting (arXiv 2511.12349): mean sojourn of
#: each lent / reclaimed window of the harvest modulating chain (ns).
#: I/O idleness varies on the same microsecond scale as the MMPP burst
#: envelope, so the default matches ``BURST_SOJOURN_NS``.
HARVEST_SOJOURN_NS = 2000.0
#: Threefry salt deriving the harvest chain's streams from each chunk /
#: phase key (``fold_in(key, salt)``); far above any lane index, so the
#: harvest draws can never collide with -- or shift -- the arrival and
#: service streams (the ``harvest_duty = 0`` bit-identity contract).
_HARVEST_SALT = 0x48415256

#: Default warmup fraction: the leading ``steps // WARMUP_DIV`` ns of
#: simulated time are simulated but not recorded (both engines).
WARMUP_DIV = 10

#: The two simulation engines (see module docstring).
ENGINES = ("timestep", "event")

#: Event-engine candidate budget per simulated ns: the candidate-arrival
#: intensity of the rho = 0.5 reference channel (the repo's W(0.5)
#: calibration anchor).  The event engine samples arrivals on the SAME
#: 1-ns lattice as the timestep engine -- a Bernoulli(p) lattice equals a
#: Poisson stream of intensity ``-ln(1-p)`` with same-cell arrivals
#: merged -- so the candidate intensity at the p = 0.3 anchor is
#: ``-ln(0.7)``.  ``steps`` ns of timestep budget and ``steps *
#: EVENTS_PER_NS`` candidates of event budget record the same number of
#: samples over the same simulated horizon at that anchor.
EVENTS_PER_NS = 0.35667

#: Steps per emission chunk of the timestep engine: the scan emits
#: ``(latency, mask)`` per step (no in-loop histogram scatter); chunking
#: bounds both the emission buffer and the chunk's precomputed per-lane
#: uniform block (``chunk x 5 x lanes`` f32) -- adaptive like the event
#: engine's, and derived from the UNPADDED batch width so the chunk
#: schedule (part of the stream contract) never depends on device count.
_TS_CHUNK_ELEMS = 24_000_000
_TS_CHUNK_MIN, _TS_CHUNK_MAX = 1024, 8192
#: Requests per chunk of the event engine: adaptive so the chunk's
#: working set (~a dozen ``chunk x cells`` f32 arrays) stays cache-sized
#: at any batch width -- wide LUT-build batches take smaller chunks,
#: narrow test batches take larger ones.
_EV_CHUNK_ELEMS = 5_000_000
_EV_CHUNK_MIN, _EV_CHUNK_MAX = 1024, 16384


def _ts_chunk_len(n: int) -> int:
    c = _TS_CHUNK_MIN
    while c < _TS_CHUNK_MAX and c * 2 * 5 * n <= _TS_CHUNK_ELEMS:
        c *= 2
    return c


def _event_chunk_len(n: int) -> int:
    c = _EV_CHUNK_MIN
    while c < _EV_CHUNK_MAX and c * 2 * n <= _EV_CHUNK_ELEMS:
        c *= 2
    return c


def canonical_chunk(engine: str) -> int:
    """The width-independent chunk length of the canonical stream contract.

    The adaptive chunk schedule (:func:`_ts_chunk_len` /
    :func:`_event_chunk_len`) keys the draw sequences on the batch width,
    so two batches of different widths never share streams even when
    their lanes share ``stream_ids``.  Callers that need a lane's result
    to be REPRODUCIBLE AT ANY BATCH WIDTH (the QueueLUT store's
    incremental builds: a cell simulated alone must equal the same cell
    inside the full-grid batch, bit for bit) pin
    ``chunk=canonical_chunk(engine)`` -- each engine's minimum, which is
    also what the adaptive heuristic picks at full LUT-grid widths, so
    pinning costs nothing where it matters and only adds dispatches on
    small probe batches.
    """
    _check_engine(engine)
    return _TS_CHUNK_MIN if engine == "timestep" else _EV_CHUNK_MIN


#: Odd (golden-ratio) constant mixing the replica index into a cell's
#: 32-bit stream id: ``lane_stream = (stream_ids[cell] + rep * MIX) mod
#: 2**32`` -- a bijection of the id space per replica, so replicas of one
#: cell draw independent streams and the mapping needs no second key.
_STREAM_REP_MIX = 0x9E3779B9


def _lane_streams(n: int, reps: int, stream_ids):
    """Per-lane stream indices for the flattened ``(reps x n)`` batch.

    ``stream_ids=None`` keeps the positional contract (global lane index
    ``rep * n + cell``); an explicit ``(n,)`` uint32 array keys each
    lane's threefry streams by the CALLER'S id instead -- the content
    half of the canonical stream contract (see :func:`canonical_chunk`
    for the schedule half).
    """
    if stream_ids is None:
        return jnp.arange(n * reps, dtype=jnp.int32)
    sid = np.asarray(stream_ids)
    if sid.shape != (n,):
        raise ValueError(f"stream_ids must have shape ({n},) -- one id "
                         f"per cell; got {sid.shape}")
    sid = sid.astype(np.uint64)
    rep = np.repeat(np.arange(reps, dtype=np.uint64), n)
    mixed = (np.tile(sid, reps) + rep * _STREAM_REP_MIX) & 0xFFFFFFFF
    return jnp.asarray(mixed.astype(np.uint32))
#: Event engine: one MMPP sojourn is simulated per this many candidates
#: (the modulating chain is ~100x slower than arrivals, so the chain
#: stays a rounding error of the candidate budget, and sizing it from
#: the budget alone keeps the kernel's trace independent of the axis
#: VALUES -- the one-trace-per-grid invariant).  Past the sampled chain
#: -- only reachable below rho ~0.05 at default budgets -- the appended
#: tail segment carries the average rate.
_SOJOURN_DIV = 48


@dataclasses.dataclass(frozen=True)
class ChannelConfig:
    """One simulated memory channel configuration.

    Every field -- the operating point AND the calibration constants -- is
    sweepable; the module-level constants are only defaults.
    """

    rho: float                  # target bus utilization, 0..~0.95
    kappa: float = 1.0          # burst peak-to-mean arrival ratio
    #: In-flight request population per channel (MSHR/ROB bound); arrivals
    #: are blocked while the backlog holds more than
    #: ``outstanding * t_xfer_ns`` of queued work.  ``inf`` = open loop.
    outstanding: float = float("inf")
    #: Queue-exposure factor (``cpu_model``'s per-workload MLP/overlap
    #: knob): scales the per-request probability of a controller blocking
    #: episode, ``p_eff = eta * stall_prob``, while the small-service
    #: level re-absorbs the difference so E[S] stays exactly ``t_xfer``
    #: (rho keeps its meaning).  Since the M/G/1 wait is dominated by the
    #: blocking tail's E[S^2], the mean wait scales ~linearly in eta --
    #: the mechanistic counterpart of the old ``eta * W`` multiplier,
    #: but with the variance and quantiles simulated, not scaled.
    #: ``1.0`` (the default) is bit-identical to the pre-eta simulator.
    eta: float = 1.0
    t_xfer_ns: float = hw.CACHE_LINE_B / hw.DDR5_CH_BW_GBPS
    service_ns: float = hw.DRAM_SERVICE_NS - 2.0   # pipelined access part
    cxl_lat_ns: float = 0.0     # CXL interface premium (0 => direct DDR)
    burst_duty: float = BURST_DUTY
    burst_sojourn_ns: float = BURST_SOJOURN_NS
    stall_prob: float = STALL_PROB
    stall_ns: float = STALL_NS
    stall_alpha: float = STALL_ALPHA
    stall_break_ns: float = STALL_BREAK_NS
    stall_alpha2: float = STALL_ALPHA2
    stall_max_ns: float = STALL_MAX_NS
    service_jitter_ns: float = SERVICE_JITTER_NS
    #: Idle-I/O harvesting: fraction of time (in [0, 1)) an idle I/O
    #: link is lent to this channel, and the extra bandwidth it brings.
    #: While lent, a request's enqueued work shrinks by
    #: ``base_bw / (base_bw + harvest_bw_gbps)``.  ``harvest_duty = 0``
    #: (the default) is bit-identical to the unharvested simulator.
    harvest_duty: float = 0.0
    harvest_bw_gbps: float = 0.0
    harvest_sojourn_ns: float = HARVEST_SOJOURN_NS


class ChannelArrays(NamedTuple):
    """Pytree of per-channel simulation parameters, ``(N,)`` float leaves.

    Mirrors :class:`cpu_model.MemSystemArrays`: :class:`ChannelConfig` is
    the frozen-dataclass façade for humans, this is what the jitted
    simulation consumes -- one leading cell axis shared by every leaf, so
    any named-axis grid flattens to one batch.
    """

    rho: jnp.ndarray
    kappa: jnp.ndarray
    outstanding: jnp.ndarray
    eta: jnp.ndarray
    t_xfer_ns: jnp.ndarray
    service_ns: jnp.ndarray
    cxl_lat_ns: jnp.ndarray
    burst_duty: jnp.ndarray
    burst_sojourn_ns: jnp.ndarray
    stall_prob: jnp.ndarray
    stall_ns: jnp.ndarray
    stall_alpha: jnp.ndarray
    stall_break_ns: jnp.ndarray
    stall_alpha2: jnp.ndarray
    stall_max_ns: jnp.ndarray
    service_jitter_ns: jnp.ndarray
    harvest_duty: jnp.ndarray
    harvest_bw_gbps: jnp.ndarray
    harvest_sojourn_ns: jnp.ndarray


#: Channel fields a distribution-sweep axis may bind (all of them).
CHANNEL_FIELDS = ChannelArrays._fields


def stack_channels(configs) -> ChannelArrays:
    """Stack :class:`ChannelConfig` façades into one ``(N,)``-leaved pytree."""
    return ChannelArrays(*(
        jnp.asarray([float(getattr(c, f)) for c in configs], jnp.float32)
        for f in CHANNEL_FIELDS))


def _apply_channel_overrides(cha: ChannelArrays, ov) -> ChannelArrays:
    """NaN-masked per-field substitution, applied branch-free in-trace."""
    return cha._replace(**{
        f: jnp.where(jnp.isnan(v), getattr(cha, f), v)
        for f, v in ov.items()})


#: Number of times each engine's jitted chunk kernel has been TRACED (not
#: called).  A trace only happens on a new (flattened cell count, device
#: count) pair -- chunk lengths derive from the unpadded batch width and
#: the event engine's sojourn count from the request budget, never from
#: axis values -- so a whole named-axis distribution grid bumps its
#: engine's counter by exactly one, sharded or not; tests pin that.
_TRACE_COUNT = {"timestep": 0, "event": 0}


def sim_trace_count(engine: str | None = None) -> int:
    """Trace count for one engine, or the sum over both when ``engine``
    is omitted."""
    if engine is None:
        return sum(_TRACE_COUNT.values())
    _check_engine(engine)
    return _TRACE_COUNT[engine]


def _check_engine(engine: str) -> str:
    if engine not in ENGINES:
        raise ValueError(f"unknown engine {engine!r}; choose from {ENGINES}")
    return engine


def _pareto_seg(ratio, a):
    """Per-unit-survival mean of one power-law segment.

    ``integral of (x0/x)**a from x0 to x1, divided by x0`` with
    ``ratio = x0/x1``: ``(1 - ratio**(a-1)) / (a-1)``, whose ``a -> 1``
    limit is ``-log(ratio)``.  Branch-free so a ``stall_alpha`` axis may
    sweep through 1.0 without the 0/0 turning the cell into silent NaN
    garbage.
    """
    d = a - 1.0
    near_one = jnp.abs(d) < 1e-4
    safe = jnp.where(near_one, 1.0, d)
    return jnp.where(near_one, -jnp.log(ratio), (1.0 - ratio ** safe) / safe)


def _channel_terms(c: ChannelArrays) -> dict:
    """Derived per-channel quantities shared by both engines.

    The SAME laws feed both engines: MMPP rates and switching
    probabilities, the two-slope blocking tail, and the small-service
    level that keeps E[S] exactly ``t_xfer``.
    """
    rate_avg = c.rho / c.t_xfer_ns               # arrivals per ns
    rate_hi = jnp.minimum(c.kappa * rate_avg, 0.98)
    # Rate in the idle state so the duty-weighted mean matches rate_avg.
    rate_lo = jnp.maximum(
        (rate_avg - c.burst_duty * rate_hi) / (1.0 - c.burst_duty), 0.0)
    p_leave = 1.0 / c.burst_sojourn_ns           # state-switch prob per ns
    # Duty-correct entry prob: stationary P(burst) = burst_duty.
    p_enter = p_leave * c.burst_duty / (1.0 - c.burst_duty)
    # Two-slope truncated-Pareto blocking durations.  Survival:
    # (sn/x)**a1 up to the break, then q_b * (xb/x)**a2, capped at the
    # max.  The capped mean (closed form, computed in-trace) lets s_small
    # absorb the blocking work so E[S] stays exactly t_xfer.
    sn, xb = c.stall_ns, c.stall_break_ns
    a1, a2, cap = c.stall_alpha, c.stall_alpha2, c.stall_max_ns
    q_b = (sn / xb) ** a1                    # survival at the break
    stall_mean = (sn + sn * _pareto_seg(sn / xb, a1) +
                  q_b * xb * _pareto_seg(xb / cap, a2))
    # Effective blocking probability: ``eta`` scales how often a request
    # triggers a blocking episode (eta = 1 reproduces stall_prob exactly,
    # bit for bit -- x * 1.0 is exact in f32).  s_small re-absorbs the
    # blocking work either way, so E[S] stays t_xfer at every eta.
    p_stall = jnp.clip(c.stall_prob * c.eta, 0.0, 0.999)
    s_small = ((c.t_xfer_ns - p_stall * stall_mean) /
               (1.0 - p_stall))
    s_small = jnp.maximum(s_small, MIN_SERVICE_NS)
    # Lattice candidate intensities for the event engine: a Bernoulli(p)
    # per-ns arrival process equals a Poisson stream of intensity
    # -ln(1-p) whose same-cell arrivals are merged, so both engines draw
    # from the SAME per-ns gap law.
    lam_hi = -jnp.log1p(-rate_hi)
    lam_lo = -jnp.log1p(-rate_lo)
    lam_avg = -jnp.log1p(-jnp.minimum(rate_avg, 0.98))
    return dict(rate_avg=rate_avg, rate_hi=rate_hi, rate_lo=rate_lo,
                p_leave=p_leave, p_enter=p_enter, q_b=q_b,
                p_stall=p_stall, s_small=s_small, lam_hi=lam_hi,
                lam_lo=lam_lo, lam_avg=lam_avg)


def _harvest_terms(c: ChannelArrays) -> dict:
    """Derived harvest-chain quantities shared by both engines.

    Deliberately NOT folded into :func:`_channel_terms`: the harvest
    terms are consumed only by the (separately jitted) harvest entry
    points, so the pre-harvest stage A executables stay byte-identical
    and the ``harvest_duty = 0`` histograms cannot shift.

    The lent/reclaimed chain mirrors the MMPP burst chain: per-ns leave
    probability ``1 / harvest_sojourn_ns`` and a duty-correct entry
    probability so the stationary lent fraction is ``harvest_duty``
    (exactly 0.0 at duty = 0 -- the chain never leaves the reclaimed
    state).  ``h_scale`` is the work shrink while lent,
    ``base_bw / (base_bw + harvest_bw)`` with the channel's own base
    bandwidth ``CACHE_LINE_B / t_xfer_ns`` -- exactly 1.0 at
    ``harvest_bw_gbps = 0``.
    """
    h_leave = 1.0 / c.harvest_sojourn_ns
    h_enter = h_leave * c.harvest_duty / (1.0 - c.harvest_duty)
    h_scale = 1.0 / (1.0 + c.harvest_bw_gbps * c.t_xfer_ns /
                     hw.CACHE_LINE_B)
    return dict(h_leave=h_leave, h_enter=h_enter, h_scale=h_scale)


def _harvest_scan_terms(cha: ChannelArrays, ov):
    return _harvest_terms(_apply_channel_overrides(cha, ov))


_harvest_scan_terms_jit = jax.jit(_harvest_scan_terms)


def _harvest_active(cha: ChannelArrays, ov) -> bool:
    """Host-side fast path: True iff any lane has an effective
    ``harvest_duty > 0`` AND ``harvest_bw_gbps > 0``.

    Inactive batches skip the harvest draws / window tables entirely --
    the chain is a provable no-op there (``h_enter = 0`` or ``h_scale =
    1``), so the skip is value-identical and the unharvested path keeps
    its pre-harvest wall-clock.  A value peek in the driver, not a
    trace-cache key: the same stage B kernel runs either way, so the
    one-trace-per-grid invariant is untouched.
    """
    def eff(field):
        # float32, as the batch is tiled: the peek gives the same answer
        # on the caller's arrays as on the tiled ones (a field missing
        # from ``ov`` keeps the channel's own value).
        own = np.asarray(getattr(cha, field), np.float32)
        o = np.asarray(ov.get(field, np.nan), np.float32)
        return np.where(np.isnan(o), own, o)
    return bool(np.any((eff("harvest_duty") > 0.0)
                       & (eff("harvest_bw_gbps") > 0.0)))


# ---------------------------------------------------------------------------
# Two-stage kernels: width-pinned randomness, shardable recursion.
#
# Bit-identity across device counts cannot survive recompiling
# transcendental math at different widths: XLA fuses ``log``/``exp``/
# ``pow`` into whatever surrounds them, and two fusions may round a
# result 1 ulp apart -- enough to flip a ``ceil`` or a bin boundary.
# So each engine is split in two:
#
#   * STAGE A (draws + transcendentals + MMPP/service law): ALWAYS
#     compiled at the UNPADDED batch width, whatever ``devices`` is.
#     Same executable + same inputs = bitwise-identical outputs -- the
#     only cross-run invariant XLA actually guarantees.
#   * STAGE B (the sequential recursion: Lindley / backlog scan, plus
#     binning): compiled per (device count, padded width) and run under
#     ``shard_map``.  Its ops are restricted to correctly-rounded
#     elementwise arithmetic (add/sub/mul/div/min/max/where/compare) and
#     integer work, each deterministic at ANY width; the one ``a*b + c``
#     pattern multiplies by an exact 0/1 indicator, so FMA contraction
#     cannot change it.  That restriction -- no transcendentals, no
#     reductions -- is what makes the per-shard recompile exact, and it
#     is also why the split helps wall-clock: the embarrassingly
#     parallel stage A runs once, and only the sequential scan (the part
#     that cannot vectorize across time) is sharded across devices.
# ---------------------------------------------------------------------------

def _lane_uniforms(key, lane_idx, shape, **kw):
    """Per-lane uniforms from lane-keyed threefry streams.

    One stream per lane, keyed by the GLOBAL lane index
    (``fold_in(chunk_key, lane)``): lane ``i`` draws the same values at
    any batch width or device layout -- the stream half of the
    determinism contract (stage A's fixed-width compile is the other
    half).  Returns ``shape + (n,)``.
    """
    lane_keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(key, lane_idx)
    u = jax.vmap(lambda k: jax.random.uniform(k, shape, **kw))(lane_keys)
    return jnp.moveaxis(u, 0, -1)


def _flat_bins(lat, rec, lane_idx, n_total: int):
    """Post-scan vectorized histogram indices for one chunk.

    ``lat``/``rec`` are ``(C, n)``; returns flattened ``lane * N_BINS +
    bin`` int32 indices with unrecorded entries parked in one overflow
    slot (``n_total * N_BINS``).  The lane offsets use the GLOBAL lane
    ids (``lane_idx``) and the overflow slot the GLOBAL padded width, so
    per-shard emissions live in one shared index space and the host's
    single ``bincount`` merges shards exactly; it drops the overflow
    slot, so no boolean compaction is needed on either side.
    """
    bins = jnp.clip((lat * (1.0 / BIN_NS)).astype(jnp.int32), 0, N_BINS - 1)
    off = (lane_idx.astype(jnp.int32) * N_BINS)[None, :]
    return jnp.where(rec, bins + off, n_total * N_BINS)


def _pad_cols(x, pad: int, value: float):
    """Append ``pad`` constant lanes to the trailing axis -- pure data
    movement (bit-exact under any compile), done INSIDE the stage B jit
    so stage A shapes never see the device count."""
    if pad == 0:
        return x
    shape = x.shape[:-1] + (pad,)
    return jnp.concatenate([x, jnp.full(shape, value, x.dtype)], axis=-1)


# ---------------------------------------------------------------------------
# Timestep engine: the 1-ns reference.
# ---------------------------------------------------------------------------

def _ts_draws(cha: ChannelArrays, ov, lane_idx, key, chunk: int):
    """Stage A of the timestep engine: one chunk of per-lane randomness.

    Draws the five per-step uniforms per lane (switch / arrival / jitter
    / blocking-or-not / blocking size) from the lane-keyed streams and
    finishes every law that needs transcendental math: the jitter offset
    and the full two-slope service draw.  Returns ``(chunk, n)`` arrays
    ``(switch_u, arrive_u, jitter, svc)`` -- everything stage B's scan
    consumes, computed at the unpadded width.
    """
    c = _apply_channel_overrides(cha, ov)
    t = _channel_terms(c)
    q_b, s_small, p_stall = t["q_b"], t["s_small"], t["p_stall"]
    sn, xb = c.stall_ns, c.stall_break_ns
    a1, a2, cap = c.stall_alpha, c.stall_alpha2, c.stall_max_ns
    u5 = _lane_uniforms(key, lane_idx, (chunk, 5))    # (chunk, 5, n)
    switch_u, arrive_u, jitter_u, svc_u, size_u = jnp.moveaxis(u5, 1, 0)
    jitter = (jitter_u * 2.0 - 1.0) * c.service_jitter_ns
    # Inverse-CDF sample of the two-slope law: the uniform IS the
    # survival value -- above q_b the first slope applies, below it the
    # far tail, capped at the max.
    u = jnp.maximum(size_u, 1e-7)
    stall = jnp.where(u > q_b, sn * u ** (-1.0 / a1),
                      xb * (q_b / u) ** (1.0 / a2))
    stall = jnp.minimum(stall, cap)
    svc = jnp.where(svc_u < p_stall, stall, s_small)
    return switch_u, arrive_u, jitter, svc


_ts_draws_jit = jax.jit(_ts_draws, static_argnames=("chunk",))


def _ts_harvest_u(lane_idx, key, chunk: int):
    """Harvest half of timestep stage A: one chunk of per-lane switch
    uniforms for the lent/reclaimed chain, drawn from a SEPARATE salted
    lane-keyed stream (``fold_in(key, _HARVEST_SALT)``) so the five
    arrival/service uniforms of :func:`_ts_draws` -- and with them the
    ``harvest_duty = 0`` histograms -- never shift.  A separate jitted
    executable for the same reason."""
    return _lane_uniforms(jax.random.fold_in(key, _HARVEST_SALT),
                          lane_idx, (chunk,))


_ts_harvest_u_jit = jax.jit(_ts_harvest_u, static_argnames=("chunk",))


def _scan_terms(cha: ChannelArrays, ov):
    """Per-run channel constants consumed by the stage B scans (computed
    once at the unpadded width, like stage A): MMPP switch/rate terms,
    the admission bound, and the deterministic access latency
    ``lat0 = service + pipeline + CXL``."""
    c = _apply_channel_overrides(cha, ov)
    t = _channel_terms(c)
    return dict(p_leave=t["p_leave"], p_enter=t["p_enter"],
                rate_hi=t["rate_hi"], rate_lo=t["rate_lo"],
                bound=c.outstanding * c.t_xfer_ns,
                lat0=c.service_ns + 2.0 + c.cxl_lat_ns)


_scan_terms_jit = jax.jit(_scan_terms)


def _ts_chunk_core(terms, state, lane_idx, switch_u, arrive_u, jitter, svc,
                   harvest_u, record, n_total: int):
    """Stage B of the timestep engine: one chunk of the backlog scan.

    The per-nanosecond recursion over stage A's precomputed draws.
    Instead of scatter-updating a histogram carried through the scan,
    the body EMITS ``(latency, arrive * record)`` and the histogram
    indices are produced post-scan, vectorized over the whole chunk (the
    host accumulates them with one ``bincount``).  Counts are small
    integers, exact in either accumulation order, so the emission
    micro-opt and the per-shard merge are both exact.
    """
    _TRACE_COUNT["timestep"] += 1  # side effect runs at trace time only
    n = lane_idx.shape[0]
    p_leave, p_enter = terms["p_leave"], terms["p_enter"]
    rate_hi, rate_lo = terms["rate_hi"], terms["rate_lo"]
    bound, lat0 = terms["bound"], terms["lat0"]
    h_leave, h_enter = terms["h_leave"], terms["h_enter"]
    h_scale = terms["h_scale"]

    # Strong-typed 0/1 so the carry dtype is stable across chunk calls
    # (a weak-typed literal would force a second trace of the kernel).
    zero, one = jnp.zeros(n, jnp.float32), jnp.ones(n, jnp.float32)

    def step(carry, xs):
        sw, au, jit_ns, s, hu, rec = xs
        backlog, in_burst, lent = carry
        in_burst = jnp.where(
            in_burst > 0.5,
            jnp.where(sw < p_leave, zero, one),
            jnp.where(sw < p_enter, one, zero))
        # Harvest lent/reclaimed chain: the same two-state construction
        # as the MMPP burst chain, on the same lattice.  ``h_enter`` is
        # exactly 0.0 at duty = 0, so the chain never leaves the
        # reclaimed state and ``s_eff`` is exactly ``s`` -- the
        # unharvested backlog path, bit for bit.
        lent = jnp.where(
            lent > 0.5,
            jnp.where(hu < h_leave, zero, one),
            jnp.where(hu < h_enter, one, zero))
        rate = jnp.where(in_burst > 0.5, rate_hi, rate_lo)
        arrive = (au < rate).astype(jnp.float32)
        # Closed-loop population bound: while the backlog holds more than
        # ``outstanding`` requests' worth of work the MSHRs are full and
        # the core stalls instead of issuing -- the arrival is blocked,
        # not queued.  inf (the default) admits everything: open loop.
        arrive = arrive * (backlog <= bound).astype(jnp.float32)
        latency = backlog + lat0 + jit_ns
        s_eff = jnp.where(lent > 0.5, s * h_scale, s)
        # arrive is an exact 0/1, so ``backlog + arrive * s`` cannot be
        # perturbed by FMA contraction -- stage B stays compile-exact.
        backlog = jnp.maximum(backlog + arrive * s_eff - 1.0, 0.0)
        return (backlog, in_burst, lent), (latency, arrive * rec)

    state, (lat, mask) = jax.lax.scan(
        step, state, (switch_u, arrive_u, jitter, svc, harvest_u, record))
    return state, _flat_bins(lat, mask > 0.0, lane_idx, n_total)


@functools.lru_cache(maxsize=None)
def _ts_kernel(ndev: int, n_total: int, n_real: int):
    """The jitted (and, for ``ndev > 1``, lane-sharded) stage B timestep
    kernel.  Pads stage A's unpadded outputs to the device multiple
    in-jit (pure data movement), then runs the scan per lane shard."""
    pad = n_total - n_real
    lane_idx = jnp.arange(n_total, dtype=jnp.int32)

    def body(terms, state, lanes, switch_u, arrive_u, jitter, svc,
             harvest_u, record):
        return _ts_chunk_core(terms, state, lanes, switch_u, arrive_u,
                              jitter, svc, harvest_u, record, n_total)

    L, R = shardsim.lanes(), shardsim.replicated()
    L1 = shardsim.lanes(1)
    fn = shardsim.jit_lanes(
        body, ndev,
        in_specs=(L, L, L, L1, L1, L1, L1, L1, R),
        out_specs=(L, L1))

    # Not ``run``: that name (``jit_run`` on the device trace) is the event
    # engine's stage B alone.
    def ts_scan(terms, state, switch_u, arrive_u, jitter, svc, harvest_u,
                record):
        # NaN terms / zeroed draws on padding lanes: they never arrive,
        # never record, and park all mass in the overflow slot.
        terms = {k: _pad_cols(v, pad, np.nan) for k, v in terms.items()}
        return fn(terms, state, lane_idx,
                  _pad_cols(switch_u, pad, 0.0), _pad_cols(arrive_u, pad, 0.0),
                  _pad_cols(jitter, pad, 0.0), _pad_cols(svc, pad, 0.0),
                  _pad_cols(harvest_u, pad, 0.0), record)

    return jax.jit(ts_scan)


# ---------------------------------------------------------------------------
# Event engine: per-request Lindley scan.
# ---------------------------------------------------------------------------

@jax.named_scope("memsim.stage_a")
def _event_tables(cha: ChannelArrays, ov, lane_idx, key, n_sojourns: int):
    """Simulate the MMPP modulating chain once per call (per lane).

    Alternating exponential sojourns starting in the burst state (drawn
    from the lane-keyed streams, :func:`_lane_uniforms`); returns
    per-lane ``(M+1,)`` rows of cumulative intensity ``L``, boundary time
    ``T`` and segment rate -- the piecewise-linear cumulative-intensity
    table the chunk kernel inverts.  The appended final segment extends
    to infinity at the average rate, so lanes whose horizon outruns the
    sampled chain degrade to uncorrelated (but rate-exact) arrivals
    instead of running dry.
    """
    c = _apply_channel_overrides(cha, ov)
    n = c.rho.shape[0]
    t = _channel_terms(c)
    su = _lane_uniforms(key, lane_idx, (n_sojourns,), minval=1e-12)
    burst = (jnp.arange(n_sojourns) % 2 == 0)[:, None]
    soj = -jnp.log(su) * jnp.where(burst, 1.0 / t["p_leave"],
                                   1.0 / t["p_enter"])
    rate_m = jnp.where(burst, t["lam_hi"], t["lam_lo"])
    T0 = jnp.concatenate([jnp.zeros((1, n)), jnp.cumsum(soj, axis=0)])
    L0 = jnp.concatenate([jnp.zeros((1, n)),
                          jnp.cumsum(rate_m * soj, axis=0)])
    rate_seg = jnp.concatenate(
        [rate_m, jnp.maximum(t["lam_avg"], 1e-9)[None]])
    # (n, M+1) intensity rows for the segment lookup + one packed gather
    # table.
    return L0.T, jnp.stack([T0.T, L0.T, rate_seg.T], axis=-1)


_event_tables_jit = jax.jit(_event_tables, static_argnames=("n_sojourns",))


def _seg_bsearch(ut, Lt):
    """Segment index of each request, ``#{j : Lt[j] < ut[k]} - 1`` per
    lane, for SORTED queries ``ut`` ``(n, C)`` against boundary rows
    ``Lt`` ``(n, M+1)``: the few boundaries are positioned among the many
    requests (one small searchsorted per lane) and the per-request index
    is recovered as a scatter + cumulative count, a staircase in k.
    Cheap where gathers are (the CPU); on a TPU its dependent gathers
    run at about 100 M a second.
    """
    n, chunk = ut.shape
    pos = jax.vmap(lambda q, l: jnp.searchsorted(q, l, side="right")
                   )(ut, Lt)                                      # (n, M+1)
    cnt = jnp.zeros((n, chunk + 1), jnp.int32)
    cnt = cnt.at[jnp.arange(n)[:, None], pos].add(1)
    return jnp.cumsum(cnt[:, :chunk], axis=1) - 1


def _count_below(t, bounds):
    """``#{j : bounds[i, j] < t[k, i]}`` as int32 ``(C, n)``, for queries
    ``t`` ``(C, n)`` against boundary rows ``bounds`` ``(n, M)``: every
    boundary against every query, summed over the boundaries (a major
    axis, so the reduction is elementwise adds across the lanes).  No
    gather and no temporary on a TPU; exact for any order of ``t``, and
    ``searchsorted(bounds[i], t[:, i], side="left")`` on sorted rows.
    """
    below = bounds.T[None, :, :] < t[:, None, :]                  # (C, M, n)
    return jnp.sum(below, axis=1, dtype=jnp.int32)


def _seg_count(ut, Lt):
    """:func:`_seg_bsearch`'s integers as one fused compare-count
    (:func:`_count_below`)."""
    return (_count_below(ut.T, Lt) - 1).T


def _seg_lookup(platform: str) -> str:
    """The lookup :func:`_event_arrivals` and :func:`_event_harvest_scale`
    both lower on ``platform`` (the ``seg_lookup`` argument of the
    ``memsim.simulate`` span)."""
    return "bsearch" if platform == "cpu" else "count"


@jax.named_scope("memsim.stage_a")
def _event_arrivals(cha: ChannelArrays, ov, state, lane_idx, key, tabs,
                    warmup_ns, chunk: int):
    """Stage A of the event engine: one chunk of arrivals + services.

    Per candidate request, in vectorized passes: a unit-exponential
    increment of cumulative intensity, inverted through the MMPP's
    piecewise-linear intensity table to a continuous arrival time (exact
    in-gap phase switching -- the Cox construction), then CEILED onto the
    timestep engine's 1-ns lattice (candidates sharing a cell merge into
    one arrival, which is exactly the Bernoulli-per-ns arrival law, gap
    by gap); a service draw from the shared two-slope law (selection and
    size from ONE uniform: conditioned on ``u < stall_prob``,
    ``u / stall_prob`` is again uniform).  Runs at the unpadded width
    (its logs/exps must not recompile with the device count); stage B
    gets ``(gaps, svc, rec_time)`` plus this stage's own
    ``(u_last, t_last)`` carry.
    """
    c = _apply_channel_overrides(cha, ov)
    t = _channel_terms(c)
    q_b, s_small, p_stall = t["q_b"], t["s_small"], t["p_stall"]
    sn, xb = c.stall_ns, c.stall_break_ns
    a1, a2, cap = c.stall_alpha, c.stall_alpha2, c.stall_max_ns
    log_qb = jnp.log(q_b)
    Lt, packed = tabs
    m = Lt.shape[1] - 1

    u_last, t_last = state
    u = _lane_uniforms(key, lane_idx, (2, chunk), minval=1e-12)
    lg = jnp.log(u)                       # one fused pass for both rows
    # Arrival times: unit-exponential increments of cumulative intensity,
    # inverted through the per-lane piecewise-linear table: request k
    # lies in segment #{j : L0[j] < U_k} - 1 (see _seg_bsearch and
    # _seg_count for the two ways to find it).
    upos = u_last[None, :] + jnp.cumsum(-lg[0], axis=0)           # (C, n)
    ut = upos.T                                                   # (n, C)
    seg = jnp.clip(jax.lax.platform_dependent(
        ut, Lt, cpu=_seg_bsearch, default=_seg_count), 0, m)
    tab = jnp.take_along_axis(packed, seg[..., None], axis=1)     # (n, C, 3)
    arr_t = jnp.ceil((tab[..., 0] + (ut - tab[..., 1]) /
                      jnp.maximum(tab[..., 2], 1e-12)).T)  # lattice cell
    gaps = jnp.diff(jnp.concatenate([t_last[None, :], arr_t], axis=0),
                    axis=0)
    real = gaps > 0.5                  # same-cell candidates merge
    # Service: one uniform for selection AND size (conditioned on
    # ``u < stall_prob``, ``u / stall_prob`` is again uniform), one log +
    # one exp for the whole two-slope inverse CDF (the slope pick happens
    # in log space).
    us = u[1]
    lu = lg[1] - jnp.log(p_stall)
    log_stall = jnp.where(us > q_b * p_stall,
                          jnp.log(sn) - lu / a1,
                          jnp.log(xb) + (log_qb - lu) / a2)
    svc = jnp.where(us < p_stall,
                    jnp.minimum(jnp.exp(log_stall), cap), s_small)
    svc = jnp.where(real, svc, 0.0)    # phantoms add no work
    # Lattice cell k is recorded iff the timestep engine would record
    # step k-1, i.e. past the warmup window (stage B adds the admission
    # test, whose witness is the emitted wait itself).
    rec_time = real & (arr_t > warmup_ns + 0.5)
    return (upos[-1], arr_t[-1]), gaps, svc, rec_time


_event_arrivals_jit = jax.jit(_event_arrivals, static_argnames=("chunk",))


@jax.named_scope("memsim.harvest")
def _event_harvest_tabs(cha: ChannelArrays, ov, lane_idx, key,
                        n_windows: int):
    """Simulate the harvest lent/reclaimed chain once per call (per lane).

    Alternating exponential sojourns starting in the RECLAIMED state,
    drawn from the salted lane-keyed streams (the event-engine mirror of
    :func:`_event_tables`'s MMPP sojourns -- same window count, sized
    from the request budget alone so the trace stays value-independent).
    Returns per-lane ``(M,)`` cumulative boundary times; the interval an
    arrival lands in (the number of boundaries strictly below it) is lent
    iff its index is odd.
    At ``duty = 0`` the first reclaimed sojourn is infinite
    (``1 / h_enter``), so every arrival lands in interval 0.
    """
    c = _apply_channel_overrides(cha, ov)
    t = _harvest_terms(c)
    su = _lane_uniforms(jax.random.fold_in(key, _HARVEST_SALT),
                        lane_idx, (n_windows,), minval=1e-12)
    lent = (jnp.arange(n_windows) % 2 == 1)[:, None]
    soj = -jnp.log(su) * jnp.where(lent, 1.0 / t["h_leave"],
                                   1.0 / t["h_enter"])
    return jnp.cumsum(soj, axis=0).T                      # (n, M)


_event_harvest_tabs_jit = jax.jit(_event_harvest_tabs,
                                  static_argnames=("n_windows",))


def _window_bsearch(arr_t, bounds):
    """:func:`_count_below`'s integers by one ``searchsorted`` per lane:
    cheap on the CPU, a loop of dependent gathers on a TPU."""
    return jax.vmap(jnp.searchsorted)(bounds, arr_t.T).T


@jax.named_scope("memsim.harvest")
def _event_harvest_scale(svc, gaps, t0, bounds, h_scale, lent_sum):
    """Scale the services that arrive inside lent windows (event engine),
    and add each lane's candidate slots that arrived in one to the
    per-lane int32 count ``lent_sum`` (the ``lent`` counter).

    A separate executable BETWEEN stage A and stage B: the arrival /
    service draws upstream (:func:`_event_arrivals`) and the Lindley
    kernel downstream are the exact same executables as the unharvested
    path -- this pass is simply skipped when harvesting is inactive, so
    ``duty = 0`` stays bit-identical by construction.  Arrival times are
    rebuilt from the gaps: lattice cells are whole f32 integers, so the
    cumulative sum reproduces them exactly (below 2**24 ns of simulated
    horizon, far beyond any realistic budget).  Each arrival's window is
    the number of boundaries strictly below it (ties go to the earlier
    window), found as stage A finds its segments: a binary search on the
    CPU, the fused compare-count elsewhere (see :func:`_seg_lookup`).
    """
    arr_t = t0[None, :] + jnp.cumsum(gaps, axis=0)        # (C, n)
    idx = jax.lax.platform_dependent(
        arr_t, bounds, cpu=_window_bsearch, default=_count_below)
    lent = idx % 2 == 1
    return (jnp.where(lent, svc * h_scale[None, :], svc),
            lent_sum + jnp.sum(lent, axis=0, dtype=jnp.int32))


_event_harvest_scale_jit = jax.jit(_event_harvest_scale)


def _event_chunk_core(terms, W, lane_idx, gaps, svc, rec_time,
                      n_total: int):
    """Stage B of the event engine: one chunk of the Lindley recursion.

    The only sequential part of the engine -- a four-op scan body:

        W <- max(W - A_k, 0);  admit = W <= outstanding * t_xfer;
        emit W;                W <- W + admit * S_k

    (phantom same-cell candidates carry ``A = 0``, ``S = 0`` and are
    masked out of the histogram, so they are invisible to the queue).
    Latencies are ``W`` plus the deterministic access terms; the uniform
    DRAM jitter is convolved into the histogram afterwards (it never
    feeds the queue).
    """
    _TRACE_COUNT["event"] += 1  # side effect runs at trace time only
    bound, lat0 = terms["bound"], terms["lat0"]

    def event(wc, xs):
        gap, s = xs
        wc = jnp.maximum(wc - gap, 0.0)
        return wc + jnp.where(wc <= bound, s, 0.0), wc

    W, wq = jax.lax.scan(event, W, (gaps, svc), unroll=8)
    # The emitted wait IS the admission witness: recompute the bound test
    # vectorized instead of emitting a second buffer from the scan.
    lat = wq + lat0
    rec = rec_time & (wq <= bound)
    return W, _flat_bins(lat, rec, lane_idx, n_total)


@functools.lru_cache(maxsize=None)
def _event_kernel(ndev: int, n_total: int, n_real: int, chunk: int):
    """The jitted (and, for ``ndev > 1``, lane-sharded) stage B event
    kernel.  Pads stage A's unpadded outputs to the device multiple
    in-jit (pure data movement), then runs the scan per lane shard."""
    pad = n_total - n_real
    lane_idx = jnp.arange(n_total, dtype=jnp.int32)

    def body(terms, W, lanes, gaps, svc, rec_time):
        with jax.named_scope("memsim.stage_b"):
            return _event_chunk_core(terms, W, lanes, gaps, svc, rec_time,
                                     n_total)

    L, R = shardsim.lanes(), shardsim.replicated()
    L1 = shardsim.lanes(1)
    fn = shardsim.jit_lanes(
        body, ndev,
        in_specs=(L, L, L, L1, L1, L1),
        out_specs=(L, L1))

    def run(terms, W, gaps, svc, rec_time):
        # Padding lanes: unit gaps, zero service, never recorded and a
        # NaN bound (every comparison false), so their wait stays 0 and
        # all their mass parks in the overflow slot.
        terms = {k: _pad_cols(v, pad, np.nan) for k, v in terms.items()}
        return fn(terms, W, lane_idx,
                  _pad_cols(gaps, pad, 1.0), _pad_cols(svc, pad, 0.0),
                  _pad_cols(rec_time, pad, False))

    return jax.jit(run)


def events_for_steps(steps: int) -> int:
    """Event-engine request budget equivalent to ``steps`` ns of timestep
    budget (see :data:`EVENTS_PER_NS`).  The driver rounds it up to whole
    chunks of the batch's (width-adaptive) chunk length."""
    return max(_EV_CHUNK_MIN, int(round(steps * EVENTS_PER_NS)))


def _jitter_kernel(width: np.ndarray) -> np.ndarray:
    """Per-lane histogram kernel of the uniform(-w, w) DRAM jitter.

    Tap ``k`` holds the overlap of bin offset ``[k*BIN - BIN/2, k*BIN +
    BIN/2)`` with the jitter support, so convolving a histogram with the
    kernel equals sampling the jitter per request, up to half-bin
    quantization.  Zero width degrades to the identity kernel.
    """
    width = np.asarray(width, np.float64)
    taps = int(np.ceil(np.max(width, initial=0.0) / BIN_NS)) + 1
    k = np.arange(-taps, taps + 1, dtype=np.float64)
    wide = width[:, None] >= 1e-9
    w = np.where(wide, width[:, None], 1.0)
    lo = np.maximum(k[None, :] * BIN_NS - BIN_NS / 2, -w)
    hi = np.minimum(k[None, :] * BIN_NS + BIN_NS / 2, w)
    kern = np.maximum(hi - lo, 0.0) / (2.0 * w)
    kern = np.where(wide, kern, (k == 0.0)[None, :])
    return kern


def _convolve_jitter(hist: np.ndarray, width: np.ndarray) -> np.ndarray:
    """Convolve per-lane histograms with their jitter kernels, clamping
    shifted-out mass into the edge bins (mass is conserved exactly)."""
    kern = _jitter_kernel(width)
    taps = (kern.shape[1] - 1) // 2
    out = np.zeros_like(hist, np.float64)
    nb = hist.shape[-1]
    for i, kk in enumerate(range(-taps, taps + 1)):
        w = kern[:, i][:, None]
        if not np.any(w > 0):
            continue
        if kk >= nb:               # shift beyond the span: all mass clamps
            out[:, -1:] += hist.sum(axis=1, keepdims=True) * w
        elif kk <= -nb:
            out[:, :1] += hist.sum(axis=1, keepdims=True) * w
        elif kk >= 0:
            out[:, kk:] += hist[:, :nb - kk] * w
            if kk > 0:
                out[:, -1:] += hist[:, nb - kk:].sum(axis=1, keepdims=True) * w
        else:
            out[:, :kk] += hist[:, -kk:] * w
            out[:, :1] += hist[:, :-kk].sum(axis=1, keepdims=True) * w
    return out


# ---------------------------------------------------------------------------
# Shared driver + statistics.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class LatencyStats:
    """Latency-distribution summary; leaves share any leading cell/grid
    shape, with ``hist`` carrying one trailing bin axis."""

    mean_ns: np.ndarray
    stdev_ns: np.ndarray
    p50_ns: np.ndarray
    p90_ns: np.ndarray
    p99_ns: np.ndarray
    hist: np.ndarray            # (..., N_BINS) counts
    bin_ns: float = BIN_NS

    _ARRAY_FIELDS = ("mean_ns", "stdev_ns", "p50_ns", "p90_ns", "p99_ns",
                     "hist")

    def __getitem__(self, idx) -> "LatencyStats":
        """Slice the leading (cell/grid) axes of every leaf identically."""
        return LatencyStats(**{f: getattr(self, f)[idx]
                               for f in self._ARRAY_FIELDS},
                            bin_ns=self.bin_ns)

    def reshape(self, *grid_shape) -> "LatencyStats":
        """Reshape the leading axes; the histogram bin axis stays last."""
        shaped = {f: getattr(self, f).reshape(grid_shape)
                  for f in self._ARRAY_FIELDS if f != "hist"}
        shaped["hist"] = self.hist.reshape(tuple(grid_shape) +
                                           self.hist.shape[-1:])
        return LatencyStats(**shaped, bin_ns=self.bin_ns)

    def cdf(self, i=None) -> tuple[np.ndarray, np.ndarray]:
        """(latency_ns, cdf) arrays for cell ``i`` (Fig 6b).

        ``i`` may be omitted when the stats hold a single cell (``hist``
        is one-dimensional), e.g. after a fully pinned
        ``DistributionSweepResult.sel``.
        """
        h = self.hist if i is None else self.hist[i]
        if h.ndim != 1:
            raise ValueError(
                f"cdf() needs one cell; hist has shape {h.shape} -- "
                f"index a cell or sel() down to one")
        c = np.cumsum(h) / max(h.sum(), 1.0)
        x = (np.arange(h.shape[-1]) + 0.5) * self.bin_ns
        return x, c


def _stats_from_hist(hist: np.ndarray) -> LatencyStats:
    centers = (np.arange(hist.shape[-1]) + 0.5) * BIN_NS
    total = np.maximum(hist.sum(axis=-1, keepdims=True), 1.0)
    p = hist / total
    mean = (p * centers).sum(axis=-1)
    var = (p * (centers - mean[..., None]) ** 2).sum(axis=-1)
    cum = np.cumsum(p, axis=-1)

    def quantile(q):
        idx = np.argmax(cum >= q, axis=-1)
        return (idx + 0.5) * BIN_NS

    return LatencyStats(
        mean_ns=mean, stdev_ns=np.sqrt(var), p50_ns=quantile(0.5),
        p90_ns=quantile(0.9), p99_ns=quantile(0.99), hist=hist)


def default_warmup(steps: int) -> int:
    return steps // WARMUP_DIV


def _nan_overrides(n: int) -> dict:
    # Explicit dtype => strong-typed leaves, so the jit signature doesn't
    # depend on WHICH fields an axis binds (bound overrides are strong
    # float32 too) -- any axis combination of one size shares a compile.
    nans = jnp.full((n,), jnp.nan, jnp.float32)
    return {f: nans for f in CHANNEL_FIELDS}


def _accumulate_chunks(dispatch, n_chunks: int, n: int) -> np.ndarray:
    """Drive the per-chunk kernel and histogram its emissions.

    ``dispatch(k)`` runs chunk ``k`` and returns the flattened histogram
    indices (asynchronously); the host folds each chunk into the counts
    with one integer ``bincount`` while the next chunk computes, then
    drops the overflow slot (its count goes to ``_CALL["unrecorded"]``).
    Counts are exact integers, so accumulation order cannot perturb them.
    """
    call = _CALL["id"]
    hist = np.zeros(n * N_BINS + 1, np.int64)

    def fold(pending):
        with _span("memsim.fetch", call=call):
            flat = np.asarray(pending)
        with _span("memsim.bincount", call=call):
            hist[:] += np.bincount(flat.reshape(-1), minlength=hist.size)

    with _span("memsim.dispatch", call=call, k=0):
        pending = dispatch(0)
    for k in range(1, n_chunks):
        with _span("memsim.dispatch", call=call, k=k):
            nxt = dispatch(k)       # async: overlaps the fold below
        fold(pending)
        pending = nxt
    fold(pending)
    _CALL["unrecorded"] = int(hist[-1])
    return hist[:-1].reshape(n, N_BINS).astype(np.float64)


def _run_timestep(cha, ov, steps, seed, warmup, ndev, n_real, pad,
                  lane_r, chunk, n_chunks):
    n_tot = n_real + pad
    with _span("memsim.prepare", call=_CALL["id"]):
        ckeys = np.asarray(jax.random.split(jax.random.PRNGKey(seed),
                                            n_chunks))
        record = np.zeros(n_chunks * chunk, np.float32)
        record[warmup:steps] = 1.0
        terms = {**_scan_terms_jit(cha, ov),
                 **_harvest_scan_terms_jit(cha, ov)}
        state = (jnp.zeros(n_tot), jnp.ones(n_tot), jnp.zeros(n_tot))
        fn = _ts_kernel(ndev, n_tot, n_real)
        # Unharvested batches skip the extra per-step uniform: with
        # ``h_enter = 0`` the chain ignores its draws, so constant zeros
        # are value-identical (same kernel, same trace) and cost nothing.
        hactive = _harvest_active(cha, ov)
        hu0 = None if hactive else jnp.zeros((chunk, n_real), jnp.float32)

    def dispatch(k):
        nonlocal state
        sw, au, jit_ns, svc = _ts_draws_jit(cha, ov, lane_r,
                                            jnp.asarray(ckeys[k]),
                                            chunk=chunk)
        hu = (_ts_harvest_u_jit(lane_r, jnp.asarray(ckeys[k]), chunk=chunk)
              if hactive else hu0)
        state, flat = fn(terms, state, sw, au, jit_ns, svc, hu,
                         jnp.asarray(record[k * chunk:(k + 1) * chunk]))
        return flat

    return _accumulate_chunks(dispatch, n_chunks, n_tot)[:n_real]


def _event_windows(n_chunks: int, chunk: int) -> int:
    """Sojourns of the event engine's MMPP table, and windows of its
    harvest table: sized from the request budget alone."""
    return max(64, (n_chunks * chunk) // _SOJOURN_DIV)


def _run_event(cha, ov, seed, warmup, ndev, n_real, pad, lane_r, chunk,
               n_chunks, hactive: bool):
    n_tot = n_real + pad
    call = _CALL["id"]
    n_sojourns = _event_windows(n_chunks, chunk)
    with _span("memsim.prepare", call=call):
        phase_key, chunk_root = jax.random.split(jax.random.PRNGKey(seed))
        keys = jax.random.split(chunk_root, n_chunks)
        tabs = _event_tables_jit(cha, ov, lane_r, phase_key,
                                 n_sojourns=n_sojourns)
        terms = _scan_terms_jit(cha, ov)
        state_a = (jnp.zeros(n_real), jnp.zeros(n_real))
        W = jnp.zeros(n_tot)
        warm = jnp.float32(warmup)
        fn = _event_kernel(ndev, n_tot, n_real, chunk)
        # Harvest windows: a second sojourn table from the salted stream
        # and a standalone scaling pass between the stages -- both
        # skipped entirely when harvesting is inactive (``hactive``, the
        # caller's :func:`_harvest_active`), so the unharvested event
        # path runs the exact pre-harvest executables.
        if hactive:
            htabs = _event_harvest_tabs_jit(cha, ov, lane_r, phase_key,
                                            n_windows=n_sojourns)
            h_scale = _harvest_scan_terms_jit(cha, ov)["h_scale"]
            lent = jnp.zeros(n_real, jnp.int32)

    def dispatch(k):
        nonlocal state_a, W, lent
        t_prev = state_a[1]
        state_a, gaps, svc, rec_time = _event_arrivals_jit(
            cha, ov, state_a, lane_r, keys[k], tabs, warm, chunk=chunk)
        if hactive:
            svc, lent = _event_harvest_scale_jit(svc, gaps, t_prev, htabs,
                                                 h_scale, lent)
        W, flat = fn(terms, W, gaps, svc, rec_time)
        return flat

    hist = _accumulate_chunks(dispatch, n_chunks, n_tot)[:n_real]
    _CALL["lent"] = 0
    if hactive:
        with _span("memsim.fetch", call=call):
            _CALL["lent"] = int(np.asarray(lent).sum(dtype=np.int64))
    # Jitter is additive observation noise: convolve its exact uniform
    # distribution into the histogram (per-lane effective width).
    with _span("memsim.jitter", call=call):
        own = np.asarray(ov["service_jitter_ns"])[:n_real]
        width = np.where(np.isnan(own),
                         np.asarray(cha.service_jitter_ns)[:n_real], own)
        return _convolve_jitter(hist, width)


def merge_reps(stats: LatencyStats) -> LatencyStats:
    """Merge a ``keep_reps=True`` result over its leading replica axis.

    Histogram counts are integers, so merging after the fact is exactly
    the ``keep_reps=False`` result.
    """
    return _stats_from_hist(stats.hist.sum(axis=0))


def simulate_cells(cha: ChannelArrays, *, overrides=None,
                   steps: int = 200_000, seed: int = 0,
                   warmup: int | None = None, reps: int = 1,
                   engine: str = "timestep", events: int | None = None,
                   devices=None, keep_reps: bool = False,
                   stream_ids=None, chunk: int | None = None
                   ) -> LatencyStats:
    """Simulate N flattened cells in one jitted batch.

    ``cha`` leaves are ``(N,)``; ``overrides`` maps channel fields to
    ``(N,)`` arrays with NaN meaning "keep the channel's own value".
    Missing override fields are filled with NaN so the jit cache keys on
    the flattened cell count alone -- any axis combination of the same
    flattened size shares one compile per engine (and device count).

    ``steps`` is the simulated-time budget in ns for EITHER engine;
    ``engine="event"`` converts it to a per-request budget
    (:func:`events_for_steps`) unless ``events`` pins one explicitly.
    ``warmup`` ns of simulated time (default ``steps // 10``) are
    excluded from the histograms.  ``reps`` runs that many independent
    replicas of every cell in the same batch and merges their histograms
    -- variance reduction that costs almost nothing next to the per-step
    (or per-request) dispatch; ``keep_reps=True`` skips the merge and
    returns stats with a leading ``(reps,)`` axis instead (per-replica
    batched means, e.g. for standard-error estimates -- see
    :func:`merge_reps`).

    ``devices`` shards the flattened ``(cells x reps)`` lane axis over
    that many host devices (``None`` means 1; ``"auto"`` uses all local
    devices).  The batch is NaN-padded to a multiple of the device
    count; lane-keyed streams and global-lane histogram slots make the
    result BIT-IDENTICAL at any device count -- the argument trades
    wall-clock only.

    Results are exactly reproducible per ``(engine, seed, budget, N)``;
    the two engines draw different streams and agree statistically, not
    bitwise.

    ``stream_ids`` (an ``(N,)`` uint32 array) replaces the positional
    lane-stream keying with CALLER-OWNED ids, and ``chunk`` pins the
    chunk schedule independently of the batch width (see
    :func:`canonical_chunk`).  Together they make a cell's histogram a
    pure function of ``(its channel values, its stream id, seed, budget,
    engine)`` -- independent of which OTHER cells share the batch -- the
    contract the QueueLUT store's incremental builds are built on
    (``tests/test_lutstore.py`` pins it bitwise).  Both default to the
    historical positional/adaptive behavior.
    """
    _check_engine(engine)
    n = int(np.shape(cha.rho)[0])
    reps = int(reps)
    if reps < 1:
        raise ValueError(f"reps must be >= 1; got {reps}")
    warmup = default_warmup(steps) if warmup is None else int(warmup)
    if not 0 <= warmup < steps:
        raise ValueError(f"warmup must be in [0, steps); got {warmup} "
                         f"with steps={steps}")
    if events is not None and engine != "event":
        raise ValueError("events is an event-engine budget; use steps "
                         "for the timestep engine")
    ndev = shardsim.resolve_devices(devices)
    n_real = n * reps
    # cha/ov stay at the UNPADDED width: stage A and the per-run terms
    # compile against n_real only, so their executables (and hence every
    # transcendental rounding) are shared across device counts; stage B
    # pads its inputs to the device multiple internally.
    pad = shardsim.pad_width(n_real, ndev)
    if chunk is not None and int(chunk) < 1:
        raise ValueError(f"chunk must be >= 1; got {chunk}")
    # Chunk length derives from the UNPADDED width: the chunk schedule is
    # part of the stream contract, padding is a device-count artifact.
    # An explicit ``chunk`` pins the schedule width-independently (the
    # canonical stream contract, see :func:`canonical_chunk`).
    if engine == "timestep":
        budget = int(steps)
        chunk = _ts_chunk_len(n_real) if chunk is None else int(chunk)
    else:
        budget = (events_for_steps(steps) if events is None
                  else max(1, int(events)))
        chunk = _event_chunk_len(n_real) if chunk is None else int(chunk)
    n_chunks = -(-budget // chunk)
    _CALL["id"] += 1
    call = _CALL["id"]

    def tile(v):
        return jnp.tile(jnp.asarray(np.asarray(v, np.float32)), reps)

    # The event engine's span names the segment lookup that stage A's
    # platform lowers (the default device's, where its inputs live), and
    # whether the harvest pass runs, with its window-table length.
    event = {}
    if engine == "event":
        hactive = _harvest_active(cha, overrides or {})
        event = dict(seg_lookup=_seg_lookup(jax.devices()[0].platform),
                     harvest=int(hactive),
                     windows=(_event_windows(n_chunks, chunk) if hactive
                              else 0))
    with _span("memsim.simulate", call=call, engine=engine, lanes=n_real,
               devices=ndev, chunks=n_chunks, chunk=chunk, **event):
        with _span("memsim.prepare", call=call):
            ov = _nan_overrides(n_real)
            ov.update({f: tile(v) for f, v in (overrides or {}).items()})
            cha = ChannelArrays(*(tile(leaf) for leaf in cha))
            lane_r = _lane_streams(n, reps, stream_ids)
        if engine == "timestep":
            hist = _run_timestep(cha, ov, int(steps), seed, warmup, ndev,
                                 n_real, pad, lane_r, chunk, n_chunks)
        else:
            hist = _run_event(cha, ov, seed, warmup, ndev, n_real, pad,
                              lane_r, chunk, n_chunks, hactive)
        candidates = n_real * n_chunks * chunk
        recorded = n_chunks * chunk * (n_real + pad) - _CALL["unrecorded"]
        lent = {} if engine == "timestep" else {"lent": _CALL["lent"]}
        with _span("memsim.stats", call=call, candidates=candidates,
                   recorded=recorded, **lent):
            hist = hist.reshape(reps, n, -1)
            return _stats_from_hist(hist if keep_reps
                                    else hist.sum(axis=0))


def simulate(configs, steps: int = 200_000, seed: int = 0,
             warmup: int | None = None, reps: int = 1,
             engine: str = "timestep", devices=None) -> LatencyStats:
    """Simulate a batch of :class:`ChannelConfig` and return stats.

    Thin shim over :func:`simulate_cells` -- bit-identical to any
    distribution sweep whose flat cells match ``configs`` in order (same
    engine, seed, steps, warmup and reps => same random streams, at any
    ``devices``).
    """
    with _span("memsim.prepare"):
        cha = stack_channels(configs)
    return simulate_cells(cha, steps=steps, seed=seed, warmup=warmup,
                          reps=reps, engine=engine, devices=devices)


def load_latency_curve(rhos=None, kappa: float = 1.0, cxl_lat_ns: float = 0.0,
                       steps: int = 200_000, seed: int = 0,
                       warmup: int | None = None, reps: int = 1,
                       engine: str = "timestep", devices=None) -> dict:
    """Fig 2a: mean/p90 latency vs bus utilization for one channel type."""
    if rhos is None:
        rhos = np.linspace(0.05, 0.95, 19)
    configs = [ChannelConfig(rho=float(r), kappa=kappa,
                             cxl_lat_ns=cxl_lat_ns) for r in rhos]
    stats = simulate(configs, steps=steps, seed=seed, warmup=warmup,
                     reps=reps, engine=engine, devices=devices)
    return dict(rho=np.asarray(rhos), mean_ns=stats.mean_ns,
                p90_ns=stats.p90_ns, p99_ns=stats.p99_ns,
                stdev_ns=stats.stdev_ns)
