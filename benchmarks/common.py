"""Shared benchmark utilities: timing + CSV emission + DES budgets.

Every benchmark prints ``name,us_per_call,derived`` rows; ``derived`` is the
figure/table-relevant quantity (a speedup, a latency, a roofline fraction).
"""

import os
import time

import jax

#: When a list, :func:`emit` also records ``(name, us, derived)`` rows --
#: ``run.py`` points this at a per-section buffer to build the versioned
#: ``BENCH_<rev>.json`` trajectory point.
ROWS = None


def enable_lut_cache() -> str | None:
    """Surface the persistent QueueLUT store (the DES-side warm start).

    ``REPRO_LUT_CACHE`` names a directory; when set, every DES-built
    :class:`repro.core.queuelut.QueueLUT` surface is persisted there and
    later sessions read it back bit-identically instead of re-running
    the simulation (see :mod:`repro.core.lutstore`).  The store is read
    directly by ``queuelut.resolve_lut`` -- this helper only resolves
    (and creates) the directory so ``run.py`` can record it in the
    BENCH trajectory point.
    """
    from repro.core import lutstore
    root = lutstore.cache_dir()
    return None if root is None else str(root)


def _engines() -> tuple:
    """The memsim engines (lazy import: a third engine added to memsim
    is budgetable here without touching this module)."""
    from repro.core import memsim
    return memsim.ENGINES


def des_budget(default: int, engine: str = "timestep") -> int:
    """Per-engine DES budget in simulated ns.

    The budget knob is engine-neutral: ``steps`` means simulated time for
    either engine (``memsim`` converts it to a per-request budget for the
    event engine via ``events_for_steps``), so the single
    ``REPRO_DES_STEPS`` cap throttles BOTH engines coherently -- CI smoke
    sets it low to keep the whole benchmark run under a few minutes; it
    can only shrink the budget, so local full runs are unaffected by a
    stale environment.  ``engine`` is validated so a typo'd engine name
    fails here rather than deep inside a sweep.
    """
    if engine not in _engines():
        raise ValueError(f"unknown engine {engine!r}; choose from "
                         f"{_engines()}")
    cap = os.environ.get("REPRO_DES_STEPS")
    return min(default, int(cap)) if cap else default


def des_steps(default: int) -> int:
    """Legacy alias of :func:`des_budget` (timestep units)."""
    return des_budget(default)


def des_engine(default: str = "timestep") -> str:
    """Engine for DES-driven benchmark sections.

    ``REPRO_DES_ENGINE`` overrides the per-benchmark default -- CI smoke
    sets ``event`` so the DES-heavy sections (the fig2a cross-check, the
    drift LUT build) collect more samples in the same wall-clock.
    """
    engine = os.environ.get("REPRO_DES_ENGINE", default)
    if engine not in _engines():
        raise ValueError(f"REPRO_DES_ENGINE={engine!r} is not an engine; "
                         f"choose from {_engines()}")
    return engine


def time_call(fn, *args, warmup=1, iters=3):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = jax.block_until_ready(fn(*args))
    dt = (time.perf_counter() - t0) / iters
    return dt * 1e6, out


def emit(name, us, derived):
    """One CSV row.  ``us=None`` marks a derived (non-timed) row: the
    timing field is left EMPTY in the CSV and null in the bench JSON,
    so the trajectory diff never mistakes "not timed" for "0.0 us"."""
    if us is None:
        print(f"{name},,{derived}")
    else:
        print(f"{name},{us:.1f},{derived}")
    if ROWS is not None:
        ROWS.append((str(name), None if us is None else float(us),
                     str(derived)))


def emit_derived(name, derived):
    """Emit a row that carries a derived quantity but no timing."""
    emit(name, None, derived)
