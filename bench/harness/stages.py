"""Which XLA modules of the device trace belong to which DES stage.

Names as the profiler's "XLA Modules" line gives them, matched at the
start: ``jit_<function>`` with a trailing ``(<id>)``.  Stage A is the
event engine's draws (``memsim._event_arrivals``, once per chunk), its
sojourn tables (``_event_tables``, once per DES call) and the harvest
pass (``_event_harvest_tabs``, ``_event_harvest_scale``); stage B is the
jitted wrapper of the Lindley scan kernel, ``memsim._event_kernel``'s
``run``.
"""

from __future__ import annotations

from bench.harness import trace as tracemod

STAGE_A = r"jit__event_(arrivals|tables|harvest_tabs|harvest_scale)\("
STAGE_B = r"jit_run\("


def per_job_ms(run, pattern: str):
    """Device ms per traced job in ``pattern``'s modules, on the busiest
    device; None without a trace or without such modules."""
    if run.trace is None:
        return None
    lo, hi = run.window_ns
    ns = tracemod.module_ns(run.trace, lo, hi, pattern)
    top = tracemod.busiest(run.trace, lo, hi)
    if not ns.get(top):
        return None
    return ns[top] * 1e-6 / run.traced_jobs
