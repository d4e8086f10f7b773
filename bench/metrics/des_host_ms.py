"""``des_host_ms``: host ms per traced job inside the program's DES calls
(the union of its ``memsim.*`` spans) less the time the host waits on
the device there (the ``memsim.fetch`` spans: a chunk's bin indices
copied back, and the wait for them)."""

from bench.harness import program_spans as ps
from bench.harness import trace as tracemod


def read(run):
    des = ps.named(ps.of_run(run), "memsim.")
    if not des or not run.traced_jobs:
        return None
    lo, hi = run.window_ns
    inside = tracemod.merged(des, lo, hi)
    fetch = tracemod.merged(ps.named(des, "memsim.fetch"), lo, hi)
    ns = sum(e - s for s, e in inside) - ps.overlap_ns(inside, fetch)
    return ps.per_job_ms(run, ns)
