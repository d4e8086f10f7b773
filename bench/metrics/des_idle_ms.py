"""``des_idle_ms``: device-idle ms per traced job inside the program's
DES calls (its ``memsim.simulate`` spans).  A device is idle where none
of its XLA modules runs, as for ``device_idle_share``; the mean over the
cell's devices."""

from bench.harness import program_spans as ps
from bench.harness import trace as tracemod


def read(run):
    sims = ps.named(ps.of_run(run), "memsim.simulate")
    if not sims or not run.traced_jobs:
        return None
    lo, hi = run.window_ns
    inside = tracemod.merged(sims, lo, hi)
    total = sum(e - s for s, e in inside)
    names = sorted(run.trace.devices)[:run.cell.chips]
    if not names:
        return None
    idle = [total - ps.overlap_ns(
        inside, tracemod.merged(run.trace.devices[n], lo, hi))
        for n in names]
    return ps.per_job_ms(run, sum(idle) / len(idle))
