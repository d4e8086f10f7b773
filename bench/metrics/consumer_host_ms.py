"""``consumer_host_ms``: host ms per traced job in the capacity planner
around its DES call: the union of the program's ``planner.*`` spans
(lane construction, the model solve, the token-latency composition)."""

from bench.harness import program_spans as ps
from bench.harness import trace as tracemod


def read(run):
    spans = ps.named(ps.of_run(run), "planner.")
    if not spans or not run.traced_jobs:
        return None
    lo, hi = run.window_ns
    return ps.per_job_ms(run, tracemod.busy_ns(spans, lo, hi))
