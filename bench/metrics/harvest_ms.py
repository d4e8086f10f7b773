"""``harvest_ms``: device ms per job in the event engine's harvest pass
(the window table and the per-chunk scaling,
``bench/harness/harvest_pass.py``) on the busiest device, from the
profiler trace."""

from bench.harness.harvest_pass import MODULES
from bench.harness.stages import per_job_ms


def read(run):
    return per_job_ms(run, MODULES)
