"""``stage_a_ms``: device ms per job in the event engine's stage A (the
draws, sojourn tables and harvest pass) on the busiest device, from the
profiler trace."""

from bench.harness.stages import STAGE_A, per_job_ms


def read(run):
    return per_job_ms(run, STAGE_A)
