"""``stage_b_ms``: device ms per job in the event engine's stage-B scan
kernel on the busiest device, from the profiler trace."""

from bench.harness.stages import STAGE_B, per_job_ms


def read(run):
    return per_job_ms(run, STAGE_B)
