"""``job_s``: the window's wall seconds over the jobs completed in it."""


def read(run):
    return run.window_s / run.jobs
