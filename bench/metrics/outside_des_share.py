"""``outside_des_share``: the share of job wall time spent outside the
program's DES calls (``memsim.simulate_cells``), in percent; the
benchmark's own host span around each call."""


def read(run):
    total = sum(run.job_seconds)
    if not run.des_calls or total <= 0:
        return None
    return 100.0 * (total - sum(c.seconds for c in run.des_calls)) / total
