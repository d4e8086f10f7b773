"""``setup_s``: seconds from the start of the run to the end of the
warm-up job: imports, device start, job set-up, one whole job at the
cell's shapes, and compilation where the cache misses."""


def read(run):
    return run.setup_s
