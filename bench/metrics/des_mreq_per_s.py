"""``des_mreq_per_s``: requests the DES recorded (its histograms' sums)
per wall second inside the DES calls, in millions."""


def read(run):
    secs = sum(c.seconds for c in run.des_calls)
    if secs <= 0:
        return None
    return sum(c.requests for c in run.des_calls) / secs * 1e-6
