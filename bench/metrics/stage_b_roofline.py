"""``stage_b_roofline``: the least time the chip could take for the
stage-B kernel's calls in the window, from the bytes they must move
(``roofline.stage_b_chunk_bytes`` for one device's lanes, per chunk) at
the chip's HBM bandwidth, over the kernel's device time on the busiest
device, in percent.  The kernel is a sequential scan with no matrix
work, so bandwidth is its only roofline."""

from bench.harness import roofline
from bench.harness.stages import STAGE_B, per_job_ms
from bench.reference import des


def read(run):
    ms = per_job_ms(run, STAGE_B)
    if ms is None:
        return None
    total = 0
    for c in run.des_calls:
        chunk = c.chunk or des.adaptive_chunk(c.lanes)
        n_chunks = -(-des.event_budget(c.steps) // chunk)
        per_dev = -(-c.lanes // c.devices)
        total += n_chunks * roofline.stage_b_chunk_bytes(per_dev, chunk)
    least_s = total / run.jobs / roofline.peak(run.device_kind,
                                               "hbm_bytes_per_s")
    return 100.0 * least_s / (ms * 1e-3)
