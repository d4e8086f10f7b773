"""``harvest_roofline``: the least time the chip could take for the
harvest pass of the traced window's DES calls, from the bytes it must
move (``harvest_pass.call_bytes``, for each call whose
``memsim.simulate`` span says it ran the pass) at the chip's HBM
bandwidth, over the pass's device time on the busiest device, in
percent.  The pass is elementwise work, a cumulative sum and a binary
search with no matrix work, so bandwidth is its only roofline."""

from bench.harness import harvest_pass, roofline
from bench.harness.stages import per_job_ms


def read(run):
    ms = per_job_ms(run, harvest_pass.MODULES)
    calls = harvest_pass.harvesting_calls(run)
    if ms is None or not calls:
        return None
    total = sum(harvest_pass.call_bytes(
        int(s.args["lanes"]), int(s.args["chunks"]), int(s.args["chunk"]),
        int(s.args["windows"])) for s in calls)
    least_s = total / run.traced_jobs / roofline.peak(run.device_kind,
                                                      "hbm_bytes_per_s")
    return 100.0 * least_s / (ms * 1e-3)
