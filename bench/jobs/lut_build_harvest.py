"""Job kind ``lut_build_harvest``: one cold build of the configuration's
5-D QueueLUT surface, ``queuelut.build_queue_lut`` over the whole grid
with the lent-duty axis (the grid's ``harvest``, passed as ``harvest=``)
and the on-disk store off, at the cell's device count: the surface
``queuelut.default_queue_lut(harvest=True)`` builds.

The answer and the comparison are ``lut_build``'s.  The program keys the
duty axis as ``harvest_duty`` (cell stream ids and channel field), so
the check does too, and simulates the sampled (job, cell) pairs with the
plain reference of the harvesting DES (``bench/reference/harvest.py``).
"""

from __future__ import annotations

import numpy as np

from bench.jobs.lut_build import TABLES, LutBuild
from bench.reference import des, harvest

#: The configuration's duty axis, and the name the program keys it by.
AXIS, FIELD = "harvest", "harvest_duty"


class LutBuildHarvest(LutBuild):
    def __init__(self, cell):
        super().__init__(cell)
        self.axes = tuple(FIELD if k == AXIS else k for k in self.names)

    def reference(self, answers, pairs, dtype="float32") -> dict:
        """The harvest reference's four tables at the sampled pairs."""
        coords = np.stack(np.meshgrid(*self.grid.values(), indexing="ij"),
                          -1).reshape(-1, len(self.axes))
        params = {f: [] for f in harvest.FIELDS}
        streams, seeds = [], []
        for rep in range(self.reps):
            for job, cell in pairs:
                point = dict(zip(self.axes, coords[cell]))
                for f in harvest.FIELDS:
                    params[f].append(point.get(f, self.channel.get(f)))
                streams.append(des.rep_stream(
                    des.cell_stream_id(self.axes, coords[cell]), rep))
                seeds.append(answers[job]["seed"])
        hist = harvest.simulate(params, streams, seeds, steps=self.steps,
                                chunk=self.chunk, dtype=dtype)
        hist = hist.reshape(self.reps, len(pairs), -1).sum(0)
        st = des.stats(hist)
        out = {k: np.maximum(st[s] - self.service_ns, 0.0)
               for k, _, s in TABLES if k != "sigma"}
        out["sigma"] = st["stdev_ns"]
        return out


def make_job(cell) -> LutBuildHarvest:
    return LutBuildHarvest(cell)
