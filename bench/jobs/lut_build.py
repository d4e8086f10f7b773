"""Job kind ``lut_build``: one cold build of the configuration's QueueLUT
surface, ``queuelut.build_queue_lut`` over the whole grid with the
on-disk store off, at the cell's device count.

The answer is the surface's four tables (mean, p90 and p99 queue wait,
latency stdev).  The check simulates, with the plain reference DES
(``bench/reference/des.py``) under the stated stream contract, every
cell of whole jobs drawn from the run's seed and a sample of (job, cell)
pairs across the other jobs, also drawn from the seed, and compares
every table at every simulated cell.
"""

from __future__ import annotations

import os

import numpy as np

from bench.harness.runner import Check, sample_rng
from bench.reference import des

#: (answer key, QueueLUT field, reference statistic)
TABLES = (("wait", "wait_ns", "mean_ns"), ("p90", "p90_wait_ns", "p90_ns"),
          ("p99", "p99_wait_ns", "p99_ns"), ("sigma", "sigma_ns",
                                              "stdev_ns"))


class LutBuild:
    def __init__(self, cell):
        c = cell.config
        self.names = tuple(c["grid"])
        self.grid = {k: tuple(float(v) for v in c["grid"][k])
                     for k in self.names}
        self.shape = tuple(len(g) for g in self.grid.values())
        self.steps, self.reps = int(c["steps"]), int(c["reps"])
        self.engine, self.chunk = c["engine"], int(c["chunk"])
        self.channel = dict(c["channel"])
        self.service_ns = float(c["dram_service_ns"])
        self.devices = int(cell.traffic["args"]["devices"])
        self.check_cells = int(cell.traffic["args"]["check_cells"])
        self.check_jobs = int(cell.traffic["args"]["check_jobs"])

    def setup(self, probe) -> None:
        from repro.core import lutstore, memsim
        os.environ.pop(lutstore.ENV_VAR, None)     # on-disk store off
        lutstore.clear_lut_cache()
        self.base = memsim.ChannelConfig(rho=0.5, **self.channel)

    def run(self, seed: int) -> dict:
        from repro.core import queuelut
        lut = queuelut.build_queue_lut(
            **self.grid, steps=self.steps, seed=seed, reps=self.reps,
            engine=self.engine, devices=self.devices, base=self.base)
        return dict(seed=seed, **{k: np.asarray(getattr(lut, f), np.float64)
                                  for k, f, _ in TABLES})

    def release(self) -> None:
        from repro.core import lutstore
        lutstore.clear_lut_cache()
        self.base = None

    @staticmethod
    def finite(answer: dict) -> bool:
        return all(np.all(np.isfinite(answer[k])) for k, _, _ in TABLES)

    # -- the comparison -------------------------------------------------

    def samples(self, answers, seed: int) -> list[tuple[int, int]]:
        """(job, flat cell) pairs drawn from the seed: every cell of
        ``check_jobs`` whole jobs, and ``check_cells`` pairs over all
        jobs."""
        n = int(np.prod(self.shape))
        rng = sample_rng(seed)
        whole = rng.choice(len(answers), replace=False,
                           size=min(self.check_jobs, len(answers)))
        k = min(self.check_cells, len(answers) * n)
        flat = rng.choice(len(answers) * n, size=k, replace=False)
        pairs = {(int(i) // n, int(i) % n) for i in flat}
        pairs |= {(int(j), c) for j in whole for c in range(n)}
        return sorted(pairs)

    def reference(self, answers, pairs, dtype="float32") -> dict:
        """The reference's four tables at the sampled pairs."""
        coords = np.stack(np.meshgrid(*self.grid.values(), indexing="ij"),
                          -1).reshape(-1, len(self.names))
        params = {f: [] for f in des.FIELDS}
        streams, seeds = [], []
        for rep in range(self.reps):
            for job, cell in pairs:
                point = dict(zip(self.names, coords[cell]))
                for f in des.FIELDS:
                    params[f].append(point.get(f, self.channel.get(f)))
                streams.append(des.rep_stream(
                    des.cell_stream_id(self.names, coords[cell]), rep))
                seeds.append(answers[job]["seed"])
        hist = des.simulate(params, streams, seeds, steps=self.steps,
                            chunk=self.chunk, dtype=dtype)
        hist = hist.reshape(self.reps, len(pairs), -1).sum(0)
        st = des.stats(hist)
        out = {k: np.maximum(st[s] - self.service_ns, 0.0)
               for k, _, s in TABLES if k != "sigma"}
        out["sigma"] = st["stdev_ns"]
        return out

    def compare(self, got: dict, ref: dict, limits: dict) -> list[Check]:
        """Per table, the widest deviation over the sampled cells, as a
        share of the reference's mean access latency in that cell."""
        scale = ref["wait"] + self.service_ns
        return [Check(f"{k}_dev_max",
                      float(np.max(np.abs(got[k] - ref[k]) / scale)),
                      float(limits[f"{k}_dev_max"]))
                for k, _, _ in TABLES]

    def check(self, answers, seed: int, limits: dict) -> list[Check]:
        pairs = self.samples(answers, seed)
        got = {k: np.asarray([answers[j][k].reshape(-1)[c]
                              for j, c in pairs]) for k, _, _ in TABLES}
        return self.compare(got, self.reference(answers, pairs), limits)

    def control(self, answers, seed: int, limits: dict) -> list[Check]:
        """The reference in bfloat16 put in the program's place."""
        pairs = self.samples(answers, seed)
        return self.compare(self.reference(answers, pairs, "bfloat16"),
                            self.reference(answers, pairs), limits)


def make_job(cell) -> LutBuild:
    return LutBuild(cell)
