"""Benchmark-side span and counters around every DES call.

Every DES run of the program goes through ``memsim.simulate_cells``.
:class:`DesProbe` wraps it (as ``chip_smoke.Phases`` does) and records,
per call: the wall seconds inside it, the lanes it simulated, the
requests its histograms recorded, and its budget, chunk and device count;
of the last call it keeps what went in and came out, for a job to read.
Each call is also a ``bench.des`` host span in the profiler's trace.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np


@dataclasses.dataclass
class DesCall:
    seconds: float
    lanes: int
    requests: float
    steps: int
    chunk: int | None     # None: the engine's width-adaptive chunk
    devices: int


class DesProbe:
    """Install with :meth:`install`; read ``calls`` and reset with
    :meth:`take`.  ``last`` is the last call's channel arrays, keyword
    arguments and statistics."""

    def __init__(self, memsim, shardsim):
        self.memsim, self.shardsim = memsim, shardsim
        self.calls: list[DesCall] = []
        self.last = None
        self._inner = None

    def install(self) -> "DesProbe":
        inner = self._inner = self.memsim.simulate_cells

        def probed(cha, *args, **kw):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.des"):
                stats = inner(cha, *args, **kw)
            self.last = (cha, kw, stats)
            self.calls.append(DesCall(
                seconds=time.perf_counter() - t0,
                lanes=len(cha.rho) * int(kw.get("reps", 1)),
                requests=float(np.sum(stats.hist)),
                steps=int(kw.get("steps", 200_000)),
                chunk=kw.get("chunk"),
                devices=self.shardsim.resolve_devices(kw.get("devices"))))
            return stats

        self.memsim.simulate_cells = probed
        return self

    def uninstall(self) -> None:
        if self._inner is not None:
            self.memsim.simulate_cells = self._inner
            self._inner = None
        self.last = None

    def take(self) -> list[DesCall]:
        calls, self.calls = self.calls, []
        return calls
