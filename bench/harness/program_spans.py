"""The program's own host spans in a run's profiler trace.

The program records ``jax.profiler.TraceAnnotation`` spans where its work
happens: ``memsim.*`` around and inside every DES call
(``repro.core.memsim``: ``memsim.simulate`` and, inside it,
``memsim.prepare``, ``memsim.dispatch``, ``memsim.fetch``,
``memsim.bincount``, ``memsim.jitter``, ``memsim.stats``) and
``planner.*`` in the capacity planner (``planner.lanes``,
``planner.solve``, ``planner.compose``).  They land on the host planes of
the trace the runner writes, on the clock of the devices' module lines,
with their keyword arguments (``call``, ``candidates``, ``recorded``, ...)
as the events' stats.

:func:`trace.load` keeps only the benchmark's ``bench.*`` spans; this
module reads the program's from the same file, once per run, whichever
metric reader asks first, clipped to the traced window.  A program that
records no such spans gives an empty list, and every reader of them then
finds nothing (None).
"""

from __future__ import annotations

import dataclasses
import glob
import os

from bench.harness import runner
from bench.harness import trace as tracemod

PREFIXES = ("memsim.", "planner.")


@dataclasses.dataclass(frozen=True)
class Span(tracemod.Event):
    #: the host plane and line (thread) it was recorded on
    line: str = ""
    #: its keyword arguments, as the trace's event stats give them
    args: dict = dataclasses.field(default_factory=dict)


def load(directory) -> list:
    """The program's spans in the newest ``.xplane.pb`` under
    ``directory``, unclipped; [] when there is no such file."""
    files = glob.glob(os.path.join(str(directory), "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return []
    from jax.profiler import ProfileData
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            out.extend(Span(e.name, int(e.start_ns), int(e.duration_ns),
                            line=f"{plane.name}#{i}", args=dict(e.stats))
                       for e in line.events if e.name.startswith(PREFIXES))
    return out


def clip(spans, lo: int, hi: int) -> list:
    """The spans that overlap ``[lo, hi)``, cut to it."""
    out = []
    for s in spans:
        a, b = max(s.start_ns, lo), min(s.end_ns, hi)
        if b > a:
            out.append(dataclasses.replace(s, start_ns=a, dur_ns=b - a))
    return out


_last: list = [None, []]


def of_run(run) -> list:
    """``run``'s program spans, clipped to its traced window: [] without
    a trace.  Read from the directory the runner traced into."""
    if run.trace is None:
        return []
    if _last[0] is not run:
        lo, hi = run.window_ns
        _last[:] = [run, clip(load(runner.CACHE / "trace" / run.cell.name),
                              lo, hi)]
    return _last[1]


def named(spans, prefix: str) -> list:
    return [s for s in spans if s.name.startswith(prefix)]


def overlap_ns(a, b) -> int:
    """Length of the intersection of two merged interval lists."""
    out, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        out += max(hi - lo, 0)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def per_job_ms(run, ns):
    """``ns`` as ms per traced job."""
    return ns * 1e-6 / run.traced_jobs
