"""Reduction of a profiler trace to device busy time, per-module device
time and the host's activity in the device's idle gaps.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain :class:`Trace`; everything after that works on the plain form, so
the tests check the arithmetic on small synthetic traces.

A device is busy while one of its XLA modules (compiled programs) runs:
the "XLA Modules" line of its plane.  The per-op line is not read; a
narrow DES traces every iteration of its scans there, millions of events
in a few seconds.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

#: Device planes, and the line on them with one event per XLA module
#: (program) execution.
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
MODULES_LINE = "XLA Modules"
#: Host spans the benchmark writes itself; the traced jobs run inside
#: the window span.
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.traced"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: int
    dur_ns: int

    @property
    def end_ns(self) -> int:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    #: device plane name -> its module executions
    devices: dict
    #: the benchmark's own host spans
    spans: list


def load(directory: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``directory``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    devices, spans = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            devices[plane.name] = [
                Event(e.name, int(e.start_ns), int(e.duration_ns))
                for line in plane.lines if line.name == MODULES_LINE
                for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(Event(e.name, int(e.start_ns),
                                   int(e.duration_ns))
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return Trace(devices=devices, spans=spans)


def window_of(trace: Trace) -> tuple[int, int]:
    """(start, end) ns of the benchmark's traced-window span."""
    win = [s for s in trace.spans if s.name == WINDOW_SPAN]
    if not win:
        raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
    w = max(win, key=lambda s: s.dur_ns)
    return w.start_ns, w.end_ns


def merged(events, lo: int, hi: int) -> list[tuple[int, int]]:
    """Union of the events' intervals, clipped to ``[lo, hi)``."""
    out: list[list[int]] = []
    for s, e in sorted((max(ev.start_ns, lo), min(ev.end_ns, hi))
                       for ev in events):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(events, lo: int, hi: int) -> int:
    return sum(e - s for s, e in merged(events, lo, hi))


def device_busy(trace: Trace, lo: int, hi: int) -> dict:
    """Busy ns per device plane: the union of its module intervals."""
    return {name: busy_ns(mods, lo, hi)
            for name, mods in trace.devices.items()}


def module_ns(trace: Trace, lo: int, hi: int, pattern: str) -> dict:
    """Device ns per plane spent in modules whose name matches
    ``pattern`` (a regular expression, matched at the start)."""
    rx = re.compile(pattern)
    return {name: sum(min(ev.end_ns, hi) - max(ev.start_ns, lo)
                      for ev in mods
                      if rx.match(ev.name) and ev.end_ns > lo
                      and ev.start_ns < hi)
            for name, mods in trace.devices.items()}


def busiest(trace: Trace, lo: int, hi: int) -> str:
    busy = device_busy(trace, lo, hi)
    return max(busy, key=busy.get)


def module_name(name: str) -> str:
    """A module event's name without its trailing ``(id)``."""
    return re.sub(r"\(\d+\)$", "", name)


def top_modules(trace: Trace, lo: int, hi: int, device: str,
                n: int = 10) -> list:
    """``[[module, seconds], ...]``: the device's modules that took most
    time in the window."""
    tot: dict[str, int] = {}
    for ev in trace.devices[device]:
        if ev.end_ns > lo and ev.start_ns < hi:
            k = module_name(ev.name)
            tot[k] = tot.get(k, 0) + min(ev.end_ns, hi) - max(ev.start_ns,
                                                                lo)
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in top]


def idle_gaps(trace: Trace, lo: int, hi: int, device: str,
              n: int = 10) -> list:
    """``[[host span, seconds], ...]``: the device's longest idle gaps in
    the window, each named by the innermost benchmark host span that
    covers the gap's midpoint (``idle`` where none does)."""
    busy = merged(trace.devices[device], lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    inner = [s for s in trace.spans if s.name != WINDOW_SPAN]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) // 2
        cover = [sp for sp in inner if sp.start_ns <= mid < sp.end_ns]
        name = (min(cover, key=lambda sp: sp.dur_ns).name if cover
                else "idle")
        out.append([name, (e - s) * 1e-9])
    return out
