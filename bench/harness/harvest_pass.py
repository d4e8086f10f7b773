"""The event engine's harvest pass on the device trace, and the bytes it
must move.

Its modules are ``memsim._event_harvest_tabs`` (the per-lane lent /
reclaimed window table, once per DES call) and
``memsim._event_harvest_scale`` (once per chunk, between stage A and
stage B), matched on the "XLA Modules" line as
``bench/harness/stages.py`` matches the stages.  The program's
``memsim.simulate`` span says whether a call ran the pass (``harvest``)
and how long its window table is (``windows``).
"""

from __future__ import annotations

from bench.harness import program_spans as ps

MODULES = r"jit__event_harvest_(tabs|scale)\("


def call_bytes(lanes: int, chunks: int, chunk: int, windows: int) -> int:
    """Bytes the harvest pass of one DES call must move on the device
    that holds its ``lanes`` lanes: per request per chunk it reads the
    gap and the service and writes the service back (f32); per lane per
    chunk it reads the first arrival, the service scale and the window
    table (f32); per lane once a call it writes the window table."""
    per_request = 4 + 4 + 4
    per_lane_chunk = 4 + 4 + 4 * windows
    return (chunks * lanes * (chunk * per_request + per_lane_chunk)
            + lanes * 4 * windows)


def harvesting_calls(run) -> list:
    """The ``memsim.simulate`` spans of ``run``'s traced window whose
    call ran the harvest pass; [] without a trace or without the span's
    ``harvest`` argument."""
    return [s for s in ps.named(ps.of_run(run), "memsim.simulate")
            if int(s.args.get("harvest", 0)) == 1]
