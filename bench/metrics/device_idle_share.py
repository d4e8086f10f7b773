"""``device_idle_share``: one minus the union of op intervals over the
traced window, averaged over the cell's devices, in percent."""

from bench.harness import trace as tracemod


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.window_ns
    busy = tracemod.device_busy(run.trace, lo, hi)
    names = sorted(busy)[:run.cell.chips]
    if not names:
        return None
    return 100.0 * (1.0 - sum(busy[n] for n in names) / len(names)
                    / (hi - lo))
