"""Readings behind a cell's limits: the numbers its comparison gives on
sound runs of the program and on the control, seed by seed.

    python3 bench/readings.py --workload lut_build.ddr5_4800_paper \\
        --seeds 12 --control-seeds 3

One process: set-up and one warm job as in a run, then per seed one job
of the program at the cell's own size, checked against the plain
reference; for the first ``--control-seeds`` seeds also the control (the
reference in bfloat16 in the program's place).  Prints one JSON line per
reading.  The benchmark's own runs never run this; it is how the limits
in ``bench/cells/`` were set (``PERF.md`` gives the readings).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 101)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench.harness import runner, spec
    runner.prepare_environment()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"readings: needs a TPU, found {dev.platform!r}",
              file=sys.stderr)
        return 1
    cell = spec.load_cell(args.workload)
    from repro.core import memsim, shardsim
    from bench.harness.des_probe import DesProbe
    job = spec.job_module(cell.traffic["job"]).make_job(cell)
    probe = DesProbe(memsim, shardsim).install()
    answers = []
    try:
        job.setup(probe)
        job.run(runner.job_seed(args.first_seed, -1))
        for i in range(args.seeds):
            seed = args.first_seed + i
            t0 = time.perf_counter()
            answers.append((seed, job.run(runner.job_seed(seed, 0))))
            print(json.dumps(dict(kind="job", seed=seed,
                                  seconds=time.perf_counter() - t0)),
                  flush=True)
    finally:
        probe.uninstall()
    job.release()
    for i, (seed, answer) in enumerate(answers):
        for kind, fn in (("program", job.check), ("control", job.control)):
            if kind == "control" and i >= args.control_seeds:
                continue
            t0 = time.perf_counter()
            checks = fn([answer], seed, cell.limits)
            print(json.dumps(dict(
                kind=kind, seed=seed, seconds=time.perf_counter() - t0,
                device=dev.device_kind,
                values={c.name: c.value for c in checks})), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
