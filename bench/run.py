"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload lut_build.ddr5_4800_paper \\
        --seed 1234 --seconds 40 --trace 0

Run from the root of a checkout.  The cell (configuration, traffic,
chips, metrics) is looked up by name in ``BENCHMARK.json``; see
``bench/harness/spec.py`` for where each piece lives.  The last line of
standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each number compared with the plain
reference beside its limit, which also close standard error).  With
``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.

Exits non-zero with no result line when JAX finds no TPU or fewer chips
than the cell asks for, when ``REPRO_DES_STEPS`` is set (it would shrink
every DES budget), or when anything compiles inside the window.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if os.environ.get("REPRO_DES_STEPS"):
        print("bench: REPRO_DES_STEPS is set; it would cap every DES "
              "budget of the cell, so no run is made", file=sys.stderr)
        return 2
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from bench.harness import runner, spec
    runner.prepare_environment()
    cell = spec.load_cell(args.workload)
    try:
        out = runner.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace))
    except runner.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
