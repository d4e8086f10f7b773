"""Find a cell's pieces by name: ``BENCHMARK.json`` at the checkout's
root names each cell's configuration and traffic; everything else is a
file of its own under ``bench/``:

- ``bench/configs/<config>.json``: the deployment as it is run;
- ``bench/traffic/<traffic>.json``: the job kind and its arguments;
- ``bench/cells/<workload>.json``: the limits of the comparisons that
  decide ``correct``;
- ``bench/jobs/<job>.py``: the job kind (``make_job``);
- ``bench/metrics/<metric>.py``: one per-layer metric reader (``read``).

A new cell, job kind or metric is new files plus a ``BENCHMARK.json``
entry; nothing here names one.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    """Everything one run of one workload needs, resolved by name."""

    root: Path             # the checkout the cell was found in
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: tuple      # BENCHMARK.json metric entries of this cell
    per_layer: tuple


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    """Resolve ``workload`` against ``root/BENCHMARK.json``."""
    spec = load_json(root / "BENCHMARK.json")
    entry = next((w for w in spec["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        names = [w["name"] for w in spec["workloads"]]
        raise KeyError(f"no workload {workload!r}; BENCHMARK.json has "
                       f"{names}")
    conf = next(c for c in spec["configs"] if c["name"] == entry["config"])
    bench = root / "bench"
    return Cell(
        root=Path(root), name=workload, chips=int(entry["chips"]),
        config=load_json(root / conf["file"]),
        traffic=load_json(bench / "traffic" / f"{entry['traffic']}.json"),
        limits=load_json(bench / "cells" / f"{workload}.json")["limits"],
        end_to_end=tuple(m for m in spec["end_to_end"]
                         if _reports(m, workload)),
        per_layer=tuple(m for m in spec["per_layer"]
                        if _reports(m, workload)))


def _load(path: Path):
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def job_module(kind: str, root: Path = ROOT):
    """The job kind's module, ``bench/jobs/<kind>.py``."""
    return _load(Path(root) / "bench" / "jobs" / f"{kind}.py")


def metric_reader(name: str, root: Path = ROOT):
    """The per-layer or end-to-end metric's ``read`` function,
    ``bench/metrics/<name>.py``."""
    return _load(Path(root) / "bench" / "metrics" / f"{name}.py").read
