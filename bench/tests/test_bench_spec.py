"""Name-based discovery: every piece of every cell is found from
``BENCHMARK.json`` by name, and a new cell, job kind and metric need only
new files and a new entry."""

import json
import shutil
import textwrap

from bench.harness import runner, spec


def _benchmark():
    return spec.load_json(spec.ROOT / "BENCHMARK.json")


def test_every_cell_resolves_to_its_files():
    b = _benchmark()
    for w in b["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.chips == w["chips"]
        mod = spec.job_module(cell.traffic["job"])
        assert callable(mod.make_job)
        job = mod.make_job(cell)
        for attr in ("setup", "run", "release", "finite", "check",
                     "control"):
            assert callable(getattr(job, attr))
        for m in cell.end_to_end + cell.per_layer:
            assert callable(spec.metric_reader(m["name"]))
        assert cell.limits, f"{w['name']} has no limits"


def test_every_metric_names_a_layer_and_what_it_moves():
    b = _benchmark()
    e2e = {m["name"] for m in b["end_to_end"]}
    cells = {w["name"] for w in b["workloads"]}
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= cells
    assert "setup_s" in e2e


TOY_JOB = textwrap.dedent('''
    from bench.harness.runner import Check

    class Toy:
        def __init__(self, cell):
            self.n = cell.config["n"]
        def setup(self, probe):
            pass
        def run(self, seed):
            return dict(seed=seed, total=sum(range(self.n)))
        def release(self):
            pass
        @staticmethod
        def finite(answer):
            return True
        def check(self, answers, seed, limits):
            gap = max(abs(a["total"] - self.n * (self.n - 1) // 2)
                      for a in answers)
            return [Check("total_gap", float(gap), limits["total_gap"])]
        def control(self, answers, seed, limits):
            return self.check([dict(total=-1)], seed, limits)

    def make_job(cell):
        return Toy(cell)
''')

TOY_METRIC = textwrap.dedent('''
    def read(run):
        return float(run.jobs)
''')


def test_a_new_cell_job_kind_and_metric_are_new_files_only(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    before = {p.relative_to(tmp_path): p.read_bytes()
              for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    bench = tmp_path / "bench"
    (bench / "configs" / "toy.json").write_text(json.dumps({"n": 10}))
    (bench / "traffic" / "toy_mix.json").write_text(
        json.dumps({"job": "toy", "args": {}}))
    (bench / "cells" / "toy.cell.json").write_text(
        json.dumps({"limits": {"total_gap": 0.0}}))
    (bench / "jobs" / "toy.py").write_text(TOY_JOB)
    (bench / "metrics" / "toy_jobs.py").write_text(TOY_METRIC)
    b = json.loads((tmp_path / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "toy", "source": "a test",
                         "file": "bench/configs/toy.json", "reduced": [],
                         "why": "a test"})
    b["workloads"].append({"name": "toy.cell", "config": "toy",
                           "traffic": "toy_mix", "chips": 1,
                           "why": "a test"})
    b["per_layer"].append({"name": "toy_jobs", "unit": "1",
                           "better": "higher", "source": "host_clock",
                           "layer": "toy", "moves": "job_s",
                           "workloads": ["toy.cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))

    cell = spec.load_cell("toy.cell", root=tmp_path)
    out = runner.run_cell(cell, 7, 0.01, False, require_chip=False)
    assert out["correct"] is True
    assert out["checks"] == {"total_gap": {"value": 0.0, "limit": 0.0}}
    assert set(out["metrics"]) == {"job_s", "setup_s"}
    run = runner.Run(cell=cell, setup_s=0.0, jobs=3, window_s=1.0,
                     traced_jobs=0, job_seconds=[], des_calls=[],
                     device_kind="cpu")
    assert spec.metric_reader("toy_jobs", tmp_path)(run) == 3.0
    after = {p.relative_to(tmp_path): p.read_bytes()
             for p in (tmp_path / "bench").rglob("*") if p.is_file()
             and "__pycache__" not in p.parts}
    assert all(after[k] == v for k, v in before.items()
               if "__pycache__" not in k.parts)


def test_job_seeds_are_fixed_by_the_run_seed_and_large_seeds_work():
    big = 2 ** 31 + 12345
    seeds = [runner.job_seed(big, j) for j in range(-1, 5)]
    assert seeds == [runner.job_seed(big, j) for j in range(-1, 5)]
    assert len(set(seeds)) == len(seeds)
    assert all(0 <= s < 2 ** 31 for s in seeds)
