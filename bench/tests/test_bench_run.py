"""``bench/run.py`` off the chip: no TPU means a non-zero exit and no
result line; a set ``REPRO_DES_STEPS`` refuses to run at all."""

import os
import subprocess
import sys

from bench.harness.spec import ROOT


def _run(env_extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("REPRO_DES_STEPS", "XLA_FLAGS")}
    env.update(JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "plan.stablelm2_decode", "--seed", str(2 ** 31 + 5),
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_non_zero_with_no_result_line():
    p = _run({})
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not any(line.lstrip().startswith("{")
                   for line in p.stdout.splitlines())


def test_a_set_des_step_cap_is_refused():
    p = _run({"REPRO_DES_STEPS": "1000"})
    assert p.returncode == 2
    assert p.stdout == ""
