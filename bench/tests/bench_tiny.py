"""Tiny versions of the benchmark's cells for the CPU tests, and the
program faults the comparison has to catch."""

import dataclasses
import functools

import numpy as np

from bench.harness import runner, spec

SEED = 2 ** 31 + 977

TINY = {
    "lut_build": dict(steps=12_000, grid=dict(
        rho=[0.05, 0.45, 0.93], kappa=[1.0, 3.2], outstanding=[2.0, 192.0],
        eta=[0.05, 1.0])),
    # Three chunks of 4,096 requests, so each chunk inherits a queue.
    "plan": dict(steps=24_000),
}


def tiny_cell(name: str) -> spec.Cell:
    """The cell with its configuration cut to a test's size; its
    traffic, channel constants and limits stay as they are."""
    cell = spec.load_cell(name)
    config = {**cell.config, **TINY[cell.traffic["job"]]}
    return dataclasses.replace(cell, config=config)


def run(name: str, seconds: float = 0.3) -> dict:
    return runner.run_cell(tiny_cell(name), SEED, seconds, False,
                           require_chip=False)


def answers(cell: spec.Cell, seeds):
    """The cell's job, set up, run once per seed and released; returns
    the job and its answers."""
    from repro.core import memsim, shardsim
    from bench.harness.des_probe import DesProbe
    job = spec.job_module(cell.traffic["job"]).make_job(cell)
    probe = DesProbe(memsim, shardsim).install()
    try:
        job.setup(probe)
        out = [job.run(s) for s in seeds]
    finally:
        probe.uninstall()
    job.release()
    return job, out


def plant(monkeypatch, fault: str) -> None:
    """Break the program's DES underneath the harness:

    - ``state_unchanged``: each stage-B chunk returns the Lindley carry
      it was given, so no chunk inherits the previous one's queue;
    - ``half_batch``: the second half of the lanes is left out, their
      histograms replaced by nothing;
    - ``answer_altered``: the p99 of every eighth lane is moved up one
      4-ns bin where the statistics are produced (the comparison reads
      a sample, so one lane alone would be caught only when drawn);
    - ``verdict_altered``: every verdict of the capacity planner is
      turned over where the planner makes it, so the pick changes too.
    """
    from repro.core import memsim
    # Kernels built while the fault is planted stay in a cache of their
    # own, which goes when the test's monkeypatch is undone.
    monkeypatch.setattr(memsim, "_event_kernel", functools.lru_cache(
        maxsize=None)(memsim._event_kernel.__wrapped__))
    if fault == "state_unchanged":
        inner = memsim._event_chunk_core

        def frozen(terms, W, *args, **kwargs):
            _, flat = inner(terms, W, *args, **kwargs)
            return W, flat

        monkeypatch.setattr(memsim, "_event_chunk_core", frozen)
    elif fault == "half_batch":
        inner = memsim._accumulate_chunks

        def half(dispatch, n_chunks, n):
            hist = inner(dispatch, n_chunks, n)
            hist[n // 2:] = 0.0
            return hist

        monkeypatch.setattr(memsim, "_accumulate_chunks", half)
    elif fault == "answer_altered":
        inner = memsim._stats_from_hist

        def altered(hist):
            st = inner(hist)
            p99 = np.array(st.p99_ns, np.float64)
            p99.reshape(-1, p99.shape[-1])[:, ::8] += memsim.BIN_NS
            return dataclasses.replace(st, p99_ns=p99)

        monkeypatch.setattr(memsim, "_stats_from_hist", altered)
    elif fault == "verdict_altered":
        from repro.serving import capacity
        made = capacity.DesignVerdict

        def turned(**kw):
            return made(**dict(kw, meets_slo=not kw["meets_slo"]))

        monkeypatch.setattr(capacity, "DesignVerdict", turned)
    else:
        raise ValueError(fault)


FAULTS = ("state_unchanged", "half_batch", "answer_altered")
#: Faults of the capacity planner around the DES.
PLAN_FAULTS = ("verdict_altered",)
