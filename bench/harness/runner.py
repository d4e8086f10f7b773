"""One run of one cell: set up, warm the cell's own job, measure a closed
loop of whole jobs for ``seconds``, check what the window produced
against the plain reference, and build the result line.

Jobs run back to back, one at a time; the window ends at the first job
boundary at or after ``seconds``.  Job ``j`` of a run draws its inputs
from :func:`job_seed` of the run's seed, so no two jobs of a window
repeat one another's work.
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
import shutil
import sys
import time

import numpy as np

from bench.harness import spec as specmod
from bench.harness import trace as tracemod
from bench.harness.des_probe import DesProbe

CACHE = specmod.BENCH / ".cache"
#: A traced run traces whole jobs from the start of its window until
#: this many seconds have passed (at least one job), then runs the rest
#: of the window untraced: a trace of a narrow cell's whole window would
#: hold millions of op events.
TRACE_SECONDS = 10.0
#: Compile events: an XLA compile, or a program read from the
#: persistent cache, both of which set-up must have done already.
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_hits")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def job_seed(seed: int, index: int) -> int:
    """The seed of job ``index`` of a run (the warm-up job is -1)."""
    h = hashlib.sha256(f"{int(seed)}:{int(index)}".encode()).digest()
    return int.from_bytes(h[:4], "little") & 0x7FFFFFFF


def sample_rng(seed: int):
    """The generator that draws a run's sample of answers to compare."""
    return np.random.default_rng(int(seed) & ((1 << 64) - 1))


@dataclasses.dataclass(frozen=True)
class Check:
    """One number compared with the plain reference, and its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Run:
    """What a metric reader may read about one run."""

    cell: specmod.Cell
    setup_s: float
    jobs: int
    window_s: float
    traced_jobs: int
    job_seconds: list
    des_calls: list
    device_kind: str
    trace: tracemod.Trace | None = None
    window_ns: tuple | None = None


def prepare_environment() -> None:
    """Put the program on the path, fix JAX's persistent compilation
    cache at one path inside the checkout (the program takes it from
    ``JAX_COMPILATION_CACHE_DIR``), cache every program however quickly
    it compiled, and keep libtpu's logs out of fixed paths.  Runs before
    the first compile."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE / "jax")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    src = str(specmod.ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def _compile_counter(jax):
    counts = {"n": 0}

    def on_event(name, *args, **kwargs):
        if name in COMPILE_EVENTS:
            counts["n"] += 1

    counts["on_event"] = on_event
    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    return counts


def _trace_counts():
    from repro.core import cpu_model, designer, memsim
    return dict(sim=memsim.sim_trace_count(),
                designer=designer.designer_trace_count(),
                solve=cpu_model.solve_trace_count())


def run_cell(cell: specmod.Cell, seed: int, seconds: float, traced: bool,
             *, require_chip: bool = True, log=sys.stderr) -> dict:
    """Run ``cell`` once; returns the result line as a dict.

    ``require_chip=False`` is for the tests, which drive the rest of a
    run on the CPU at a tiny size.
    """
    t_start = time.perf_counter()
    import jax
    devs = jax.devices()
    dev = devs[0]
    if require_chip and dev.platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found platform {dev.platform!r} "
                     f"({dev.device_kind})")
    if len(devs) < cell.chips:
        raise NoChip(f"cell {cell.name} needs {cell.chips} chips, JAX "
                     f"found {len(devs)}")
    from repro.core import memsim, shardsim
    probe = DesProbe(memsim, shardsim).install()
    try:
        job = specmod.job_module(cell.traffic["job"],
                                 cell.root).make_job(cell)
        job.setup(probe)
        with jax.profiler.TraceAnnotation("bench.warmup"):
            job.run(job_seed(seed, -1))
        probe.take()
        setup_s = time.perf_counter() - t_start
        compiles = _compile_counter(jax)

        trace_dir = CACHE / "trace" / cell.name
        answers, job_seconds = [], []

        def one_job():
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.job"):
                answers.append(job.run(job_seed(seed, len(answers))))
            job_seconds.append(time.perf_counter() - t0)

        before, n_before = _trace_counts(), compiles["n"]
        traced_jobs = 0
        w0 = time.perf_counter()
        if traced:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(trace_dir))
            with jax.profiler.TraceAnnotation(tracemod.WINDOW_SPAN):
                while True:
                    one_job()
                    if (time.perf_counter() - w0
                            >= min(seconds, TRACE_SECONDS)):
                        break
            jax.profiler.stop_trace()
            traced_jobs = len(answers)
        while not answers or time.perf_counter() - w0 < seconds:
            one_job()
        window_s = time.perf_counter() - w0
        after, n_after = _trace_counts(), compiles["n"]
        rise = {k: after[k] - before[k] for k in after}
        print(f"job seconds: min {min(job_seconds)} median "
              f"{float(np.median(job_seconds))} max {max(job_seconds)}",
              file=log, flush=True)
        print(f"window: {len(answers)} jobs in {window_s} s; traces "
              f"during the window: sim +{rise['sim']}, designer "
              f"+{rise['designer']}, solve +{rise['solve']}; compile "
              f"events +{n_after - n_before}", file=log, flush=True)
        if any(rise.values()) or n_after != n_before:
            raise RuntimeError("something compiled inside the measured "
                               "window: set-up did not warm every shape")
        used = devs[:cell.chips]
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in used]
        des_calls = probe.take()
    finally:
        probe.uninstall()

    jax.monitoring.unregister_event_listener(compiles["on_event"])
    jax.monitoring.unregister_event_duration_listener(compiles["on_event"])
    job.release()
    t0 = time.perf_counter()
    checks = job.check(answers, seed, cell.limits)
    correct = all(c.ok for c in checks)
    print(f"reference comparison took {time.perf_counter() - t0} s",
          file=log, flush=True)

    run = Run(cell=cell, setup_s=setup_s, jobs=len(answers),
              window_s=window_s, traced_jobs=traced_jobs,
              job_seconds=job_seconds,
              des_calls=des_calls, device_kind=dev.device_kind)
    device = dict(platform=dev.platform, kind=dev.device_kind,
                  count=len(devs), memory_peak_bytes=int(max(peaks)))
    out = dict(correct=correct, attempted=len(answers),
               failed=sum(not job.finite(a) for a in answers))
    if traced:
        run.trace = tracemod.load(str(trace_dir))
        if not run.trace.devices:
            raise RuntimeError("the trace holds no device planes")
        lo, hi = run.window_ns = tracemod.window_of(run.trace)
        names = sorted(run.trace.devices)[:cell.chips]
        busy = tracemod.device_busy(run.trace, lo, hi)
        device.update(busy_s=sum(busy[n] for n in names) / len(names)
                      * 1e-9, window_s=(hi - lo) * 1e-9)
        top = tracemod.busiest(run.trace, lo, hi)
        out["breakdown"] = dict(
            device_ops=tracemod.top_modules(run.trace, lo, hi, top),
            idle_gaps=tracemod.idle_gaps(run.trace, lo, hi, top))
        metrics = cell.per_layer
    else:
        metrics = cell.end_to_end
    values = {}
    for m in metrics:
        v = specmod.metric_reader(m["name"], cell.root)(run)
        if v is not None:
            values[m["name"]] = dict(value=v, unit=m["unit"])
    out["metrics"] = values
    out["device"] = device
    for c in checks:
        print(f"check {c.name}: {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAIL'}", file=log, flush=True)
    out["checks"] = {c.name: dict(value=c.value, limit=c.limit)
                     for c in checks}
    return out
