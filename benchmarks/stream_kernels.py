"""STREAM Pallas kernels (paper SS5 workloads): interpret-mode correctness
timing + modeled TPU roofline fractions.

On CPU the us_per_call column is interpret-mode overhead (not TPU time);
the derived column reports the bytes each call would move and the fraction
of the 819 GB/s HBM roofline the kernel's access pattern sustains by
construction (pure streaming => 1.0 modeled)."""

import jax
import jax.numpy as jnp

from benchmarks.common import emit, time_call
from repro.core import hw
from repro.kernels import ops
from repro.kernels.stream import stream_bytes


def main():
    shape = (2048, 512)
    a = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32)
    # Compiled kernels on a TPU; the interpreter anywhere else.
    interp = jax.default_backend() != "tpu"
    for name, fn in [
        ("copy", lambda: ops.stream_copy(a, interpret=interp)),
        ("scale", lambda: ops.stream_scale(a, 2.0, interpret=interp)),
        ("add", lambda: ops.stream_add(a, b, interpret=interp)),
        ("triad", lambda: ops.stream_triad(a, b, 2.0, interpret=interp)),
    ]:
        us, _ = time_call(fn, iters=1)
        nbytes = stream_bytes(name, shape)
        t_roof_us = nbytes / hw.TPU_HBM_BW * 1e6
        emit(f"stream.{name}.bytes", us, nbytes)
        emit(f"stream.{name}.tpu_roofline_us", 0.0, f"{t_roof_us:.2f}")


if __name__ == "__main__":
    main()
