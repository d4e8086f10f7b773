"""Lane-axis device parallelism for the DES: a 1-D mesh over host devices.

``memsim``'s flattened ``(cells x reps)`` batch is embarrassingly parallel
-- lanes are independent Markov chains that never exchange data -- so the
device-parallel story is the simplest one ``shard_map`` can tell: build a
1-D :class:`~jax.sharding.Mesh` whose single axis is the **lane axis**,
pad the batch to a multiple of the device count (NaN lanes, the same
masked-override idiom ``memsim`` already uses -- a NaN channel never
records an arrival, so padding lanes park all their histogram mass in the
overflow slot the host drops anyway), and run the *same jitted chunk
kernel* on every device over its lane slice.

This is the ``core/``-side sibling of ``repro.distributed.sharding`` (the
model-parameter rules engine): that module maps *logical tensor axes*
onto a training mesh; this one owns the single ``"lanes"`` axis the DES
needs and stays importable from ``core`` (jax-only, no model deps).

Determinism contract (pinned by ``tests/test_shardsim.py``):

  * every random stream is keyed by the **logical lane index** (threefry
    ``fold_in(chunk_key, lane)``), never by batch width or device count,
    so a lane draws the same uniforms whether it is simulated alone, in a
    wider batch, on one device or on eight;
  * chunk lengths and budgets derive from the UNPADDED flat width, so
    padding (a device-count artifact) cannot perturb them;
  * histogram indices are ``lane * N_BINS + bin`` with *global* lane ids,
    so per-shard emissions concatenate into one flat index space and the
    host's integer ``bincount`` merges them exactly -- counts are small
    integers, exact in any accumulation order.

Together these make the sharded path **bit-identical** to the unsharded
path per cell, which is why ``devices`` can default to an environment
knob (``REPRO_DES_DEVICES``) without perturbing a single pinned test.

Use ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (SNIPPETS
idiom) to split one host CPU into N XLA devices; on real multi-device
hosts the flag is unnecessary.
"""

from __future__ import annotations

import functools
import os

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

#: The single mesh axis name: the flattened (cells x reps [x pad]) axis.
AXIS = "lanes"

#: Environment knob consulted when ``devices=None``: an integer device
#: count, or ``auto`` for every local device.  Unset means 1 (the exact
#: historical single-device path).
ENV_DEVICES = "REPRO_DES_DEVICES"


def resolve_devices(devices=None) -> int:
    """Resolve a ``devices=`` knob to a concrete device count.

    ``None`` consults ``$REPRO_DES_DEVICES`` (unset -> 1); ``"auto"``
    means every local device; an int (or int-like string) is validated
    against the local device count.  Results never depend on the choice
    -- only wall-clock does -- so callers may cache across values.
    """
    if devices is None:
        env = os.environ.get(ENV_DEVICES, "").strip()
        if not env:
            return 1
        devices = env
    if isinstance(devices, str):
        if devices.lower() == "auto":
            return len(jax.devices())
        try:
            devices = int(devices)
        except ValueError:
            raise ValueError(
                f"devices must be an int, 'auto' or None; got {devices!r} "
                f"(via ${ENV_DEVICES}?)") from None
    n = int(devices)
    avail = len(jax.devices())
    if n < 1:
        raise ValueError(f"devices must be >= 1, got {n}")
    if n > avail:
        hint = (f"; force more host devices with XLA_FLAGS="
                f"--xla_force_host_platform_device_count={n}"
                if jax.default_backend() == "cpu" else "")
        raise ValueError(
            f"devices={n} exceeds the {avail} local device(s){hint}")
    return n


def pad_width(n: int, ndev: int) -> int:
    """Lanes to append so ``n`` divides evenly over ``ndev`` devices."""
    return (-int(n)) % int(ndev)


@functools.lru_cache(maxsize=None)
def lane_mesh(ndev: int) -> Mesh:
    """The 1-D lane mesh over the first ``ndev`` local devices."""
    return Mesh(np.array(jax.devices()[:ndev]), (AXIS,))


def lanes(dim: int = 0) -> P:
    """PartitionSpec sharding axis ``dim`` over the lane mesh axis."""
    return P(*((None,) * dim + (AXIS,)))


def replicated() -> P:
    return P()


def jit_lanes(body, ndev: int, in_specs, out_specs):
    """Jit ``body``; for ``ndev > 1`` wrap it in ``shard_map`` first.

    ``in_specs`` / ``out_specs`` are pytree prefixes of the body's args /
    results (a single :func:`lanes` spec covers a whole ``ChannelArrays``
    or state-tuple subtree).  ``ndev == 1`` skips ``shard_map`` entirely:
    the sharded path is bit-identical, but the plain jit is the exact
    historical code path and free of partitioning overhead.  Either way
    the body traces ONCE per compile, so trace-count pins hold.
    """
    if ndev == 1:
        return jax.jit(body)
    return jax.jit(jax.shard_map(body, mesh=lane_mesh(ndev),
                                 in_specs=in_specs, out_specs=out_specs,
                                 check_vma=False))
