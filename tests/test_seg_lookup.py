"""The event engine's two stage-A segment lookups give the same integers.

``memsim._event_arrivals`` finds each request's MMPP segment with
``jax.lax.platform_dependent``: a binary search plus staircase on the CPU
(``_seg_bsearch``), a fused compare-count elsewhere (``_seg_count``).
These tests run both branch functions directly, on random sorted rows at
reduced ``lut_build`` and ``plan`` shapes, and stage A itself with the
count branch forced in place of the CPU's.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import memsim

#: (lanes, chunk, M+1): the lut_build cell's chunk and table at 48 of its
#: 4,032 lanes, and the plan cell's at 12 of its 320.
SHAPES = {"lut_build": (48, 1024, 897), "plan": (12, 8192, 513)}


def _rows(shape, case, seed):
    """Sorted boundary rows ``L`` (n, M+1), starting at 0, and sorted
    queries ``u`` (n, C) above 0, planted per ``case``."""
    n, c, m1 = shape
    rng = np.random.default_rng(seed)
    L = np.concatenate([np.zeros((n, 1)),
                        np.cumsum(rng.exponential(size=(n, m1 - 1)), 1)], 1)
    top = L[:, -1:]
    if case == "ties":
        # Every fourth query equals a boundary exactly; a run of
        # boundaries repeats (a zero-length sojourn).
        L[:, 5:9] = L[:, 5:6]
        u = rng.uniform(0.0, top, (n, c))
        u[:, ::4] = np.take_along_axis(
            L, rng.integers(0, m1, (n, c // 4)), 1)
    elif case == "tail":
        # The last third of each chunk lies past the sampled chain, in
        # the final, infinite segment.
        k = c // 3
        u = rng.uniform(0.0, top, (n, c))
        u[:, -k:] = top + rng.exponential(size=(n, k)) + 1.0
    elif case == "one_segment":
        # Each lane's whole chunk inside one segment.
        L = L.astype(np.float32)
        j = rng.integers(0, m1 - 1, n)[:, None]
        lo, hi = np.take_along_axis(L, j, 1), np.take_along_axis(L, j + 1, 1)
        u = rng.uniform(lo, hi, (n, c)).astype(np.float32)
        u = np.clip(u, np.nextafter(lo, np.inf), np.nextafter(hi, -np.inf))
    else:
        u = rng.uniform(0.0, top * 1.05, (n, c))
    return (np.sort(u, 1).astype(np.float32), L.astype(np.float32))


@pytest.mark.parametrize("case", ["random", "ties", "tail", "one_segment"])
@pytest.mark.parametrize("shape", list(SHAPES), ids=list(SHAPES))
def test_count_equals_bsearch(shape, case):
    u, L = _rows(SHAPES[shape], case, seed=len(case))
    got = jax.jit(memsim._seg_count)(u, L)
    want = jax.jit(memsim._seg_bsearch)(u, L)
    assert got.dtype == want.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # Both are the last boundary strictly below each request.
    below = (L[:, None, :] < u[:, :, None]).sum(-1) - 1
    np.testing.assert_array_equal(np.asarray(got), below)
    if case == "tail":
        assert (np.asarray(got)[:, -1] == L.shape[1] - 1).all()
    if case == "one_segment":
        assert (np.asarray(got) == np.asarray(got)[:, :1]).all()


def _stage_a_chunks(n_chunks=2, chunk=256):
    """Stage A's outputs for ``n_chunks`` chunks of a small batch, the
    carry threaded through, under a freshly traced executable."""
    cfgs = [memsim.ChannelConfig(rho=r, kappa=k)
            for r, k in ((0.05, 1.0), (0.3, 2.0), (0.6, 1.0), (0.85, 3.0),
                         (0.95, 2.0), (0.5, 0.5))]
    cha = memsim.stack_channels(cfgs)
    n = len(cfgs)
    ov = memsim._nan_overrides(n)
    lanes = jnp.arange(n, dtype=jnp.int32)
    phase_key, root = jax.random.split(jax.random.PRNGKey(7))
    tabs = memsim._event_tables_jit(cha, ov, lanes, phase_key,
                                    n_sojourns=64)
    arrivals = jax.jit(lambda *a: memsim._event_arrivals(*a, chunk=chunk))
    state = (jnp.zeros(n), jnp.zeros(n))
    out = []
    for key in jax.random.split(root, n_chunks):
        state, gaps, svc, rec = arrivals(cha, ov, state, lanes, key, tabs,
                                         jnp.float32(100.0))
        out.append((gaps, svc, rec, state[0], state[1]))
    return jax.tree_util.tree_map(np.asarray, out)


def test_stage_a_is_bitwise_equal_with_the_count_forced(monkeypatch):
    cpu = _stage_a_chunks()
    monkeypatch.setattr(memsim, "_seg_bsearch", memsim._seg_count)
    forced = _stage_a_chunks()
    for a, b in zip(jax.tree_util.tree_leaves(cpu),
                    jax.tree_util.tree_leaves(forced)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)
    # The chunks held real arrivals past the warm-up.
    assert all(c[2].any() for c in cpu)
