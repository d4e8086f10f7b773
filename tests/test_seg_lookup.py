"""The event engine's two ways of each lookup give the same integers.

``memsim._event_arrivals`` finds each request's MMPP segment, and
``memsim._event_harvest_scale`` each arrival's lent/reclaimed window,
with ``jax.lax.platform_dependent``: a binary search on the CPU
(``_seg_bsearch``, ``_window_bsearch``), a fused compare-count elsewhere
(``_seg_count``, ``_count_below``, which both share).  These tests run
the branch functions directly, on random sorted rows at reduced
``lut_build``, ``plan`` and harvest shapes, and stage A and the harvest
pass themselves with the count branch forced in place of the CPU's.
"""

import hashlib

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


#: The harvest cell's chunk and window table at 48 of its 16,128 lanes:
#: (lanes, chunk, windows).
HARVEST_SHAPE = (48, 1024, 896)


def _windows(case, seed):
    """Sorted arrival times ``t`` (C, n), whole numbers of ns as the
    lattice makes them, and sorted window boundaries ``b`` (n, M),
    planted per ``case``."""
    n, c, m = HARVEST_SHAPE
    rng = np.random.default_rng(seed)
    b = np.cumsum(rng.exponential(2000.0, size=(n, m)), 1)
    top = b[:, -1:].copy()
    if case == "ties":
        # Whole-ns boundaries, a run of them repeated, and every fourth
        # arrival on a boundary exactly.
        b = np.ceil(b)
        b[:, 5:9] = b[:, 5:6]
        t = np.ceil(rng.uniform(0.0, top, (n, c)))
        t[:, ::4] = np.take_along_axis(b, rng.integers(0, m, (n, c // 4)), 1)
    elif case == "duty0":
        # Every other lane lends nothing: its boundaries are all inf.
        b[::2] = np.inf
        t = np.ceil(rng.uniform(0.0, top, (n, c)))
    elif case == "past_last":
        # The whole chunk lies past each lane's last boundary.
        t = np.ceil(top) + np.cumsum(rng.integers(1, 20, (n, c)), 1)
    elif case == "one_window":
        # Each lane's whole chunk inside one window, widened to 5 us.
        j = rng.integers(0, m - 1, n)[:, None]
        b += np.where(np.arange(m) > j, 5000.0, 0.0)
        lo = np.floor(np.take_along_axis(b, j, 1)) + 1.0
        hi = np.ceil(np.take_along_axis(b, j + 1, 1)) - 1.0
        t = np.floor(rng.uniform(lo, hi, (n, c)))
    else:
        t = np.ceil(rng.uniform(0.0, top * 1.05, (n, c)))
    return (np.sort(t, 1).T.astype(np.float32), b.astype(np.float32))


@pytest.mark.parametrize("case", ["random", "ties", "duty0", "past_last",
                                  "one_window"])
def test_count_below_equals_searchsorted(case):
    t, b = _windows(case, seed=len(case))
    got = np.asarray(jax.jit(memsim._count_below)(t, b))
    want = np.asarray(jax.jit(memsim._window_bsearch)(t, b))
    assert got.dtype == want.dtype == np.int32
    assert got.shape == t.shape
    np.testing.assert_array_equal(got, want)
    # Both are the number of boundaries strictly below each arrival.
    np.testing.assert_array_equal(
        got, (b.T[None, :, :] < t[:, None, :]).sum(1))
    if case == "duty0":
        assert (got[:, ::2] == 0).all() and got[:, 1::2].any()
    if case == "past_last":
        assert (got == b.shape[1]).all()
    if case == "one_window":
        assert (got == got[:1]).all()


#: test_spans.py's harvesting batch: three duties at the reference lent
#: bandwidth and one lane that lends nothing.
HARVEST = [memsim.ChannelConfig(rho=r, kappa=k, harvest_duty=d,
                                harvest_bw_gbps=38.4)
           for r, k, d in ((0.3, 1.0, 0.25), (0.7, 2.0, 0.5),
                           (0.9, 1.6, 0.75), (0.6, 1.0, 0.0))]


def _harvest_batch(monkeypatch):
    """The histogram sha256, the ``lent`` counter and each chunk's
    harvest-pass outputs of test_spans.py's harvesting call, through a
    freshly traced pass."""
    passes = []
    scale = jax.jit(lambda *a: memsim._event_harvest_scale(*a))

    def keep(*args):
        out = scale(*args)
        passes.append(jax.tree_util.tree_map(np.asarray, out))
        return out

    monkeypatch.setattr(memsim, "_event_harvest_scale_jit", keep)
    st = memsim.simulate_cells(memsim.stack_channels(HARVEST), steps=8000,
                               engine="event", chunk=1024, seed=5)
    sha = hashlib.sha256(np.ascontiguousarray(st.hist, np.float64)
                         .tobytes()).hexdigest()
    return sha, memsim._CALL["lent"], passes


def test_harvest_pass_is_bitwise_equal_with_the_count_forced(monkeypatch):
    cpu = _harvest_batch(monkeypatch)
    monkeypatch.setattr(memsim, "_window_bsearch", memsim._count_below)
    forced = _harvest_batch(monkeypatch)
    assert forced[:2] == cpu[:2]
    assert len(forced[2]) == len(cpu[2]) > 1
    for (svc_a, lent_a), (svc_b, lent_b) in zip(cpu[2], forced[2]):
        assert svc_a.tobytes() == svc_b.tobytes()
        np.testing.assert_array_equal(lent_a, lent_b)
    # The pass engaged: some slots were lent, none on the duty-0 lane.
    lent = cpu[2][-1][1]
    assert 0 < cpu[1] == lent.sum() and lent[3] == 0
