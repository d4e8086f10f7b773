"""Per-kernel validation: shape/dtype sweeps + hypothesis property tests,
all against the pure-jnp oracles in kernels/ref.py (interpret=True on CPU).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.decode_attn import decode_attn
from repro.kernels.rwkv_wkv import wkv
from repro.kernels.stream import (stream_add, stream_copy, stream_scale,
                                  stream_triad)

jax.config.update("jax_platform_name", "cpu")


def _rand(seed, shape, dtype=jnp.float32):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
    return x.astype(dtype)


# ---------------------------------------------------------------------------
# STREAM
# ---------------------------------------------------------------------------

STREAM_SHAPES = [(128, 128), (512, 256), (1024, 384), (2048, 128)]
STREAM_DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("shape", STREAM_SHAPES)
@pytest.mark.parametrize("dtype", STREAM_DTYPES)
class TestStream:
    def test_copy(self, shape, dtype):
        a = _rand(0, shape, dtype)
        np.testing.assert_array_equal(
            np.asarray(stream_copy(a, interpret=True)), np.asarray(a))

    def test_scale(self, shape, dtype):
        a = _rand(1, shape, dtype)
        out = stream_scale(a, 2.5, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32),
            np.asarray(ref.stream_scale_ref(a, jnp.asarray(2.5, dtype)),
                       np.float32), rtol=1e-2 if dtype == jnp.bfloat16
            else 1e-5, atol=1e-5)

    def test_add(self, shape, dtype):
        a, b = _rand(2, shape, dtype), _rand(3, shape, dtype)
        np.testing.assert_array_equal(
            np.asarray(stream_add(a, b, interpret=True)),
            np.asarray(ref.stream_add_ref(a, b)))

    def test_triad(self, shape, dtype):
        a, b = _rand(4, shape, dtype), _rand(5, shape, dtype)
        out = stream_triad(a, b, 2.5, interpret=True)
        want = ref.stream_triad_ref(a, b, jnp.asarray(2.5, dtype))
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=1e-2 if dtype == jnp.bfloat16
                                   else 1e-5, atol=1e-5)


def test_stream_non_divisible_rows():
    """Grid must cover shapes that do not divide the block size."""
    a = _rand(0, (300, 128))
    np.testing.assert_array_equal(
        np.asarray(stream_copy(a, interpret=True)), np.asarray(a))


# ---------------------------------------------------------------------------
# Flash-decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("hq,hk", [(8, 8), (8, 2), (12, 2), (4, 1)])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("s", [512, 1024, 1536])
def test_decode_attn_sweep(hq, hk, d, s):
    b = 2
    q = _rand(0, (b, hq, d))
    k = _rand(1, (b, hk, s, d))
    v = _rand(2, (b, hk, s, d))
    length = jnp.array(s - 100, jnp.int32)
    out = decode_attn(q, k, v, length, block_s=512, interpret=True)
    want = ref.decode_attn_ref(q, k, v, length)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attn_dtypes(dtype):
    b, hq, hk, d, s = 1, 4, 2, 64, 512
    q, k, v = (_rand(i, shp, dtype) for i, shp in
               enumerate([(b, hq, d), (b, hk, s, d), (b, hk, s, d)]))
    length = jnp.array(s, jnp.int32)
    out = decode_attn(q, k, v, length, interpret=True)
    want = ref.decode_attn_ref(q, k, v, length)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=3e-2, rtol=3e-2)


@settings(max_examples=15, deadline=None)
@given(
    s=st.integers(1, 16),
    frac=st.floats(0.1, 1.0),
    g=st.sampled_from([1, 2, 4]),
)
def test_decode_attn_property_length_invariance(s, frac, g):
    """Property: entries beyond `length` never influence the output."""
    b, hk, d = 1, 2, 64
    seq = 128 * s
    length = jnp.array(max(int(seq * frac), 1), jnp.int32)
    q = _rand(0, (b, hk * g, d))
    k = _rand(1, (b, hk, seq, d))
    v = _rand(2, (b, hk, seq, d))
    out1 = decode_attn(q, k, v, length, block_s=128, interpret=True)
    poison = jnp.where(jnp.arange(seq)[None, None, :, None] < length, k, 77.0)
    out2 = decode_attn(q, poison, v, length, block_s=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# RWKV6 WKV
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t", [64, 128, 256])
@pytest.mark.parametrize("h,d", [(2, 32), (4, 64)])
def test_wkv_sweep(t, h, d):
    b = 2
    r, k, v = (_rand(i, (b, h, t, d)) for i in range(3))
    w = jax.nn.sigmoid(_rand(3, (b, h, t, d))) * 0.5 + 0.5  # decays in (0.5,1)
    u = _rand(4, (h, d))
    s0 = _rand(5, (b, h, d, d))
    y, s = wkv(r, k, v, w, u, s0, block_t=64, interpret=True)
    y_ref, s_ref = ref.wkv_ref(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), atol=1e-4,
                               rtol=1e-4)


def test_wkv_state_chaining():
    """wkv(T) == wkv(T/2) chained twice (state carry is exact)."""
    b, t, h, d = 1, 128, 2, 32
    r, k, v = (_rand(i, (b, h, t, d)) for i in range(3))
    w = jax.nn.sigmoid(_rand(3, (b, h, t, d))) * 0.4 + 0.6
    u = _rand(4, (h, d))
    s0 = jnp.zeros((b, h, d, d), jnp.float32)
    y_full, s_full = wkv(r, k, v, w, u, s0, block_t=64, interpret=True)
    half = t // 2
    y1, s1 = wkv(r[:, :, :half], k[:, :, :half], v[:, :, :half],
                 w[:, :, :half], u, s0, block_t=64, interpret=True)
    y2, s2 = wkv(r[:, :, half:], k[:, :, half:], v[:, :, half:],
                 w[:, :, half:], u, s1, block_t=64, interpret=True)
    np.testing.assert_allclose(np.asarray(y_full),
                               np.concatenate([y1, y2], axis=2), atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s_full), np.asarray(s2),
                               atol=1e-4, rtol=1e-4)


@settings(max_examples=10, deadline=None)
@given(decay=st.floats(0.05, 0.99))
def test_wkv_property_uniform_decay(decay):
    """Property: with k=0 the state just decays: S_T = S_0 * decay^T."""
    b, t, h, d = 1, 64, 1, 32
    r = _rand(0, (b, h, t, d))
    k = jnp.zeros((b, h, t, d))
    v = _rand(1, (b, h, t, d))
    w = jnp.full((b, h, t, d), decay)
    u = jnp.zeros((h, d))
    s0 = _rand(2, (b, h, d, d))
    _, s = wkv(r, k, v, w, u, s0, block_t=64, interpret=True)
    want = np.asarray(s0) * decay ** t
    np.testing.assert_allclose(np.asarray(s), want, atol=1e-5, rtol=1e-3)
