"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

Each ``*_ref`` mirrors the semantics of its kernel exactly; kernel tests
sweep shapes/dtypes and assert_allclose against these (interpret=True on
CPU, compiled on real TPU).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


# --- STREAM (paper §5 workloads: copy/scale/add/triad) ---------------------

def stream_copy_ref(a):
    return a + 0  # materialize a copy


def stream_scale_ref(a, alpha):
    return alpha * a


def stream_add_ref(a, b):
    return a + b


def stream_triad_ref(a, b, alpha):
    return a + alpha * b


# --- GQA flash-decode attention --------------------------------------------

def decode_attn_ref(q, k, v, length):
    """q: (B, Hq, D); k/v: (B, Hk, S, D); length: () valid prefix length.

    Returns (B, Hq, D): softmax(q k^T / sqrt(D)) v over the valid prefix,
    with GQA head grouping (Hq = G * Hk).
    """
    b, hq, d = q.shape
    hk, s = k.shape[1], k.shape[2]
    g = hq // hk
    qg = q.reshape(b, hk, g, d)
    logits = jnp.einsum("bhgd,bhsd->bhgs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * (d ** -0.5)
    mask = jnp.arange(s)[None, None, None, :] < length
    logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgs,bhsd->bhgd", p, v.astype(jnp.float32))
    return out.reshape(b, hq, d).astype(q.dtype)


# --- RWKV6 WKV recurrence ---------------------------------------------------

def wkv_ref(r, k, v, w, u, state):
    """r/k/v/w: (B, H, T, D); u: (H, D); state: (B, H, D, D) fp32.

    y_t = r_t . (S_{t-1} + u * k_t^T v_t);  S_t = diag(w_t) S_{t-1} + k_t^T v_t
    Returns (y (B, H, T, D), final state).
    """
    f32 = jnp.float32
    r, k, v, w = (t.astype(f32) for t in (r, k, v, w))

    def step(s, inp):
        rt, kt, vt, wt = inp
        a = jnp.einsum("bhk,bhv->bhkv", kt, vt)
        y = jnp.einsum("bhk,bhkv->bhv", rt, s + u[None, :, :, None] * a)
        s = s * wt[..., None] + a
        return s, y

    xs = tuple(t.transpose(2, 0, 1, 3) for t in (r, k, v, w))
    state, ys = jax.lax.scan(step, state.astype(f32), xs)
    return ys.transpose(1, 2, 0, 3), state
