"""GQA flash-decode attention as a Pallas TPU kernel.

This is the per-chip hot loop of the channelized decode path (DESIGN.md §3):
one query token attends a long KV cache; the kernel streams the KV cache
from HBM in (BLOCK_S, D) tiles, maintaining online-softmax running
(max, denom, acc) in VMEM scratch.  Arithmetic intensity is ~2 flops/byte,
so this kernel IS the HBM bandwidth roofline of decode -- tiling exists to
keep the stream DMA-friendly, not to feed the MXU.

Layout: the cache is head-major, (batch, kv_head, seq, head_dim), so a KV
tile's last two dims are (BLOCK_S, D), the shape the TPU compiler's (8, 128)
tiling accepts.  The grid is (batch, kv_head, seq_blocks); the sequence
dimension is innermost so TPU grid iteration carries scratch across KV
tiles.  Each tile serves all G = Hq/Hk query heads of its KV head at once
(the GQA trick: one KV byte feeds G queries, multiplying arithmetic
intensity by G).

In the distributed layout, the cache's sequence axis is sharded over the
``model`` mesh axis; each chip runs this kernel on its local S/N slice and
the (m, l, acc) partials are merged across chips (flash-decode combine) --
COAXIAL's channels, with this kernel as the per-channel controller.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_S = 512
NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref):
    s_idx = pl.program_id(2)
    n_s = pl.num_programs(2)

    @pl.when(s_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)            # (BLOCK_S, D)
    v = v_ref[0, 0].astype(jnp.float32)

    scale = q.shape[-1] ** -0.5
    logits = jnp.dot(q * scale, k.T,
                     preferred_element_type=jnp.float32)   # (G, BLOCK_S)
    positions = s_idx * k.shape[0] + jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, 1)
    logits = jnp.where(positions < len_ref[0], logits, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, logits.max(axis=-1))
    p = jnp.exp(logits - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(s_idx == n_s - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def decode_attn(q, k, v, length, *, block_s: int = BLOCK_S,
                interpret: bool = False):
    """q: (B, Hq, D); k/v: (B, Hk, S, D); length: () int32 -> (B, Hq, D)."""
    b, hq, d = q.shape
    hk, s = k.shape[1], k.shape[2]
    g = hq // hk
    block_s = min(block_s, s)
    grid = (b, hk, pl.cdiv(s, block_s))

    qg = q.reshape(b, hk, g, d)
    length = jnp.asarray(length, jnp.int32).reshape(1)

    out = pl.pallas_call(
        _decode_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda bi, hi, si: (0,)),
            pl.BlockSpec((1, 1, g, d), lambda bi, hi, si: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, block_s, d),
                         lambda bi, hi, si: (bi, hi, si, 0)),
            pl.BlockSpec((1, 1, block_s, d),
                         lambda bi, hi, si: (bi, hi, si, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d),
                               lambda bi, hi, si: (bi, hi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hk, g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, d), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
        ],
        interpret=interpret,
    )(length, qg, k, v)
    return out.reshape(b, hq, d)
