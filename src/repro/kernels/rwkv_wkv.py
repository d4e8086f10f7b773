"""RWKV6 WKV recurrence as a Pallas TPU kernel.

The WKV state is an (D, D) matrix per (batch, head); the recurrence

    y_t = r_t (S + u * k_t^T v_t);   S <- diag(w_t) S + k_t^T v_t

is sequential in t but embarrassingly parallel over (batch, head) -- which
is exactly the grid.  Operands are head-major, (batch, head, time, D), so a
time tile's last two dims are (BLOCK_T, D) -- the aligned shape the TPU
compiler accepts; each grid cell owns one head's state in VMEM scratch
and walks its time tile with a fori_loop.  The time axis is the innermost
grid dimension so the state persists across tiles (TPU grid order is
sequential), making the kernel O(1) in sequence length for VMEM: state
(D x D x 4B = 16 KiB at D=64) + one (BLOCK_T, D) tile per operand.

This is the exactness-first recurrence form; the chunked matmul
formulation (better MXU utilization for training) is the documented
next optimization -- semantics pinned by ref.wkv_ref either way.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_T = 128


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sout_ref,
                state_ref):
    t_idx = pl.program_id(2)
    n_t = pl.num_programs(2)

    @pl.when(t_idx == 0)
    def _init():
        state_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    u = u_ref[0, 0].astype(jnp.float32)                   # (D,)
    bt = r_ref.shape[2]

    def step(i, _):
        rt = r_ref[0, 0, i, :].astype(jnp.float32)        # (D,)
        kt = k_ref[0, 0, i, :].astype(jnp.float32)
        vt = v_ref[0, 0, i, :].astype(jnp.float32)
        wt = w_ref[0, 0, i, :].astype(jnp.float32)
        a = kt[:, None] * vt[None, :]                     # (D, D) outer
        s = state_ref[...]
        y = jnp.sum(rt[:, None] * (s + u[:, None] * a), axis=0)
        y_ref[0, 0, i, :] = y.astype(y_ref.dtype)
        state_ref[...] = s * wt[:, None] + a
        return 0

    jax.lax.fori_loop(0, bt, step, 0)

    @pl.when(t_idx == n_t - 1)
    def _finish():
        sout_ref[0, 0] = state_ref[...].astype(sout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def wkv(r, k, v, w, u, state, *, block_t: int = BLOCK_T,
        interpret: bool = False):
    """r/k/v/w: (B, H, T, D); u: (H, D); state: (B, H, D, D) fp32.

    Returns (y (B, H, T, D) fp32, final state (B, H, D, D) fp32).
    """
    b, h, t, d = r.shape
    block_t = min(block_t, t)
    grid = (b, h, pl.cdiv(t, block_t))

    seq_spec = pl.BlockSpec((1, 1, block_t, d),
                            lambda bi, hi, ti: (bi, hi, ti, 0))
    y, sout = pl.pallas_call(
        _wkv_kernel,
        grid=grid,
        in_specs=[
            seq_spec, seq_spec, seq_spec, seq_spec,
            pl.BlockSpec((1, 1, d), lambda bi, hi, ti: (hi, 0, 0)),
            pl.BlockSpec((1, 1, d, d), lambda bi, hi, ti: (bi, hi, 0, 0)),
        ],
        out_specs=[
            seq_spec,
            pl.BlockSpec((1, 1, d, d), lambda bi, hi, ti: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t, d), jnp.float32),
            jax.ShapeDtypeStruct((b, h, d, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        interpret=interpret,
    )(r, k, v, w, u.reshape(h, 1, d), state)
    return y, sout
