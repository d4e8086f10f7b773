"""Jitted public wrappers for the Pallas kernels.

``interpret`` is the caller's choice, never inferred from the backend:
``interpret=False`` hands the kernel to the TPU compiler, ``interpret=True``
executes the same kernel body as plain JAX ops on any backend (the CPU
tests' mode).  A kernel the TPU compiler refuses is a compile error, not a
silent fallback.
"""

from __future__ import annotations

from repro.kernels import decode_attn as _da
from repro.kernels import rwkv_wkv as _wkv
from repro.kernels import stream as _stream


def stream_copy(a, *, interpret: bool):
    return _stream.stream_copy(a, interpret=interpret)


def stream_scale(a, alpha, *, interpret: bool):
    return _stream.stream_scale(a, alpha, interpret=interpret)


def stream_add(a, b, *, interpret: bool):
    return _stream.stream_add(a, b, interpret=interpret)


def stream_triad(a, b, alpha, *, interpret: bool):
    return _stream.stream_triad(a, b, alpha, interpret=interpret)


def decode_attn(q, k, v, length, block_s: int = _da.BLOCK_S, *,
                interpret: bool):
    """q: (B, Hq, D); k/v head-major (B, Hk, S, D)."""
    return _da.decode_attn(q, k, v, length, block_s=block_s,
                           interpret=interpret)


def wkv(r, k, v, w, u, state, block_t: int = _wkv.BLOCK_T, *,
        interpret: bool):
    """r/k/v/w head-major (B, H, T, D)."""
    return _wkv.wkv(r, k, v, w, u, state, block_t=block_t,
                    interpret=interpret)
