"""Int8-on-the-wire gradient all-reduce (shard_map-explicit).

§Perf H4 showed that quantize->dequantize *inside* a pjit leaves GSPMD
reducing f32 — the compression was numerically active but moved no fewer
bytes.  This module is H4': the reduction itself runs on int8 payloads,
expressed with shard_map so the collectives are explicit:

    1. quantize the local gradient (per-tensor scale, int8);
    2. all_to_all the int8 chunks (each member receives its 1/N slice from
       every peer) -- int8 wire bytes;
    3. dequantize with the gathered peer scales, sum in f32 (no overflow);
    4. requantize the reduced slice and all_gather int8 -- int8 wire bytes.

Wire traffic: ~2x int8 tensor size, vs ~2x f32 for a ring all-reduce -- a
4x reduction, proven at the HLO level by ``repro.launch.dryrun
--collective-proof`` (results/dryrun/int8_proof.json), which parses the
compiled collective bytes of both versions on the production mesh.

This is the CXL-asym idea executed on the training write path: gradients
are the "writes" of a data-parallel step, and the scarce cross-pod links
are provisioned to what the traffic actually needs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _quantize(x):
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def int8_all_reduce(x, axis_name: str):
    """All-reduce-mean of f32 ``x`` with int8 wire payloads.

    Call inside shard_map with ``x`` replicated over ``axis_name``.
    The leading-dim size must divide the axis size after padding.
    """
    n = jax.lax.psum(1, axis_name)
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % n
    flat = jnp.pad(flat, (0, pad))
    q, scale = _quantize(flat)
    chunks = q.reshape(n, -1)                       # (N, size/N) int8
    # Each member ships chunk i to member i: int8 on the wire.
    recv = jax.lax.all_to_all(chunks, axis_name, split_axis=0,
                              concat_axis=0, tiled=False)
    scales = jax.lax.all_gather(scale, axis_name)   # (N,) f32 (tiny)
    partial = jnp.sum(recv.astype(jnp.float32) *
                      scales[:, None], axis=0) / n  # my 1/N slice, reduced
    q2, s2 = _quantize(partial)
    gathered = jax.lax.all_gather(q2, axis_name)    # (N, size/N) int8
    s2_all = jax.lax.all_gather(s2, axis_name)
    out = (gathered.astype(jnp.float32) *
           s2_all[:, None]).reshape(-1)
    out = out[:x.size] if pad else out
    return out.reshape(x.shape)


def f32_all_reduce(x, axis_name: str):
    """Reference: plain psum-mean (f32 on the wire)."""
    n = jax.lax.psum(1, axis_name)
    return jax.lax.psum(x, axis_name) / n


def make_reducer(mesh: Mesh, axis: str = "data", int8: bool = True):
    """A jit-able tree reducer over one mesh axis (grads replicated on the
    other axes)."""
    fn = int8_all_reduce if int8 else f32_all_reduce

    def reduce_tree(tree):
        def one(x):
            return fn(x, axis)

        inner = jax.shard_map(
            lambda t: jax.tree_util.tree_map(one, t), mesh=mesh,
            in_specs=(P(),), out_specs=P(), check_vma=False)
        return inner(tree)

    return reduce_tree
