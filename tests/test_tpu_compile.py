"""Compile the main path's kernels for a described TPU v5e, with no chip.

The TPU compiler ships with libtpu and compiles for a topology that is
described, not attached.  It refuses what interpret mode and the CPU
backend accept: Pallas block shapes off the (8, 128) tiling, programs
that do not fit the device, kernels that cannot be partitioned.  These
tests compile, at the widths users resolve by default:

* stage A and stage B of both DES engines at the default QueueLUT width
  (2,016 cells x 2 reps = 4,032 lanes) and the harvest-LUT width
  (x 4 harvest points = 16,128 lanes), plus the harvest passes; the
  event engine's stage A takes its TPU branch, the fused compare-count;
* one event-engine stage B under ``shard_map`` over the four described
  devices (the ``devices=4`` LUT build);
* the four STREAM kernels and the decode-attention / WKV kernels.

The topology is described inside a module fixture -- never at import --
so that under several test workers only the worker running this file
loads libtpu.  Nothing here runs: a compile that passes is not a chip run.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import memsim, queuelut, shardsim
from repro.kernels import decode_attn, rwkv_wkv, stream

LUT_CELLS = (len(queuelut.DEFAULT_RHO_GRID) * len(queuelut.DEFAULT_KAPPA_GRID)
             * len(queuelut.DEFAULT_OUTSTANDING_GRID)
             * len(queuelut.DEFAULT_ETA_GRID))
#: Default LUT and harvest-LUT lane counts (cells x reps [x harvest]).
WIDTHS = (LUT_CELLS * queuelut.DEFAULT_REPS,
          LUT_CELLS * queuelut.DEFAULT_REPS
          * len(queuelut.DEFAULT_HARVEST_GRID))


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:      # noqa: BLE001 -- any failure => skip
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described compile is written to the persistent cache but cannot
    # be read back without a chip; keep the cache out of it.
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _placed(tree, sharding):
    """Abstract values of ``tree`` (arrays or ShapeDtypeStructs) placed
    on ``sharding``."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                       sharding=sharding), tree)


def _compile(fn, *args, **static):
    """Compile ``fn`` for the shardings carried by ``args``; returns the
    compiled program (raises what the TPU compiler raises)."""
    return jax.jit(functools.partial(fn, **static)).lower(*args).compile()


def _lane_inputs(n):
    """Abstract (cha, ov, lane_idx, key) of an ``n``-lane LUT build."""
    f32 = jax.ShapeDtypeStruct((n,), jnp.float32)
    cha = memsim.ChannelArrays(*([f32] * len(memsim.CHANNEL_FIELDS)))
    ov = {f: f32 for f in memsim.CHANNEL_FIELDS}
    lanes = jax.ShapeDtypeStruct((n,), jnp.uint32)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    return cha, ov, lanes, key


def _event_budget():
    """(chunk, n_sojourns) of a default-budget event LUT build."""
    chunk = memsim.canonical_chunk("event")
    events = memsim.events_for_steps(queuelut.DEFAULT_STEPS)
    n_chunks = -(-events // chunk)
    return chunk, max(64, (n_chunks * chunk) // memsim._SOJOURN_DIV)


def _stage_a(engine, n, sharding):
    """Compile stage A of ``engine`` at ``n`` lanes; returns its outputs'
    abstract values, on ``sharding``, for stage B."""
    cha, ov, lanes, key = _placed(_lane_inputs(n), sharding)
    terms = jax.eval_shape(memsim._scan_terms, cha, ov)
    _compile(memsim._scan_terms, cha, ov)
    if engine == "timestep":
        chunk = memsim.canonical_chunk("timestep")
        _compile(memsim._ts_draws, cha, ov, lanes, key, chunk=chunk)
        draws = jax.eval_shape(functools.partial(memsim._ts_draws,
                                                 chunk=chunk),
                               cha, ov, lanes, key)
        hterms = jax.eval_shape(memsim._harvest_scan_terms, cha, ov)
        return _placed(({**terms, **hterms}, draws), sharding) + (chunk,)
    chunk, n_soj = _event_budget()
    _compile(memsim._event_tables, cha, ov, lanes, key, n_sojourns=n_soj)
    args = _event_arrivals_args(n, sharding)
    _compile(memsim._event_arrivals, *args, chunk=chunk)
    out = jax.eval_shape(functools.partial(memsim._event_arrivals,
                                           chunk=chunk), *args)
    return _placed((terms, out[1:]), sharding) + (chunk,)


def _event_arrivals_args(n, sharding):
    """Abstract arguments of the event engine's stage A at ``n`` lanes."""
    cha, ov, lanes, key = _placed(_lane_inputs(n), sharding)
    _, n_soj = _event_budget()
    tabs = _placed(jax.eval_shape(functools.partial(
        memsim._event_tables, n_sojourns=n_soj), cha, ov, lanes, key),
        sharding)
    vec = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=sharding)
    warm = jax.ShapeDtypeStruct((), jnp.float32, sharding=sharding)
    return cha, ov, (vec, vec), lanes, key, tabs, warm


@pytest.mark.parametrize("n", WIDTHS)
@pytest.mark.parametrize("engine", memsim.ENGINES)
def test_des_stages_compile(one_chip, engine, n):
    terms, draws, chunk = _stage_a(engine, n, one_chip)
    vec = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    if engine == "timestep":
        kernel = memsim._ts_kernel.__wrapped__(1, n, n)
        hu = draws[0]                       # same shape as switch_u
        rec = jax.ShapeDtypeStruct((chunk,), jnp.float32, sharding=one_chip)
        compiled = kernel.lower(terms, (vec, vec, vec), *draws, hu,
                                rec).compile()
    else:
        kernel = memsim._event_kernel.__wrapped__(1, n, n, chunk)
        compiled = kernel.lower(terms, vec, *draws).compile()
    stats = compiled.memory_analysis()
    assert stats.temp_size_in_bytes < 16e9


def test_event_arrivals_count_fuses(one_chip):
    """The TPU lowers stage A's segment lookup as a compare-count that
    fuses into its reduction: no binary-search loop, and far less temp
    than the unfused ``(4032, 1024, 897)`` predicate's 3.7 GB."""
    chunk, _ = _event_budget()
    compiled = _compile(memsim._event_arrivals,
                        *_event_arrivals_args(WIDTHS[0], one_chip),
                        chunk=chunk)
    assert compiled.memory_analysis().temp_size_in_bytes < 256e6
    assert " while(" not in compiled.as_text()


def test_harvest_passes_compile(one_chip):
    n = WIDTHS[1]
    cha, ov, lanes, key = _placed(_lane_inputs(n), one_chip)
    _compile(memsim._harvest_scan_terms, cha, ov)
    _compile(memsim._ts_harvest_u, lanes, key,
             chunk=memsim.canonical_chunk("timestep"))
    chunk, n_soj = _event_budget()
    _compile(memsim._event_harvest_tabs, cha, ov, lanes, key,
             n_windows=n_soj)
    cn = jax.ShapeDtypeStruct((chunk, n), jnp.float32, sharding=one_chip)
    vec = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    bounds = jax.ShapeDtypeStruct((n, n_soj), jnp.float32, sharding=one_chip)
    lent = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    _compile(memsim._event_harvest_scale, cn, cn, vec, bounds, vec, lent)


def test_event_stage_b_shard_map_four_chips(topo, monkeypatch):
    """``devices=4``: the default LUT's event stage B, lane-sharded over
    a mesh of the four described chips."""
    n = WIDTHS[0]
    mesh = Mesh(np.array(topo.devices), (shardsim.AXIS,))
    monkeypatch.setattr(shardsim, "lane_mesh", lambda ndev: mesh)
    cols = NamedSharding(mesh, P(None, shardsim.AXIS))
    rows = NamedSharding(mesh, P(shardsim.AXIS))
    chunk, _ = _event_budget()
    terms = {k: jax.ShapeDtypeStruct((n,), jnp.float32, sharding=rows)
             for k in ("p_leave", "p_enter", "rate_hi", "rate_lo", "bound",
                       "lat0")}
    W = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=rows)
    gaps = jax.ShapeDtypeStruct((chunk, n), jnp.float32, sharding=cols)
    rec = jax.ShapeDtypeStruct((chunk, n), jnp.bool_, sharding=cols)
    kernel = memsim._event_kernel.__wrapped__(4, n, n, chunk)
    compiled = kernel.lower(terms, W, gaps, gaps, rec).compile()
    # Lanes never exchange data: the sharded scan needs no collective.
    text = compiled.as_text()
    assert "all-gather" not in text and "all-reduce" not in text


def _assert_kernel(fn, *args):
    assert "tpu_custom_call" in _compile(fn, *args).as_text()


@pytest.mark.parametrize("name", ["copy", "scale", "add", "triad"])
def test_stream_kernel_compiles(one_chip, name):
    a = jax.ShapeDtypeStruct((8192, 1024), jnp.float32, sharding=one_chip)
    fn = {"copy": lambda a, b: stream.stream_copy(a),
          "scale": lambda a, b: stream.stream_scale(a, 2.0),
          "add": stream.stream_add,
          "triad": lambda a, b: stream.stream_triad(a, b, 2.0)}[name]
    _assert_kernel(fn, a, a)


@pytest.mark.parametrize("hq,hk,d", [(96, 8, 128), (32, 32, 64)])
def test_decode_attn_compiles(one_chip, hq, hk, d):
    """Mistral-Large-123B (GQA, 12 query heads per KV head) and
    StableLM-1.6B (MHA) decode at a 4,096-token context."""
    def s(*shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    _assert_kernel(decode_attn.decode_attn, s(8, hq, d), s(8, hk, 4096, d),
                   s(8, hk, 4096, d), s(dt=jnp.int32))


def test_wkv_compiles(one_chip):
    """RWKV6-1.6B: 32 heads of 64, a 1,024-token time span."""
    def s(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    seq = s(1, 32, 1024, 64)
    _assert_kernel(rwkv_wkv.wkv, seq, seq, seq, seq, s(32, 64),
                   s(1, 32, 64, 64))
