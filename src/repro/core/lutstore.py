"""Content-addressed on-disk QueueLUT store (``$REPRO_LUT_CACHE``).

The DES-built :class:`~repro.core.queuelut.QueueLUT` is the costliest
artifact every session rebuilds: CI smoke, ``python -m repro.designer``,
``repro.serving.plan`` and the tier-1 tests each pay for the full
14x6x6x4(xharvest) surface behind an in-process cache that dies with the
process.  This module persists the surfaces: set
``$REPRO_LUT_CACHE`` to a directory and every built surface is written
there once and read back bit-identically forever after -- a warm read
runs ZERO simulation (``memsim.sim_trace_count`` stays flat, pinned by
``tests/test_lutstore.py``).

Store layout -- one ``.npz`` per surface, named by its key::

    $REPRO_LUT_CACHE/qlut-<sha256[:32]>.npz

The key is a sha256 over every input that determines the tables:

* all grid tuples (rho / kappa / outstanding / eta / harvest);
* the DES build parameters (steps, seed, reps, engine,
  harvest_bw_gbps, and the base ChannelConfig's field values);
* the per-engine **mechanism fingerprint** (:func:`mechanism_fingerprint`);
* the platform and device kind the build runs on (:func:`device_tag`):
  the simulator's ``log``/``exp``/``pow`` round differently per backend,
  so a surface built on the CPU is not the chip's answer.

The fingerprint hashes the SOURCE of the simulator stack (``memsim.py``,
``shardsim.py``, ``queuelut.py``) plus a schema version -- any simulator
change shifts the key, so stale surfaces are never read, only orphaned
(and later :func:`gc`'d).  It is deliberately coarser than the
BEHAVIORAL fingerprints sha-pinned in ``tests/test_harvest.py``
(``PRE_HARVEST_SHA``): computing those requires *running* the DES, which
is exactly what a warm read must skip; a source hash over-invalidates at
worst (one spurious rebuild per comment edit), never under-invalidates.

Integrity: writes are atomic (temp file + ``os.replace`` in the store
directory), and a corrupted or truncated artifact is QUARANTINED on read
(renamed to ``*.corrupt``) and rebuilt -- never a crash.

On top of the disk layer sits a small bounded in-process LRU
(:data:`MEM_CACHE_MAX` surfaces) -- the replacement for the historical
unbounded ``functools.lru_cache`` on ``default_queue_lut``, which pinned
every distinct surface's device arrays for process lifetime.
:func:`clear_lut_cache` empties it (tests use this to force cold reads).
"""

from __future__ import annotations

import hashlib
import json
import os
import tempfile
import time
from collections import OrderedDict
from pathlib import Path

import jax
import numpy as np

#: Bump to invalidate every stored surface on a format change.
SCHEMA = 1

#: Environment knob naming the store directory (unset => store disabled).
ENV_VAR = "REPRO_LUT_CACHE"

#: Source files whose bytes define the mechanism fingerprint: the
#: simulator, its sharding layer, and the table derivation.
_FINGERPRINT_SOURCES = ("memsim.py", "shardsim.py", "queuelut.py")

#: Max surfaces held by the bounded in-process layer.  Each default
#: surface is ~100 KB of tables; 8 covers every (engine, harvest, steps)
#: combination a test session or benchmark run actually touches.
MEM_CACHE_MAX = 8

_mem_cache: OrderedDict[str, object] = OrderedDict()
_fingerprint_memo: str | None = None


def cache_dir() -> Path | None:
    """The store directory per ``$REPRO_LUT_CACHE``, created on demand.

    Unset or blank disables the on-disk store entirely (the bounded
    in-process layer still works).
    """
    path = os.environ.get(ENV_VAR, "").strip()
    if not path:
        return None
    p = Path(path)
    p.mkdir(parents=True, exist_ok=True)
    return p


def mechanism_fingerprint() -> str:
    """sha256 over the simulator stack's source + the store schema.

    Memoized per process: the sources cannot change under a running
    interpreter in any way the interpreter would notice anyway.
    """
    global _fingerprint_memo
    if _fingerprint_memo is None:
        h = hashlib.sha256(f"schema={SCHEMA}".encode())
        here = Path(__file__).parent
        for name in _FINGERPRINT_SOURCES:
            h.update(name.encode())
            h.update((here / name).read_bytes())
        _fingerprint_memo = h.hexdigest()
    return _fingerprint_memo


def device_tag() -> dict:
    """Platform and device kind of the device a build lands on: the
    ``jax.default_device`` in force, else the default backend's first
    device."""
    dev = jax.config.jax_default_device
    if dev is None:
        dev = jax.devices()[0]
    elif isinstance(dev, str):
        dev = jax.devices(dev)[0]
    return dict(platform=dev.platform, device_kind=dev.device_kind)


def store_key(params: dict) -> str:
    """Content address of a surface: sha256 over build params, the
    mechanism fingerprint and the device (:func:`device_tag`).

    ``params`` must be JSON-serializable with deterministic ordering
    (grids as tuples of floats, scalars, or None) -- the caller
    (``queuelut.resolve_lut``) canonicalizes them.
    """
    body = json.dumps({"fingerprint": mechanism_fingerprint(),
                       "device": device_tag(), **params}, sort_keys=True)
    return hashlib.sha256(body.encode()).hexdigest()


def entry_path(key: str, root: Path | None = None) -> Path | None:
    root = cache_dir() if root is None else root
    if root is None:
        return None
    return root / f"qlut-{key[:32]}.npz"


def _quarantine(path: Path) -> None:
    """Move a bad artifact aside (never delete: it is evidence)."""
    try:
        path.replace(path.with_suffix(path.suffix + ".corrupt"))
    except OSError:
        pass                      # racing process already moved it


def save(key: str, lut, meta: dict | None = None) -> Path | None:
    """Persist a QueueLUT atomically; returns the path (None = disabled).

    Leaves are written as raw numpy arrays (float32 under the default
    jax config); the round trip back through :func:`load` is bit-exact.
    """
    path = entry_path(key)
    if path is None:
        return None
    arrays = {f: np.asarray(leaf) for f, leaf in zip(lut._fields, lut)
              if leaf is not None}
    meta = dict(meta or {}, schema=SCHEMA, key=key,
                fingerprint=mechanism_fingerprint(),
                unix_time=int(time.time()))
    fd, tmp = tempfile.mkstemp(dir=path.parent, suffix=".npz.tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            np.savez(f, meta_json=np.frombuffer(
                json.dumps(meta).encode(), np.uint8), **arrays)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    return path


def load(key: str):
    """Read a stored surface; None on miss.  Corruption => quarantine.

    Returns the reconstructed ``QueueLUT`` (imported lazily -- queuelut
    imports this module at top level).  Any failure to read, parse, or
    validate the artifact quarantines the file and reports a miss, so a
    torn write or a flipped bit costs one rebuild, never a crash.
    """
    path = entry_path(key)
    if path is None or not path.exists():
        return None
    from repro.core.queuelut import QueueLUT
    import jax.numpy as jnp
    try:
        with np.load(path, allow_pickle=False) as z:
            meta = json.loads(bytes(z["meta_json"]).decode())
            if meta.get("schema") != SCHEMA or meta.get("key") != key:
                raise ValueError("schema/key mismatch")
            if meta.get("fingerprint") != mechanism_fingerprint():
                raise ValueError("fingerprint mismatch")
            fields = {f: jnp.asarray(z[f]) for f in QueueLUT._fields
                      if f in z.files}
        for f in QueueLUT._fields[:8]:        # grids + the four tables
            if f not in fields:
                raise ValueError(f"missing field {f}")
        return QueueLUT(**fields)
    except Exception:             # noqa: BLE001 -- ANY read failure
        _quarantine(path)
        return None


def read_meta(path: Path) -> dict | None:
    """Best-effort meta block of one store entry (None if unreadable)."""
    try:
        with np.load(path, allow_pickle=False) as z:
            return json.loads(bytes(z["meta_json"]).decode())
    except Exception:             # noqa: BLE001 -- inspect never raises
        return None


def entries() -> list[dict]:
    """Every store entry with its meta (for ``python -m repro.lut``)."""
    root = cache_dir()
    if root is None:
        return []
    out = []
    for path in sorted(root.glob("qlut-*.npz")):
        meta = read_meta(path) or {}
        out.append(dict(path=str(path), bytes=path.stat().st_size,
                        **meta))
    return out


def gc(max_age_days: float | None = None, everything: bool = False) -> dict:
    """Drop stale entries (and all ``*.corrupt`` quarantine files).

    ``everything=True`` empties the store; otherwise entries older than
    ``max_age_days`` (by recorded build time, falling back to mtime) and
    entries whose fingerprint no longer matches the current simulator
    are removed.  Returns ``{"removed": n, "bytes": freed}``.
    """
    root = cache_dir()
    if root is None:
        return dict(removed=0, bytes=0)
    removed = freed = 0
    now = time.time()
    fp = mechanism_fingerprint()
    for path in list(root.glob("qlut-*.npz")) + \
            list(root.glob("*.corrupt")):
        drop = everything or path.suffix == ".corrupt"
        if not drop:
            meta = read_meta(path)
            if meta is None or meta.get("fingerprint") != fp:
                drop = True
            elif max_age_days is not None:
                built = meta.get("unix_time", path.stat().st_mtime)
                drop = (now - built) > max_age_days * 86_400.0
        if drop:
            try:
                size = path.stat().st_size
                path.unlink()
                removed += 1
                freed += size
            except OSError:
                pass
    return dict(removed=removed, bytes=freed)


# ---------------------------------------------------------------------------
# Bounded in-process layer.
# ---------------------------------------------------------------------------

def cache_get(key: str):
    """In-process LRU lookup (refreshes recency on hit)."""
    lut = _mem_cache.get(key)
    if lut is not None:
        _mem_cache.move_to_end(key)
    return lut


def cache_put(key: str, lut) -> None:
    _mem_cache[key] = lut
    _mem_cache.move_to_end(key)
    while len(_mem_cache) > MEM_CACHE_MAX:
        _mem_cache.popitem(last=False)


def clear_lut_cache() -> None:
    """Empty the bounded in-process layer (tests force cold reads with
    this; the on-disk store is untouched)."""
    _mem_cache.clear()
