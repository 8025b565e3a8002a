"""Device-residency cache for host arrays (and derived binned variants).

Motivation (round-5 perf work): every host->device transfer is a round trip
plus the copy, and the selector sweep used to re-upload the SAME feature
matrix once per model family per rep (plus re-quantize it per tree group).  This cache keys device
buffers by the identity of the host ``np.ndarray`` so X / y / binned-X
upload once and every family reuses the resident buffer.

A weakref on the source array evicts its entry when the array dies, so the
cache cannot leak past the data's lifetime and a recycled ``id()`` can never
serve another array's buffers (the eviction callback runs before the id can
be reused).  Arrays that refuse weakrefs are simply not cached.

Caveat (documented contract): callers must not MUTATE a cached array in
place — the framework's columnar pipeline never does (transforms build new
arrays).  Set ``TRANSMOG_DEVCACHE_CHECK=1`` to enforce it: a cheap
fingerprint (shape, dtype, first/last-row checksum) is stored at insert and
re-verified at every lookup; a mismatch raises ``DevCacheMutationError``
instead of silently serving stale device buffers.
"""
from __future__ import annotations

import os
import weakref
import zlib
from typing import Any, Dict, Optional, Tuple

import numpy as np

from ..obs import registry as obs_registry
from ..obs import trace

#: what crossed host -> device, for ``obs.snapshot()`` (``["devcache"]``):
#: bytes and count of this cache's real uploads, lookups served from a
#: resident buffer, and bytes and count of the uncached uploads of :func:`put`
_scope = obs_registry.scope("devcache", defaults={
    "h2d_bytes": 0, "h2d_uploads": 0, "cache_hits": 0,
    "put_bytes": 0, "puts": 0})


class DevCacheMutationError(RuntimeError):
    """A host array was mutated in place after its device copy was cached."""


def _check_enabled() -> bool:
    return os.environ.get("TRANSMOG_DEVCACHE_CHECK", "") == "1"


def _fingerprint(arr: np.ndarray) -> Optional[Tuple]:
    """(shape, dtype, crc(first row), crc(last row)) — O(row width), not O(n)."""
    try:
        first = np.ascontiguousarray(arr[:1])
        last = np.ascontiguousarray(arr[-1:])
        return (arr.shape, arr.dtype.str,
                zlib.crc32(first.tobytes()), zlib.crc32(last.tobytes()))
    except Exception:  # non-bytes-able contents (object arrays): skip the check
        return None


_entries: Dict[int, Dict[str, Any]] = {}


def _slot(arr: np.ndarray) -> Optional[Dict[Any, Any]]:
    """The per-array cache dict (derived products keyed by caller tags), or
    None when the array cannot be weakref'd (then nothing is cached)."""
    key = id(arr)
    ent = _entries.get(key)
    if ent is not None:
        if _check_enabled():
            fp = _fingerprint(arr)
            old = ent.get("fp")
            if old is None:
                ent["fp"] = fp  # inserted while the check was off: adopt now
            elif fp is not None and fp != old:
                raise DevCacheMutationError(
                    f"devcache: host array id={key} was mutated in place after "
                    f"caching (fingerprint {old} -> {fp}); cached device "
                    f"buffers would be stale. Build a new array instead.")
        return ent["products"]
    try:
        ref = weakref.ref(arr, lambda _r, k=key: _entries.pop(k, None))
    except TypeError:  # exotic ndarray subclass without weakref support
        return None
    products: Dict[Any, Any] = {}
    ent = {"_ref": ref, "products": products}
    if _check_enabled():
        ent["fp"] = _fingerprint(arr)
    _entries[key] = ent
    return products


def device_array(arr, dtype=None, tag: str = "base", device=None):
    """Device-resident copy of ``arr`` (cached by host-array identity).

    Already-on-device jax arrays pass through untouched.  ``tag`` separates
    derived variants (e.g. different dtypes) of the same host array.
    ``device`` pins the copy to a specific ``jax.Device`` (cached per device)
    — the multi-chip sweep uses this to keep one resident X/y per shard.
    """
    import jax
    import jax.numpy as jnp

    def build():
        # the copy's size is known before it is made: rows x the target
        # dtype's width
        nbytes = int(arr.size) * (arr.dtype if dtype is None
                                  else np.dtype(dtype)).itemsize
        with trace.span("devcache.upload", bytes=nbytes, tag=tag):
            a = jnp.asarray(arr) if dtype is None \
                else jnp.asarray(np.asarray(arr, dtype))
            a = a if device is None else jax.device_put(a, device)
        _scope.inc("h2d_bytes", nbytes)
        _scope.inc("h2d_uploads")
        return a

    if not isinstance(arr, np.ndarray):  # jax array (or scalar): no caching
        a = jnp.asarray(arr) if dtype is None else jnp.asarray(arr, dtype)
        return a if device is None else jax.device_put(a, device)
    products = _slot(arr)
    if products is None:
        return build()
    key = (tag, None if dtype is None else np.dtype(dtype).str,
           None if device is None else str(device))
    dev = products.get(key)
    if dev is None:
        dev = build()
        products[key] = dev
    else:
        _scope.inc("cache_hits")
    return dev


def holds(arr, dtype=None, tag: str = "base", device=None) -> bool:
    """Whether :func:`device_array` would serve ``arr`` from the cache."""
    ent = _entries.get(id(arr)) if isinstance(arr, np.ndarray) else None
    return ent is not None and (
        tag, None if dtype is None else np.dtype(dtype).str,
        None if device is None else str(device)) in ent["products"]


def resident_bytes() -> int:
    """The bytes of every device buffer the cache keeps, derived products
    included."""
    import jax

    return sum(int(leaf.nbytes) for ent in list(_entries.values())
               for leaf in jax.tree_util.tree_leaves(
                   list(ent["products"].values()))
               if isinstance(leaf, jax.Array))


def put(x, dtype=None, tag: str = "put", device=None):
    """Uncached upload: what ``jnp.asarray(x, dtype)`` does (with
    ``device``, what ``jax.device_put`` onto it does), seen by the trace and
    the counters.

    For a one-off host array (an estimator's rows, a fold-weight block) that
    :func:`device_array` would only cache to no use.  The copy runs in a
    ``devcache.put`` span and adds to ``put_bytes`` / ``puts``; its size is
    the element count x the width of the dtype it lands in.  An argument
    that is already a ``jax.Array`` passes through (converted to ``dtype``,
    placed on ``device``) and counts 0 bytes.
    """
    import jax
    import jax.numpy as jnp

    if isinstance(x, jax.Array):
        a = x if dtype is None else jnp.asarray(x, dtype)
        return a if device is None else jax.device_put(a, device)
    dt = jax.dtypes.canonicalize_dtype(
        np.asarray(x).dtype if dtype is None else dtype)
    nbytes = int(np.size(x)) * dt.itemsize
    with trace.span("devcache.put", bytes=nbytes, tag=tag):
        if device is not None:
            a = jax.device_put(x if dtype is None else np.asarray(x, dtype),
                               device)
        else:
            a = jnp.asarray(x) if dtype is None else jnp.asarray(x, dtype)
    _scope.inc("put_bytes", nbytes)
    _scope.inc("puts")
    return a


def as_f32(x):
    """``x`` in float32 where it already lies: a ``jax.Array`` stays on its
    device (cast there, itself when already float32), anything else becomes
    a C-contiguous host ``np.ndarray``.  For consumers that take either the
    selector's device rows or a host matrix, so that neither is moved."""
    import jax
    import jax.numpy as jnp

    if isinstance(x, jax.Array):
        return jnp.asarray(x, jnp.float32)
    return np.ascontiguousarray(np.asarray(x, np.float32))


def seed(arr: np.ndarray, dev, dtype=None, tag: str = "base",
         device=None) -> bool:
    """Pre-populate ``arr``'s cached device product with ``dev``.

    The serving executor uses this to keep a freshly computed
    device-resident matrix discoverable: after seeding,
    ``device_array(arr, dtype)`` returns ``dev`` without re-uploading the
    host copy.  The caller GUARANTEES ``dev`` equals ``arr`` (same values,
    rows, dtype) — the contract is the same as the no-in-place-mutation one
    above.  Returns False when ``arr`` cannot be weakref'd (nothing cached).
    """
    if not isinstance(arr, np.ndarray):
        return False
    products = _slot(arr)
    if products is None:
        return False
    key = (tag, None if dtype is None else np.dtype(dtype).str,
           None if device is None else str(device))
    products[key] = dev
    return True


def derived(arr: np.ndarray, key: Tuple, build) -> Any:
    """Cached derived product of ``arr`` (e.g. quantized bins + edges).

    ``build()`` is called once per (array identity, key); its result is
    cached for the array's lifetime.  Uncacheable arrays just rebuild.
    """
    products = _slot(arr)
    if products is None:
        return build()
    k = ("derived",) + key
    out = products.get(k)
    if out is None:
        out = build()
        products[k] = out
    return out


def clear() -> None:
    _entries.clear()
