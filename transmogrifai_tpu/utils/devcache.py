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

#: what crossed host -> device through this cache, for ``obs.snapshot()``
#: (``["devcache"]``): bytes and count of real uploads, and lookups served
#: from a resident buffer
_scope = obs_registry.scope("devcache", defaults={
    "h2d_bytes": 0, "h2d_uploads": 0, "cache_hits": 0})


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


def seed(arr: np.ndarray, dev, dtype=None, tag: str = "base",
         device=None) -> bool:
    """Pre-populate ``arr``'s cached device product with ``dev``.

    The streaming transform executor uses this to hand a freshly computed
    device-resident matrix straight to the selector sweep: after seeding,
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
