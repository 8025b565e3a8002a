"""Persistent AOT executable cache — cold-start elimination for serving.

BENCH_r05 measured an 8.08 s compile warmup against a 0.39 s steady state:
every process restart, hot-swap, and cold deploy re-pays XLA for programs
it has compiled before.  The AOT-compilation lesson (arXiv:1810.09868) is
to pay XLA once — so warmup lowers each (version, bucket) score program,
asks this cache for the executable, and only compiles on a true miss.

Entries are ``jax.experimental.serialize_executable`` payloads (serialized
XLA executables + arg pytrees) pickled to ``TMOG_COMPILE_CACHE/<name>-
<fingerprint>.aotx``.  The fingerprint is content-based: a SHA-256 over the
lowered StableHLO text (which bakes in the fitted model constants, so two
models never collide), the jax version, and the target device — a restart
that lowers the same model to the same chip deserializes in milliseconds
instead of recompiling in seconds.

Degradation contract: a corrupt, stale, or undeserializable entry NEVER
fails the caller — it falls back to ``lowered.compile()`` and records the
reason via the central fallback audit trail
(``obs.snapshot()["compile_cache"]["fallbacks"]``).  Writes are atomic
(tmp + rename) so a crashed process cannot poison the directory.

Note this is deliberately NOT jax's own persistent compilation cache
(``utils/backend.compile_cache_dir`` switches that one on, off the CPU):
XLA's CPU cache refuses its own entries, while serialized executables
round-trip on every backend — which is what CI exercises.  Keep this
tier's directory under the same root (``utils/backend.cache_root()`` +
``/aotx``).
"""
from __future__ import annotations

import hashlib
import os
import pickle
import tempfile
import threading
import time
from collections import OrderedDict
from typing import Any, Optional, Sequence, Tuple

from ..obs import registry as obs_registry
from ..obs import trace
from ..resilience import inject as _inject
from ..utils import env

__all__ = ["cache_dir", "fingerprint", "load_or_compile", "cache_stats",
           "reset_cache_stats"]

#: pickle payload format — bump when the on-disk tuple layout changes;
#: mismatched entries fall back to compile (never an error)
_ENTRY_VERSION = 1

#: process-level second tier, keyed by (cache dir, program name, content
#: fingerprint).  XLA:CPU cannot round-trip SOME serialized executables
#: (deserialize_and_load raises "Symbols not found" on e.g. the logistic
#: prediction-head program, while the fused bucket programs round-trip
#: fine) — so a re-deploy in the same process reuses the executable the
#: cache itself produced for that exact fingerprint.  Consulted ONLY when a
#: VALID entry fails backend deserialization: corrupt/truncated pickles
#: still take the recorded compile fallback, and a process restart (memo
#: empty) still measures the true disk round-trip.
_MEM: "OrderedDict[Tuple[str, str, str], Any]" = OrderedDict()
_MEM_CAP = 256
_MEM_LOCK = threading.Lock()


def _mem_put(mkey: Tuple[str, str, str], compiled: Any) -> None:
    with _MEM_LOCK:
        _MEM[mkey] = compiled
        _MEM.move_to_end(mkey)
        while len(_MEM) > _MEM_CAP:
            _MEM.popitem(last=False)


def _mem_get(mkey: Tuple[str, str, str]) -> Optional[Any]:
    with _MEM_LOCK:
        compiled = _MEM.get(mkey)
        if compiled is not None:
            _MEM.move_to_end(mkey)
        return compiled

_scope = obs_registry.scope("compile_cache", defaults=dict(
    hits=0, misses=0, compiles=0, compile_s=0.0, load_s=0.0,
    saves=0, save_errors=0, fallbacks=[]))


def reset_cache_stats() -> None:
    _scope.reset()


def cache_stats() -> dict:
    """Point-in-time counters (also ``obs.snapshot()["compile_cache"]``)."""
    return _scope.snapshot()


def _record_fallback(reason: str, **detail: Any) -> None:
    obs_registry.record_fallback("compile_cache", reason, **detail)


def cache_dir() -> Optional[str]:
    """``TMOG_COMPILE_CACHE`` directory, or None (cache disabled)."""
    d = env.env_str("TMOG_COMPILE_CACHE")
    return d or None


def fingerprint(name: str, hlo_text: str, device: Any,
                extra: Sequence[Any] = ()) -> str:
    """Content hash of one executable: lowered program text (constants
    included — verified: changing a fitted weight changes the text), jax
    version, and the exact target device (executables are device-pinned;
    a payload compiled for chip 0 must not serve chip 3)."""
    import jax

    h = hashlib.sha256()
    for part in (name, jax.__version__, str(device),
                 getattr(device, "device_kind", ""), getattr(device, "platform", ""),
                 *[str(x) for x in extra]):
        h.update(part.encode())
        h.update(b"\x00")
    h.update(hlo_text.encode())
    return h.hexdigest()[:32]


def _entry_path(directory: str, name: str, key: str) -> str:
    safe = "".join(c if (c.isalnum() or c in "._-") else "_" for c in name)
    return os.path.join(directory, f"{safe}-{key}.aotx")


def _load_on(device: Any, payload: bytes, in_tree: Any, out_tree: Any) -> Any:
    """``serialize_executable.deserialize_and_load`` for ONE device, handing
    the runtime the compile options (device assignment) as jax's own
    persistent cache does on a read.  jax 0.9.0's loader passes none:
    without ``execution_devices`` it loads over every local device, and
    with it libtpu still assigns the executable to chip 0 whatever chip it
    was compiled for ("Buffer passed to Execute() ... is on device TPU_1,
    but replica is assigned to device TPU_0" on every replica but the
    first).  No public entry point takes the options, hence the two private
    names; entries are keyed by jax version, and any failure here is a
    recorded compile fallback."""
    import io

    import jax
    import numpy as np
    from jax._src import compiler
    from jax.experimental import serialize_executable

    options = compiler.get_compile_options(
        num_replicas=1, num_partitions=1,
        device_assignment=np.array([[device.id]]), backend=device.client)

    class Unpickler(serialize_executable._JaxPjrtUnpickler):
        def persistent_load(self, pid):
            if pid[0] == "exec":
                return self.backend.deserialize_executable(
                    pid[1], executable_devices=self.execution_devices,
                    compile_options=options)
            return super().persistent_load(pid)

    unloaded, args_info_flat, no_kwargs = Unpickler(
        io.BytesIO(payload), device.client, [device]).load()
    return jax.stages.Compiled(unloaded.load(), [],
                               in_tree.unflatten(args_info_flat), out_tree,
                               no_kwargs=no_kwargs)


def _try_load(path: str, device: Any) -> Tuple[Optional[Any], Optional[str]]:
    """Deserialize one entry onto ``device`` -> ``(compiled, failure_kind)``.

    ``(executable, None)`` on success.  On any defect the fallback is
    recorded and ``compiled`` is None; ``failure_kind`` distinguishes
    ``"corrupt"`` (truncated pickle, wrong entry version — the entry itself
    is bad) from ``"backend"`` (a VALID entry whose payload this backend
    refuses to deserialize — XLA:CPU round-trip gaps), which decides
    whether the in-process memo may stand in."""
    t0 = time.perf_counter()
    entry = None
    try:
        _inject.maybe_fail("compile_cache.load")
        with open(path, "rb") as f:
            entry = pickle.load(f)
        if not (isinstance(entry, tuple) and len(entry) == 4
                and entry[0] == _ENTRY_VERSION):
            entry = None
            raise ValueError(f"entry version mismatch")
        _, payload, in_tree, out_tree = entry
        compiled = _load_on(device, payload, in_tree, out_tree)
    except Exception as e:  # noqa: BLE001 — corrupt entry -> compile fallback
        kind = "backend" if entry is not None else "corrupt"
        _record_fallback("corrupt_cache_entry" if kind == "corrupt"
                         else "backend_deserialize_failed",
                         path=path, error=repr(e))
        return None, kind
    _scope.inc("load_s", time.perf_counter() - t0)
    return compiled, None


def _save(path: str, compiled: Any) -> bool:
    """Atomic write (tmp + rename); failure is recorded, never raised."""
    from jax.experimental import serialize_executable

    try:
        payload, in_tree, out_tree = serialize_executable.serialize(compiled)
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path) or ".",
                                   suffix=".tmp")
        try:
            with os.fdopen(fd, "wb") as f:
                pickle.dump((_ENTRY_VERSION, payload, in_tree, out_tree), f)
            os.replace(tmp, path)
        finally:
            if os.path.exists(tmp):
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
    except Exception as e:  # noqa: BLE001 — an unserializable backend degrades
        _scope.inc("save_errors")
        _record_fallback("cache_save_failed", path=path, error=repr(e))
        return False
    _scope.inc("saves")
    return True


def load_or_compile(name: str, lowered: Any, device: Any,
                    extra: Sequence[Any] = (),
                    hlo_text: Optional[str] = None) -> Tuple[Any, str]:
    """The one entry point: executable for ``lowered``, cache-first.

    ``lowered`` is the lowered program or a zero-arg callable producing it
    (lazy: on a cache hit the lowering itself is skipped — tracing 56
    replica x bucket programs costs seconds even when every compile is a
    hit).  Lazy callers must pass ``hlo_text`` (the canonical program text
    for fingerprinting; device identity is NOT part of the text, so one
    replica's text fingerprints every device — verified empirically).

    Returns ``(compiled, source)`` with source in {"hit", "compile"}.
    With no ``TMOG_COMPILE_CACHE`` configured this is a plain compile
    (counted, so the obs compile counters stay meaningful either way).
    """
    directory = cache_dir()
    path = mkey = None
    if directory:
        if hlo_text is None:
            hlo_text = lowered.as_text()
        key = fingerprint(name, hlo_text, device, extra)
        path = _entry_path(directory, name, key)
        mkey = (directory, name, key)
        if os.path.exists(path):
            with trace.span("compile_cache.load", program=name,
                            device=str(device)):
                compiled, fail_kind = _try_load(path, device)
            if compiled is not None:
                _mem_put(mkey, compiled)
                _scope.inc("hits")
                return compiled, "hit"
            if fail_kind == "backend":
                compiled = _mem_get(mkey)
                if compiled is not None:
                    _scope.inc("hits")
                    return compiled, "hit"
        _scope.inc("misses")
    if callable(lowered) and not hasattr(lowered, "compile"):
        lowered = lowered()
    t0 = time.perf_counter()
    with trace.span("compile_cache.compile", program=name,
                    device=str(device)):
        compiled = lowered.compile()
    _scope.inc("compiles")
    _scope.inc("compile_s", time.perf_counter() - t0)
    if path is not None:
        _save(path, compiled)
        _mem_put(mkey, compiled)
    return compiled, "compile"
