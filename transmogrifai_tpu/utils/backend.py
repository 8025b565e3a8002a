"""The device, the compile cache and the roofline peaks — one place each.

The platform is whatever JAX selects: ``JAX_PLATFORMS=cpu`` for tests and
local drives, nothing set on a machine that has a TPU.  Nothing here (or
anywhere in the package) changes ``jax_platforms``, probes in a child
process or falls back to another platform: a chip belongs to one process at
a time, and a run that silently landed on the CPU reports numbers nobody
deploys.  Entry points print what :func:`device_summary` found; the
harnesses that measure (``bench.py``, ``scale10m.py``, ``scale100m.py``)
call :func:`require_tpu` and exit non-zero without one, and
``chip_smoke.py`` ends ``"ok": false`` on any other platform.
"""
from __future__ import annotations

import functools
import json
import os
import sys
from typing import Optional

#: the package's parent directory — the checkout root when run from source
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def device_summary() -> dict:
    """``{"platform", "kind", "count"}`` as JAX reports them (initializes
    the backend JAX selected)."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu(who: str) -> dict:
    """The device summary, or exit 1 when the platform JAX selected is not
    a TPU — after printing ``{"ok": false, "error": ...}`` on stdout and the
    reason on stderr.  For harnesses whose numbers are device metrics: a CPU
    run must not look like a result."""
    dev = None
    try:
        dev = device_summary()
        reason = (None if dev["platform"] == "tpu" else
                  f"needs a TPU, JAX selected {dev['platform']!r} "
                  f"({dev['kind']} x{dev['count']}); a CPU timing is not a "
                  "device number")
    except RuntimeError as e:  # JAX found no usable backend at all
        reason = f"no JAX backend: {e}"
    if reason is None:
        return dev
    print(json.dumps({"ok": False, "error": f"{who}: {reason}",
                      "device": dev}))
    sys.exit(f"{who}: {reason}")


def cache_root() -> str:
    """Where this program's compiled artifacts persist:
    ``JAX_COMPILATION_CACHE_DIR`` when set, else one fixed path inside the
    checkout, ``<root>/.jax_cache``.  The path is part of jax's cache key,
    so a directory that moves with a pid, a temp name or ``$HOME`` never
    hits.  The serving tier's serialized executables (``TMOG_COMPILE_CACHE``,
    ``serve/compile_cache``) belong under ``<this>/aotx``."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
            or os.path.join(_ROOT, ".jax_cache"))


@functools.cache
def compile_cache_dir() -> Optional[str]:
    """Switch on jax's persistent compilation cache — the ONE place the
    program does — and return its directory (None: the cache is off).
    Decided once per process (a racing first call sets the same values);
    call before the first compile.  Initializes the backend JAX selected.

    - ``JAX_COMPILATION_CACHE_DIR`` set: jax already reads it, so no
      directory is set in code.
    - CPU backend: off.  XLA:CPU executables read back from this cache do
      not survive re-serialization (``serve/compile_cache`` saved entries
      missing their kernels: "Function dot_general.0_kernel not found" on
      the next deploy), and concurrent per-shard compiles aborted the
      interpreter inside the cache write.
    - otherwise :func:`cache_root`.
    """
    import jax

    from_env = bool(os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip())
    if not from_env and jax.default_backend() == "cpu":
        return None
    if not from_env:
        jax.config.update("jax_compilation_cache_dir", cache_root())
    # the sweep and stream programs are many and mostly compile in under
    # jax's 1 s default threshold; together they are the cold start
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # op metadata (the jax.named_scope paths of ops/sweep.py and
    # ops/metrics.py, which name the device ops in a profiler trace) is not
    # part of the cache key by default: a directory filled by a checkout
    # without the scopes would serve executables without their names
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return cache_root()


#: per-device-kind roofline peaks, keyed by ``jax.Device.device_kind``.
#: Only kinds with a cited source; ``chip_smoke.py`` looks its device up
#: here, so each key is checked against the real device string.
#: "TPU v5 lite" (v5e): 197 TFLOP/s bf16 (our kernels run f32, so
#: utilization against it is conservative), 16 GB HBM at 819 GB/s — Google
#: Cloud documentation, "TPU v5e".
PEAK_FLOPS = {"TPU v5 lite": 197e12}
PEAK_HBM_GBPS = {"TPU v5 lite": 819.0}


def device_peaks(device_kind: Optional[str] = None,
                 platform: Optional[str] = None) -> dict:
    """Roofline peaks for a ``device_kind``: {"peak_flops", "peak_hbm_gbps"}.

    ``TMOG_PEAK_FLOPS`` / ``TMOG_PEAK_HBM_GBPS`` override either entry (the
    off-TPU calibration knobs).  A TPU kind that is neither in the table nor
    overridden is an error — the ledger would otherwise read the missing
    roof as "launch-bound".  Off the TPU (CPU hosts, no kind) the values are
    None.  Pure table + env lookup: safe to call without initializing JAX.
    """
    from . import env as _env

    kind = device_kind or ""
    pf = _env.env_float("TMOG_PEAK_FLOPS", 0.0) or PEAK_FLOPS.get(kind)
    bw = _env.env_float("TMOG_PEAK_HBM_GBPS", 0.0) or PEAK_HBM_GBPS.get(kind)
    if (platform == "tpu" or kind.startswith("TPU")) and not (pf and bw):
        raise KeyError(
            f"no roofline peaks for device kind {device_kind!r}: add it to "
            "utils/backend.py with its source (or set TMOG_PEAK_FLOPS / "
            "TMOG_PEAK_HBM_GBPS)")
    return {"peak_flops": float(pf) if pf else None,
            "peak_hbm_gbps": float(bw) if bw else None}
