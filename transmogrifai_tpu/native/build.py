"""Build + load the native C++ kernel library.

Compiles ``src/*.cpp`` with g++ -O3 into ``_libtransmog.<stamp>.so`` next
to this file.  The stamp hashes the sources, the compile command and the
machine's name, so a library is only ever loaded by the machine that built
it from the sources it sits beside; anything else is rebuilt (about a
second).  No ``-march=native``: the tree is copied between machines, and a
library tuned to one host's CPU can die with SIGILL on another.  Failures
(no toolchain, sandboxed env) degrade to ``None`` and the Python fallbacks
take over.
"""
from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import platform
import subprocess
import sys
import tempfile
from typing import List, Optional

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC_DIR = os.path.join(_DIR, "src")
_CMD = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC"]


def _sources() -> List[str]:
    if not os.path.isdir(_SRC_DIR):
        return []
    return [os.path.join(_SRC_DIR, n) for n in sorted(os.listdir(_SRC_DIR))
            if n.endswith((".cpp", ".h"))]


def _stamp(sources: List[str]) -> str:
    h = hashlib.sha256()
    for part in (*_CMD, platform.machine(), platform.node()):
        h.update(part.encode() + b"\x00")
    for path in sources:
        h.update(os.path.basename(path).encode() + b"\x00")
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:16]


def build(verbose: bool = False) -> Optional[str]:
    """Compile the native library; returns its path or None on failure."""
    sources = _sources()
    cpp = [p for p in sources if p.endswith(".cpp")]
    if not cpp:
        return None
    lib_path = os.path.join(_DIR, f"_libtransmog.{_stamp(sources)}.so")
    if os.path.exists(lib_path):
        return lib_path
    # build beside the target and rename: concurrent importers (test
    # workers) never see a half-written library
    fd, tmp = tempfile.mkstemp(dir=_DIR, prefix="_libtransmog.",
                               suffix=".tmp")
    os.close(fd)
    try:
        try:
            res = subprocess.run(_CMD + ["-o", tmp] + cpp,
                                 capture_output=True, text=True, timeout=120)
        except (OSError, subprocess.TimeoutExpired):
            return None
        if res.returncode != 0:
            if verbose:
                print(f"native build failed:\n{res.stderr}", file=sys.stderr)
            return None
        os.replace(tmp, lib_path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    for stale in glob.glob(os.path.join(_DIR, "_libtransmog*.so")):
        if stale != lib_path:
            try:
                os.unlink(stale)
            except OSError:
                pass
    return lib_path


def load_native() -> Optional[ctypes.CDLL]:
    """Build if needed and dlopen; configure ctypes signatures."""
    if os.environ.get("TRANSMOG_NO_NATIVE"):
        return None
    try:
        path = build()
    except OSError:  # read-only install dir
        return None
    if path is None:
        return None
    try:
        lib = ctypes.CDLL(path)
    except OSError:
        return None
    try:
        lib.tm_murmur3_32.argtypes = [ctypes.c_char_p, ctypes.c_size_t, ctypes.c_uint32]
        lib.tm_murmur3_32.restype = ctypes.c_uint32
    except AttributeError:
        return None
    return lib
