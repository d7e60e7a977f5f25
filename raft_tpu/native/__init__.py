"""Native (C++) runtime components with ctypes bindings.

The compute path of this framework is JAX/XLA/Pallas; the runtime around it
— bulk host IO like the hnswlib-format writer — is native C++ like the
reference's, compiled on demand with the system toolchain. The build lands
in ``_build/`` (gitignored) under a name keyed by the source's content hash,
so a library is reused only when it was built from exactly the committed
source — never a stale or copied-in ``.so`` trusted for its mtime. Every
native entry point has a pure-Python fallback so the package works without
a compiler.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

_DIR = os.path.dirname(os.path.abspath(__file__))
_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False


def _build_lib() -> Optional[str]:
    src = os.path.join(_DIR, "hnsw_writer.cpp")
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    out = os.path.join(_DIR, "_build", f"hnsw_writer-{digest}.so")
    if os.path.exists(out):
        return out
    tmp = f"{out}.{os.getpid()}.tmp"  # concurrent builders: rename is atomic
    try:
        os.makedirs(os.path.dirname(out), exist_ok=True)
        subprocess.run(
            ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", src, "-o", tmp],
            check=True, capture_output=True, timeout=120,
        )
        os.replace(tmp, out)
        return out
    except (subprocess.SubprocessError, FileNotFoundError, OSError):
        return None


def get_native_lib() -> Optional[ctypes.CDLL]:
    """The compiled native library, building it on first use; None when no
    toolchain is available (callers fall back to Python)."""
    global _LIB, _TRIED
    with _LOCK:
        if _TRIED:
            return _LIB
        _TRIED = True
        path = _build_lib()
        if path is None:
            return None
        try:
            lib = ctypes.CDLL(path)
            lib.raft_tpu_write_hnsw.restype = ctypes.c_int
            lib.raft_tpu_write_hnsw.argtypes = [
                ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint32,
                ctypes.c_uint32,
                ctypes.POINTER(ctypes.c_uint32), ctypes.POINTER(ctypes.c_float),
                ctypes.c_uint64,
            ]
        except (OSError, AttributeError):
            # stale/foreign-arch cached .so: fall back to pure Python
            return None
        _LIB = lib
        return _LIB
