"""Build + load the native DES event core (``des_core.c``) via ctypes.

The core is compiled once per source hash with the system C compiler and
cached next to the package in ``_native_cache/`` (gitignored).  No Python
dependencies are added; only ``cc`` (or ``$CC``) is invoked, and only on
first use.  A failed build or load raises with the compiler's output:
callers never fall back silently.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import shutil
import subprocess
import tempfile
import threading
from pathlib import Path

_SRC = Path(__file__).with_name("des_core.c")
_CACHE = Path(__file__).with_name("_native_cache")
_lock = threading.Lock()
_lib: ctypes.CDLL | None = None

_ARGTYPES = [
    ctypes.c_void_p,  # nodes (S,B,H) int32
    ctypes.c_void_p,  # service (S,B,H) float32
    ctypes.c_void_p,  # n_hops (S,B) int32
    ctypes.c_void_p,  # arrivals (S,B) float64 or NULL
    ctypes.c_int64,   # S
    ctypes.c_int64,   # B
    ctypes.c_int64,   # H
    ctypes.c_int64,   # K
    ctypes.c_int64,   # N
    ctypes.c_double,  # link
    ctypes.c_double,  # think
    ctypes.c_int32,   # mode_closed
    ctypes.c_void_p,  # scratch_node_free (N,) f64
    ctypes.c_void_p,  # scratch_hop (B,) i32
    ctypes.c_void_p,  # scratch_heap (B+1,2) f64
    ctypes.c_void_p,  # finish (S,B) f64
    ctypes.c_void_p,  # issue (S,B) f64
    ctypes.c_void_p,  # hop_done (S,B,H) f64 or NULL
]


def _so_path() -> Path:
    tag = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:16]
    return _CACHE / f"des_core_{tag}.so"


def _build(src: Path, out: Path) -> None:
    cc = os.environ.get("CC", "cc")
    fd, tmp = tempfile.mkstemp(suffix=".so", dir=str(out.parent))
    os.close(fd)
    try:
        r = subprocess.run(
            [cc, "-O2", "-shared", "-fPIC", "-o", tmp, str(src)],
            capture_output=True,
            text=True,
            timeout=120,
        )
        if r.returncode != 0:
            raise RuntimeError(
                f"{cc} failed to build {src.name} (exit {r.returncode}):\n"
                f"{r.stderr}"
            )
        os.replace(tmp, out)  # atomic under concurrent builders
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def load() -> ctypes.CDLL:
    """The compiled core.  Raises if it cannot be built or loaded."""
    global _lib
    with _lock:
        if _lib is None:
            so = _so_path()
            if not so.exists():
                _CACHE.mkdir(parents=True, exist_ok=True)
                _build(_SRC, so)
            lib = ctypes.CDLL(str(so))
            lib.des_simulate_batch.restype = None
            lib.des_simulate_batch.argtypes = _ARGTYPES
            _lib = lib
        return _lib


def available() -> bool:
    """Whether the core is built or a C compiler exists to build it."""
    return _so_path().exists() or shutil.which(
        os.environ.get("CC", "cc")) is not None
