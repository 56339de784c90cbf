"""Native runtime components (C++), loaded via ctypes.

The compute path is JAX/XLA/Pallas; the node RUNTIME's hot host-side
ops live here (the reference's equivalents are Rust/C crates).  Builds
are on-demand from the committed ``.cpp`` sources into the checkout's
git-ignored cache root (utils/accel.py CACHE_ROOT) — no binary lives in
the tree; every native component has a pure-Python twin as fallback and
test oracle, and :func:`status` says which one a process got.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from pathlib import Path

from ..utils import accel

_DIR = Path(__file__).resolve().parent
_OUT = accel.CACHE_ROOT / "native"
NAMES = tuple(sorted(p.stem for p in _DIR.glob("*.cpp")))
_LOCK = threading.Lock()
_LIBS: dict[str, ctypes.CDLL | None] = {}


def _build(name: str) -> Path | None:
    src = _DIR / f"{name}.cpp"
    lib = _OUT / f"libsmtpu_{name}.so"
    if lib.exists() and lib.stat().st_mtime >= src.stat().st_mtime:
        return lib
    tmp = lib.with_suffix(".so.tmp%d" % os.getpid())
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-o", str(tmp), str(src)]
    try:
        _OUT.mkdir(parents=True, exist_ok=True)
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        # durable publish (utils/fsio): fsync + atomic rename + dir
        # fsync — a half-flushed .so dlopens as garbage after a crash
        from ..utils import fsio

        fsio.persist(tmp, lib)
        return lib
    except (subprocess.SubprocessError, OSError):
        tmp.unlink(missing_ok=True)
        return None


def load(name: str) -> ctypes.CDLL | None:
    """Compile (if stale) + dlopen libsmtpu_<name>.so; None on any
    failure — callers fall back to their Python twin."""
    with _LOCK:
        if name in _LIBS:
            return _LIBS[name]
        lib_path = _build(name)
        lib = None
        if lib_path is not None:
            try:
                lib = ctypes.CDLL(str(lib_path))
            except OSError:
                lib = None
        _LIBS[name] = lib
        return lib


def status() -> dict[str, bool]:
    """Which native libraries this process runs on (True) and which fell
    back to their Python twin (False) — builds/loads any not yet tried."""
    return {name: load(name) is not None for name in NAMES}
