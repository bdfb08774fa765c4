"""Threaded native argsort for the big host-side prep sorts.

``lexsort_pairs(major, minor)`` == ``np.lexsort((minor, major))`` (sort by
major, ties by minor, stable) but runs the threaded C++ radix sort in
``native/sortperm.cpp`` when it can be built and the keys are non-negative
int64 — the routing/tiling prep's dominant cost at 1e7+ nnz. Falls back to
``np.lexsort`` transparently (negative keys, no toolchain, tiny inputs).
"""

from __future__ import annotations

import ctypes
import logging
from pathlib import Path
from typing import Optional

import numpy as np

from photon_ml_tpu.utils.nativelib import build_and_load

logger = logging.getLogger(__name__)

_NATIVE_DIR = Path(__file__).resolve().parent.parent / "native"
_SRC = _NATIVE_DIR / "sortperm.cpp"

_lib: Optional[ctypes.CDLL] = None
_lib_tried = False

# below this the fallback's constant factors win and threading is noise
_MIN_NATIVE = 1 << 16


def _load_native():
    global _lib, _lib_tried
    if _lib_tried:
        return _lib
    _lib_tried = True
    lib = build_and_load(_SRC)
    if lib is not None:
        lib.argsort_pairs.restype = ctypes.c_int
        lib.argsort_pairs.argtypes = [
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int,
        ]
    _lib = lib
    return _lib


def lexsort_pairs(major: np.ndarray, minor: Optional[np.ndarray] = None) -> np.ndarray:
    """Stable argsort by (major, minor); equivalent to
    ``np.lexsort((minor, major))`` / ``np.argsort(major, kind="stable")``."""
    major = np.ascontiguousarray(major, dtype=np.int64)
    n = major.shape[0]
    use_native = n >= _MIN_NATIVE and (n == 0 or major.min() >= 0)
    if minor is not None:
        minor = np.ascontiguousarray(minor, dtype=np.int64)
        if minor.shape[0] != n:
            raise ValueError(
                f"minor key length {minor.shape[0]} != major length {n}"
            )
        use_native = use_native and (n == 0 or minor.min() >= 0)
    if use_native:
        lib = _load_native()
        if lib is not None:
            import os

            out = np.empty(n, dtype=np.int64)
            i64p = ctypes.POINTER(ctypes.c_int64)
            rc = lib.argsort_pairs(
                ctypes.c_int64(n),
                major.ctypes.data_as(i64p),
                minor.ctypes.data_as(i64p) if minor is not None else None,
                out.ctypes.data_as(i64p),
                ctypes.c_int(max(1, min(os.cpu_count() or 1, 16))),
            )
            if rc == 0:
                return out
            logger.warning("native argsort_pairs rc=%d; numpy fallback", rc)
    if minor is None:
        return np.argsort(major, kind="stable")
    return np.lexsort((minor, major))
