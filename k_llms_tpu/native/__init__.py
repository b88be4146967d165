"""ctypes bindings for the native C++ scalar kernels.

The reference leans on two native wheels for its scalar hot loops: the
python-Levenshtein C extension and scipy's Hungarian solver
(`/root/reference/k_llms/utils/consensus_utils.py:15,20,372,759`). Here both are
first-party C++ (``levenshtein.cpp``, ``hungarian.cpp``) compiled to one shared
library and bound via ctypes — no pybind11 dependency. The library is a build
product, not a source file: it is git-ignored and compiled from the ``.cpp``
files with ``make`` on first import (and again whenever a source is newer
than it). Pure-Python fallbacks keep the package usable where there is no
toolchain; :func:`native_status` says which one is serving, so a health check
can tell a fast deployment from a degraded one.

These stay host-side on purpose: inputs are tiny (n <= 32 samples, short strings),
so the TPU/MXU has no role here — see SURVEY.md §2.3.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Any, Dict, Optional, Sequence

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_DIR, "libkllms_native.so")
_SOURCES = ("levenshtein.cpp", "hungarian.cpp", "Makefile")

_lib: Optional[ctypes.CDLL] = None
_attempted = False
_load_error: Optional[str] = None


def _stale() -> bool:
    """Is the library missing, or older than a file it is built from?"""
    try:
        built = os.path.getmtime(_LIB_PATH)
    except OSError:
        return True
    return any(
        os.path.getmtime(os.path.join(_DIR, name)) > built for name in _SOURCES
    )


def build(quiet: bool = True) -> bool:
    """Compile the shared library in-place (the Makefile writes a temporary
    file and renames it, so a concurrent importer never loads half a
    library). Returns True on success; the reason for a failure is kept for
    :func:`native_status`."""
    global _load_error
    try:
        subprocess.run(
            ["make", "-C", _DIR],
            check=True,
            capture_output=quiet,
            timeout=120,
        )
    except (OSError, subprocess.SubprocessError) as e:
        detail = getattr(e, "stderr", None) or b""
        _load_error = f"build failed: {e!r} {detail[-300:]!r}"
        return False
    return os.path.exists(_LIB_PATH)


def _load() -> Optional[ctypes.CDLL]:
    """The library, built and loaded at most once per process: a failed
    attempt is remembered (the fallbacks serve from then on) rather than
    re-running ``make`` on every distance call."""
    global _lib, _load_error, _attempted
    if _lib is not None or _attempted:
        return _lib
    _attempted = True
    if _stale():
        build(quiet=True)  # on failure an older library, if any, still serves
    if not os.path.exists(_LIB_PATH):
        return None
    try:
        lib = ctypes.CDLL(_LIB_PATH)
    except OSError as e:
        _load_error = f"load failed: {e}"
        return None

    lib.kllms_levenshtein.restype = ctypes.c_int64
    lib.kllms_levenshtein.argtypes = [
        ctypes.POINTER(ctypes.c_uint32),
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_uint32),
        ctypes.c_int64,
    ]
    lib.kllms_linear_sum_assignment.restype = ctypes.c_int
    lib.kllms_linear_sum_assignment.argtypes = [
        ctypes.POINTER(ctypes.c_double),
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64),
    ]
    _lib = lib
    _load_error = None
    return lib


def native_available() -> bool:
    return _load() is not None


def native_status() -> Dict[str, Any]:
    """``{"loaded": bool, "error": str | None}`` — whether the C++ kernels
    (True) or the pure-Python fallbacks (False, with why) are serving."""
    loaded = _load() is not None
    return {"loaded": loaded, "error": None if loaded else _load_error}


def _to_u32(s: str) -> np.ndarray:
    return np.frombuffer(s.encode("utf-32-le"), dtype=np.uint32)


def levenshtein_distance(s1: str, s2: str) -> int:
    """Edit distance between two strings (code-point level)."""
    lib = _load()
    if lib is not None:
        a = _to_u32(s1)
        b = _to_u32(s2)
        ap = a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)) if a.size else ctypes.POINTER(ctypes.c_uint32)()
        bp = b.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)) if b.size else ctypes.POINTER(ctypes.c_uint32)()
        return int(lib.kllms_levenshtein(ap, a.size, bp, b.size))
    return _levenshtein_py(s1, s2)


def _levenshtein_py(s1: str, s2: str) -> int:
    if len(s1) < len(s2):
        s1, s2 = s2, s1
    if not s2:
        return len(s1)
    prev = list(range(len(s2) + 1))
    for i, ca in enumerate(s1, 1):
        cur = [i]
        for j, cb in enumerate(s2, 1):
            cur.append(min(prev[j] + 1, cur[j - 1] + 1, prev[j - 1] + (ca != cb)))
        prev = cur
    return prev[-1]


def linear_sum_assignment(cost: Sequence[Sequence[float]] | np.ndarray):
    """Minimum-cost assignment; same contract as scipy.optimize.linear_sum_assignment."""
    c = np.ascontiguousarray(cost, dtype=np.float64)
    if c.ndim != 2:
        raise ValueError("cost matrix must be 2-D")
    nr, nc = c.shape
    k = min(nr, nc)
    if k == 0:
        return np.empty(0, dtype=np.int64), np.empty(0, dtype=np.int64)
    lib = _load()
    if lib is not None and np.isfinite(c).all():
        row = np.empty(k, dtype=np.int64)
        col = np.empty(k, dtype=np.int64)
        rc = lib.kllms_linear_sum_assignment(
            c.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            nr,
            nc,
            row.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            col.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        )
        if rc == 0:
            return row, col
    try:  # scipy fallback (also handles +inf entries)
        from scipy.optimize import linear_sum_assignment as _scipy_lsa  # type: ignore

        return _scipy_lsa(c)
    except ImportError:
        return _lsa_py(c)


def _lsa_py(c: np.ndarray):
    """Brute-ish pure-Python augmenting-path LSAP fallback."""
    nr, nc = c.shape
    transposed = nr > nc
    if transposed:
        c = c.T
        nr, nc = c.shape
    INF = float("inf")
    u = [0.0] * (nr + 1)
    v = [0.0] * (nc + 1)
    p = [0] * (nc + 1)  # p[j] = row assigned to col j (1-indexed)
    way = [0] * (nc + 1)
    for i in range(1, nr + 1):
        p[0] = i
        j0 = 0
        minv = [INF] * (nc + 1)
        used = [False] * (nc + 1)
        while True:
            used[j0] = True
            i0 = p[j0]
            delta = INF
            j1 = -1
            for j in range(1, nc + 1):
                if used[j]:
                    continue
                cur = c[i0 - 1][j - 1] - u[i0] - v[j]
                if cur < minv[j]:
                    minv[j] = cur
                    way[j] = j0
                if minv[j] < delta:
                    delta = minv[j]
                    j1 = j
            for j in range(nc + 1):
                if used[j]:
                    u[p[j]] += delta
                    v[j] -= delta
                else:
                    minv[j] -= delta
            j0 = j1
            if p[j0] == 0:
                break
        while j0:
            j1 = way[j0]
            p[j0] = p[j1]
            j0 = j1
    pairs = sorted((p[j] - 1, j - 1) for j in range(1, nc + 1) if p[j] != 0)
    row = np.array([r for r, _ in pairs], dtype=np.int64)
    col = np.array([j for _, j in pairs], dtype=np.int64)
    if transposed:
        order = np.argsort(col, kind="stable")
        return col[order], row[order]
    return row, col


# Build/load once at import; where the toolchain is absent the fallbacks serve
# and native_status() records why.
_load()
