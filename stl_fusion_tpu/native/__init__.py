"""Native runtime components (C++ via ctypes).

The performance-critical host-side pieces of the framework — where the
reference leans on the .NET runtime's optimized primitives, this build uses
C++ compiled on first use (g++ is in the image; no pip/pybind needed):

- ``graphpack``: the ELL graph packer and topo leveller feeding the mirror
  builders (counting-sort degree bounding; ~10x the numpy path at 10M nodes).

Every native entry point has a numpy fallback — ``load_graphpack()``
returning None means "use the Python path", never a hard failure.
"""
from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading

log = logging.getLogger("stl_fusion_tpu")

__all__ = [
    "load_graphpack",
    "native_build_ell",
    "native_topo_levels",
]

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "graphpack.cpp")
_lock = threading.Lock()
_lib = None
_lib_failed = False


def _lib_path() -> str:
    # Content-keyed path: a source change produces a NEW .so path, so a
    # stale cached library can never be picked up, and we never need to
    # dlopen the same path twice (glibc dedupes dlopen by path, which would
    # silently return the old mapping instead of the rebuilt one).
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_DIR, f"_graphpack_{digest}.so")


def _compile(lib_path: str) -> bool:
    # no -march=native: a cached .so must run on any host this package is
    # copied to (counting sorts are memory-bound; vector ISA gains nothing)
    tmp = lib_path + f".tmp{os.getpid()}"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-o", tmp, _SRC]
    try:
        result = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired) as e:
        log.warning("graphpack native compile unavailable: %s", e)
        return False
    if result.returncode != 0:
        log.warning("graphpack native compile failed:\n%s", result.stderr[-2000:])
        return False
    os.replace(tmp, lib_path)  # atomic: concurrent processes race safely
    return True


def load_graphpack():
    """The ctypes lib, compiling on first use; None → use the numpy path."""
    global _lib, _lib_failed
    if _lib is not None or _lib_failed:
        return _lib
    with _lock:
        if _lib is not None or _lib_failed:
            return _lib
        lib_path = _lib_path()
        if not os.path.exists(lib_path):
            if not _compile(lib_path):
                _lib_failed = True
                return None
        try:
            lib = ctypes.CDLL(lib_path)
        except OSError as e:
            log.warning("graphpack load failed: %s", e)
            _lib_failed = True
            return None
        lib.gp_n_tot.restype = ctypes.c_int64
        lib.gp_n_tot.argtypes = [ctypes.c_void_p]
        lib.gp_free.restype = None
        lib.gp_free.argtypes = [ctypes.c_void_p]
        lib.gp_topo_levels.restype = ctypes.c_int32
        lib.gp_topo_levels.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32, ctypes.c_void_p,
        ]
        lib.gp_build_ell.restype = ctypes.c_void_p
        lib.gp_build_ell.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int, ctypes.c_int32,
        ]
        lib.gp_fill_out.restype = ctypes.c_int32
        lib.gp_fill_out.argtypes = [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int32]
        _lib = lib
        return _lib


def native_topo_levels(in_src, n: int, k: int):
    """Kahn longest-path levels over a packed in-ELL table, or None → fallback.

    ``in_src`` is int32[(n+1), k] (row d's in-neighbors, entries >= n are
    pads); returns int32[n] with level[d] = 1 + max(level of in-neighbors).
    """
    import numpy as np

    lib = load_graphpack()
    if lib is None:
        return None
    in_src = np.ascontiguousarray(in_src, dtype=np.int32)
    level = np.empty(n, dtype=np.int32)
    rc = lib.gp_topo_levels(
        in_src.ctypes.data_as(ctypes.c_void_p), n, k,
        level.ctypes.data_as(ctypes.c_void_p),
    )
    if rc != 0:
        # A cycle is a hard invariant violation of the dependency DAG, not a
        # native-path miss: falling back would grind through the numpy
        # relaxation's full non-convergence loop before failing anyway.
        raise ValueError(f"dependency graph contains a cycle (gp_topo_levels rc={rc})")
    return level


def native_build_ell(src, dst, n_nodes: int, k: int):
    """(ell_dst[(n_tot+1), k], n_tot) bounding OUT-degree at k with virtual
    forwarding trees, via the native packer; None → numpy fallback."""
    import numpy as np

    lib = load_graphpack()
    if lib is None:
        return None
    src = np.ascontiguousarray(src, dtype=np.int32)
    dst = np.ascontiguousarray(dst, dtype=np.int32)
    handle = lib.gp_build_ell(
        src.ctypes.data_as(ctypes.c_void_p),
        dst.ctypes.data_as(ctypes.c_void_p),
        len(src), n_nodes, k, 1,
    )
    try:
        n_tot = lib.gp_n_tot(handle)
        ell_dst = np.empty((n_tot + 1, k), dtype=np.int32)
        rc = lib.gp_fill_out(handle, ell_dst.ctypes.data_as(ctypes.c_void_p), k)
        if rc != 0:
            log.error("graphpack ELL degree bound violated (rc=%d); using numpy path", rc)
            return None
        return ell_dst, int(n_tot)
    finally:
        lib.gp_free(handle)

