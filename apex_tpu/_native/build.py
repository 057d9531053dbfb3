"""Build + load the native helper library (apex_tpu/_csrc/apex_tpu_native.cpp).

No pybind11 in this image → plain C ABI + ctypes. Compiled lazily on first
use with g++; failures degrade to the pure-Python paths (native is an
accelerator, never a requirement — unlike the reference, where a missing
extension disables the feature, setup.py:24-46).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

_LIB = None
_TRIED = False
_LOCK = threading.Lock()

_SRC = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                    "_csrc", "apex_tpu_native.cpp")
_OUT = os.path.join(os.path.dirname(__file__), "_apex_tpu_native.so")


def _compile() -> str | None:
    """The library built from ``_csrc/`` (rebuilt when the source is
    newer), or None for the Python paths. A ``.so`` whose source is
    missing is NOT accepted: it could not be told apart from a stale
    build of other code."""
    try:
        if os.path.getmtime(_OUT) >= os.path.getmtime(_SRC):
            return _OUT
    except OSError:  # no .so yet (build it) or no source (the build fails)
        pass
    try:
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
             _SRC, "-o", _OUT],
            check=True, capture_output=True, timeout=120)
        return _OUT
    except (OSError, subprocess.SubprocessError):
        return None


def get_lib():
    """Returns the loaded ctypes library or None (Python fallback)."""
    global _LIB, _TRIED
    with _LOCK:
        if _TRIED:
            return _LIB
        _TRIED = True
        path = _compile()
        if path is None:
            return None
        try:
            lib = ctypes.CDLL(path)
        except OSError:
            return None
        i64p = ctypes.POINTER(ctypes.c_int64)
        i32p = ctypes.POINTER(ctypes.c_int32)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        u8pp = ctypes.POINTER(ctypes.c_void_p)
        lib.plan_flat.restype = ctypes.c_int64
        lib.plan_flat.argtypes = [i64p, ctypes.c_int64, ctypes.c_int64,
                                  i64p, i64p]
        lib.plan_buckets.restype = ctypes.c_int64
        lib.plan_buckets.argtypes = [i64p, i32p, ctypes.c_int64,
                                     ctypes.c_int64, i32p]
        lib.pack_bytes.restype = None
        lib.pack_bytes.argtypes = [u8pp, i64p, i64p, ctypes.c_int64,
                                   u8p, ctypes.c_int32]
        lib.unpack_bytes.restype = None
        lib.unpack_bytes.argtypes = [u8p, i64p, i64p, ctypes.c_int64,
                                     u8pp, ctypes.c_int32]
        lib.plan_fragments.restype = ctypes.c_int64
        lib.plan_fragments.argtypes = [i64p, i64p, ctypes.c_int64,
                                       ctypes.c_int64, i32p, i32p, i64p,
                                       i64p, i64p]
        _LIB = lib
        return _LIB


def native_available() -> bool:
    return get_lib() is not None


def native_status() -> str:
    """Which implementation this process uses, for run logs."""
    if native_available():
        return f"built from {os.path.relpath(_SRC, os.path.dirname(_OUT))}"
    return "python path (no g++, no source, or the build failed)"
