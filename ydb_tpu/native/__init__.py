"""Native runtime loader.

Builds `blobio.cpp` into a shared library with the system toolchain on
first import and exposes it through ctypes. The built library is keyed on
a hash of the source (`_blobio_py<ver>_<sha256[:16]>.so`), so what is
loaded was built from the `blobio.cpp` beside it — file times say nothing
in a copied or checked-out tree. The reference's storage runtime is
native C++ (PDisk/LocalDB); here the native layer owns the blob/WAL IO
floor while JAX/XLA owns the compute plane. If no compiler is present (or
``YDB_TPU_NATIVE=0``), callers fall back to the byte-identical numpy
implementation in `ydb_tpu/storage/blobfile.py`; `available()` says which
one is in use, and `chip_smoke.py` fails when it is the fallback on a
machine that has `g++`.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import sys

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "blobio.cpp")
_STEM = os.path.join(
    _DIR, f"_blobio_py{sys.version_info[0]}{sys.version_info[1]}")

_lib = None
_tried = False


def _so_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return f"{_STEM}_{digest}.so"


def _build() -> str:
    """Path of the library built from the current source, or "" when it
    cannot be built."""
    try:
        so = _so_path()
        if os.path.exists(so):
            return so
        tmp = f"{so}.{os.getpid()}.tmp.so"    # per-pid: concurrent builds
        subprocess.run(                        # must not interleave writes
            ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-o", tmp,
             _SRC],
            check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)
        for old in glob.glob(f"{_STEM}*.so"):  # builds of other sources
            if old != so and ".tmp." not in old:
                try:
                    os.remove(old)
                except OSError:
                    pass
        return so
    except Exception:                          # noqa: BLE001 — no toolchain
        return ""


def lib():
    """The loaded native library, or None when unavailable."""
    global _lib, _tried
    if _tried:
        return _lib
    _tried = True
    if os.environ.get("YDB_TPU_NATIVE", "1") == "0":
        return None
    so = _build()
    if not so:
        return None
    try:
        L = ctypes.CDLL(so)
        L.ydbt_abi_version.restype = ctypes.c_int
        if L.ydbt_abi_version() != 2:
            return None
        L.ydbt_crc32.restype = ctypes.c_uint32
        L.ydbt_crc32.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
        L.ydbt_write_portion.restype = ctypes.c_int
        L.ydbt_write_portion.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_uint64,
            ctypes.c_int32, ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_uint64)]
        L.ydbt_wal_append.restype = ctypes.c_int
        L.ydbt_wal_append.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_uint64,
            ctypes.c_int32]
        L.ydbt_wal_scan.restype = ctypes.c_int64
        L.ydbt_wal_scan.argtypes = [ctypes.c_char_p, ctypes.c_uint64,
                                    ctypes.POINTER(ctypes.c_uint64),
                                    ctypes.POINTER(ctypes.c_int32)]
        _lib = L
    except OSError:
        _lib = None
    return _lib


def available() -> bool:
    return lib() is not None
