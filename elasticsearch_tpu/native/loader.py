"""Build-on-demand loader for the native runtime library.

The C++ sources live in native/ at the repo root; the shared library is
compiled once with g++ (cached under native/build/) and loaded with
ctypes. The library's file name carries a hash of its source, so a build
directory copied from elsewhere can never serve a library built from
different source: a changed text_indexer.cpp names a library that does
not exist yet, and it is built. Everything using it falls back to pure
Python when the toolchain or library is unavailable — the native layer
is an accelerator, never a requirement.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
)
_SRC = os.path.join(_NATIVE_DIR, "text_indexer.cpp")

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False


def _build() -> str | None:
    """Path of the library built from the current source, building it if
    no library of that source exists; None when it cannot be built."""
    try:
        with open(_SRC, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
    except OSError:
        return None
    lib_path = os.path.join(
        _NATIVE_DIR, "build", f"libestpu_native-{digest}.so"
    )
    if os.path.exists(lib_path):
        return lib_path
    # Build under a private name, then rename: concurrent builders (test
    # workers) never load a half-written library.
    tmp = f"build/.libestpu_native-{digest}.{os.getpid()}.so"
    try:
        subprocess.run(
            ["make", "-C", _NATIVE_DIR, f"LIB={tmp}"],
            check=True,
            capture_output=True,
            timeout=120,
        )
        os.replace(os.path.join(_NATIVE_DIR, tmp), lib_path)
    except (OSError, subprocess.SubprocessError):
        return None
    return lib_path


def get_lib() -> ctypes.CDLL | None:
    """The loaded native library, building it on first use; None if the
    toolchain/library is unavailable (callers use their Python path)."""
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        if os.environ.get("ESTPU_DISABLE_NATIVE"):
            return None
        lib_path = _build()
        if lib_path is None:
            return None
        try:
            lib = ctypes.CDLL(lib_path)
        except OSError:
            return None
        i64, i32, u8 = (
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_uint8),
        )
        lib.estpu_tokenize_ascii.restype = ctypes.c_int64
        lib.estpu_tokenize_ascii.argtypes = [u8, ctypes.c_int64, u8, i64]
        lib.estpu_acc_create.restype = ctypes.c_void_p
        lib.estpu_acc_create.argtypes = [ctypes.c_int]
        lib.estpu_acc_destroy.argtypes = [ctypes.c_void_p]
        lib.estpu_acc_add.argtypes = [
            ctypes.c_void_p, ctypes.c_int32, u8, i64, i32, ctypes.c_int64,
        ]
        lib.estpu_acc_sizes.argtypes = [ctypes.c_void_p, i64]
        lib.estpu_acc_build.argtypes = [
            ctypes.c_void_p, u8, i64, i32, i64, i32,
            ctypes.POINTER(ctypes.c_float), i64, i32,
        ]
        _lib = lib
        return _lib


def available() -> bool:
    return get_lib() is not None
