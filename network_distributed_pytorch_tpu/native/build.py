"""Compile + load the native runtime (g++ → .so, ctypes).

Built once per source hash into ``_build/`` beside this file; concurrent
builders race benignly (compile to a temp name, atomic rename).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
from typing import Optional

_SRC = os.path.join(os.path.dirname(__file__), "dataloader.cpp")
_BUILD_DIR = os.path.join(os.path.dirname(__file__), "_build")
_lib: Optional[ctypes.CDLL] = None
_load_attempted = False
_load_refused = ""  # why the numpy tier is feeding this process, if it is


# Portable flags on purpose: -march=native would bake host ISA into a .so
# that is cached beside the source and may be shared across machines (image
# builds, NFS) — SIGILL on a lesser host. -O3 auto-vectorizes for the
# baseline ISA; the kernels are memory-bound anyway.
_CXX_FLAGS = ["-O3", "-std=c++17", "-shared", "-fPIC", "-pthread"]


def _so_path() -> str:
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    h.update(" ".join(_CXX_FLAGS).encode())  # flag changes invalidate cache
    return os.path.join(_BUILD_DIR, f"ndp_native_{h.hexdigest()[:16]}.so")


def _compile(so_path: str) -> None:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    fd, tmp = tempfile.mkstemp(suffix=".so", dir=_BUILD_DIR)
    os.close(fd)
    cmd = ["g++", *_CXX_FLAGS, _SRC, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
        os.replace(tmp, so_path)
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise


def load_library() -> Optional[ctypes.CDLL]:
    """The loaded native library, building it if needed; None when disabled
    (``NDP_TPU_NO_NATIVE=1``) or the toolchain/build is unavailable — the
    callers then run their numpy tier, and :func:`host_data_tier` says so."""
    global _lib, _load_attempted, _load_refused
    if _lib is not None:
        return _lib
    if _load_attempted:
        return None
    _load_attempted = True
    if os.environ.get("NDP_TPU_NO_NATIVE") == "1":
        _load_refused = "NDP_TPU_NO_NATIVE=1"
        return None
    try:
        so = _so_path()
        if not os.path.exists(so):
            _compile(so)
        lib = ctypes.CDLL(so)
        _declare(lib)
        _lib = lib
    except (OSError, subprocess.CalledProcessError) as e:
        _load_refused = f"{type(e).__name__}: {e}"[:200]
        return None
    return _lib


def native_available() -> bool:
    return load_library() is not None


def host_data_tier() -> str:
    """Which host data tier has fed this process so far, for run summaries:
    ``"native"`` (the C++ library loaded), ``"numpy (<why>)"`` (a load was
    refused and the numpy fallbacks ran), or ``"numpy (native not
    requested)"`` when nothing asked for the library. Never triggers a
    build itself."""
    if _lib is not None:
        return "native"
    return f"numpy ({_load_refused or 'native not requested'})"


def _declare(lib: ctypes.CDLL) -> None:
    c = ctypes
    lib.ndp_decode_cifar10_bin.argtypes = [
        c.c_void_p, c.c_int64, c.c_float, c.c_float, c.c_void_p, c.c_void_p,
        c.c_int,
    ]
    lib.ndp_decode_cifar10_bin.restype = None
    lib.ndp_gather_normalize_u8.argtypes = [
        c.c_void_p, c.c_void_p, c.c_int64, c.c_int64, c.c_float, c.c_float,
        c.c_void_p, c.c_int,
    ]
    lib.ndp_gather_normalize_u8.restype = None
    lib.ndp_gather_f32.argtypes = [
        c.c_void_p, c.c_void_p, c.c_int64, c.c_int64, c.c_void_p, c.c_int,
    ]
    lib.ndp_gather_f32.restype = None
    lib.ndp_gather_i32.argtypes = list(lib.ndp_gather_f32.argtypes)
    lib.ndp_gather_i32.restype = None
    lib.ndp_loader_create.argtypes = [
        c.c_void_p, c.c_void_p, c.c_void_p, c.c_int64, c.c_int64, c.c_float,
        c.c_float, c.c_void_p, c.c_int64, c.c_int64, c.c_int64, c.c_int,
    ]
    lib.ndp_loader_create.restype = c.c_void_p
    lib.ndp_loader_next.argtypes = [c.c_void_p, c.c_void_p, c.c_void_p]
    lib.ndp_loader_next.restype = c.c_int
    lib.ndp_loader_destroy.argtypes = [c.c_void_p]
    lib.ndp_loader_destroy.restype = None
    lib.ndp_loader_stats.argtypes = [c.c_void_p, c.POINTER(c.c_longlong)]
    lib.ndp_loader_stats.restype = None
    lib.ndp_tokenize_hash.argtypes = [
        c.c_void_p, c.c_void_p, c.c_int64, c.c_int32, c.c_int32, c.c_int,
        c.c_void_p, c.c_void_p,
    ]
    lib.ndp_tokenize_hash.restype = None
    lib.ndp_wordpiece_build.argtypes = [c.c_void_p, c.c_void_p, c.c_int64]
    lib.ndp_wordpiece_build.restype = c.c_void_p
    lib.ndp_wordpiece_free.argtypes = [c.c_void_p]
    lib.ndp_wordpiece_free.restype = None
    lib.ndp_wordpiece_encode.argtypes = [
        c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p, c.c_int64,
        c.c_int32, c.c_int32, c.c_int32, c.c_int32, c.c_int32, c.c_int,
        c.c_void_p, c.c_void_p,
    ]
    lib.ndp_wordpiece_encode.restype = None
    lib.ndp_wordpiece_encode_ascii.argtypes = [
        c.c_void_p, c.c_void_p, c.c_void_p, c.c_int64,
        c.c_int32, c.c_int32, c.c_int32, c.c_int32, c.c_int32, c.c_int32,
        c.c_int, c.c_void_p, c.c_void_p,
    ]
    lib.ndp_wordpiece_encode_ascii.restype = None
