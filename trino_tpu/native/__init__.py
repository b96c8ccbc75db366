"""Native (C++) runtime kernels, loaded via ctypes.

Built on demand with g++ (baked toolchain) on the machine that runs them and
cached next to the source. Where the build fails, ``native_available()`` is
False, ``load_error()`` says why, and serde uses its pure-Python store codec.
"""

from __future__ import annotations

import contextlib
import ctypes
import glob
import hashlib
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False
_ERROR: Optional[str] = None


def _machine() -> bytes:
    """This boot of this host. Part of the binary's key because the build
    uses -march=native and a checkout's disk gets copied between hosts."""
    with open("/proc/sys/kernel/random/boot_id", "rb") as f:
        return f.read().strip()


def _build_and_load() -> Optional[ctypes.CDLL]:
    # The binary is keyed by a content hash of the source and the machine, so
    # a stale, committed or copied-in .so is never dlopen'd: it is rebuilt
    # here from the reviewed source (*.so is gitignored).
    global _ERROR
    here = os.path.dirname(__file__)
    src = os.path.join(here, "pageserde.cpp")
    try:
        with open(src, "rb") as f:
            digest = hashlib.sha256(f.read() + _machine()).hexdigest()[:16]
        out = os.path.join(here, f"_pageserde-{digest}.so")
        if not os.path.exists(out):
            tmp = out + f".tmp{os.getpid()}"
            subprocess.run(
                ["g++", "-O3", "-march=native", "-shared", "-fPIC", "-o", tmp, src],
                check=True,
                capture_output=True,
            )
            os.replace(tmp, out)
            for stale in glob.glob(os.path.join(here, "_pageserde-*.so")):
                if stale != out:
                    with contextlib.suppress(FileNotFoundError):
                        os.unlink(stale)  # a sibling process got there first
        lib = ctypes.CDLL(out)
    except subprocess.CalledProcessError as e:
        _ERROR = f"g++ failed: {e.stderr.decode(errors='replace').strip()}"
        return None
    except OSError as e:
        _ERROR = f"{type(e).__name__}: {e}"
        return None
    lib.lz4_compress.restype = ctypes.c_int64
    lib.lz4_compress.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64,
    ]
    lib.lz4_decompress.restype = ctypes.c_int64
    lib.lz4_decompress.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64,
    ]
    lib.lz4_max_compressed.restype = ctypes.c_int64
    lib.lz4_max_compressed.argtypes = [ctypes.c_int64]
    lib.hash64.restype = ctypes.c_uint64
    lib.hash64.argtypes = [ctypes.c_char_p, ctypes.c_int64]
    return lib


def get_lib() -> Optional[ctypes.CDLL]:
    global _LIB, _TRIED
    with _LOCK:
        if not _TRIED:
            _LIB = _build_and_load()
            _TRIED = True
        return _LIB


def native_available() -> bool:
    return get_lib() is not None


def load_error() -> Optional[str]:
    """Why the codec is unavailable (None while it is, or before a load)."""
    return _ERROR


def lz4_compress(data: bytes) -> bytes:
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native serde not available")
    n = len(data)
    cap = lib.lz4_max_compressed(n)
    dst = ctypes.create_string_buffer(cap)
    written = lib.lz4_compress(data, n, dst, cap)
    if written < 0:
        raise RuntimeError("lz4_compress failed")
    return dst.raw[:written]


def lz4_decompress(data: bytes, raw_len: int) -> bytes:
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native serde not available")
    dst = ctypes.create_string_buffer(raw_len)
    written = lib.lz4_decompress(data, len(data), dst, raw_len)
    if written != raw_len:
        raise ValueError(f"lz4_decompress: corrupt frame ({written} != {raw_len})")
    return dst.raw


def hash64(data: bytes) -> int:
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native serde not available")
    return int(lib.hash64(data, len(data)))
