"""Native (C++) host-runtime components.

Compiled lazily with the system toolchain into a per-source-hash
shared object and loaded through ctypes (pybind11 is unavailable;
a plain C ABI keeps the binding dependency-free).  Every native entry
point has a numpy fallback in its caller, which a machine WITHOUT a
compiler takes: that is the one routing rule.  Where g++ exists, a
build or load that fails is an error — a scan that quietly decodes
through the slow path is a different program from the one measured.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import shutil
import subprocess
import threading
from typing import Optional

_lib = None
_tried = False
_lock = threading.Lock()


def _build(src: str, out: str) -> None:
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
           src, "-o", out]
    r = subprocess.run(cmd, capture_output=True, timeout=120)
    if r.returncode != 0 or not os.path.exists(out):
        raise RuntimeError(
            f"native host codec failed to build ({' '.join(cmd)}):\n"
            + r.stderr.decode(errors="replace")[-2000:])


def load() -> Optional[ctypes.CDLL]:
    """The host codec library, building it on first use; None when the
    machine has no g++ (callers fall back to numpy).  With g++ present
    a failed build or load raises."""
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        here = os.path.dirname(__file__)
        src = os.path.join(here, "hostcodec.cpp")
        with open(src, "rb") as f:
            tag = hashlib.sha256(f.read()).hexdigest()[:16]
        build_dir = os.path.join(here, "_build")
        out = os.path.join(build_dir, f"hostcodec-{tag}.so")
        if not os.path.exists(out):
            if shutil.which("g++") is None:
                _tried = True
                return None
            os.makedirs(build_dir, exist_ok=True)
            # build beside the target and rename: two processes racing
            # the first build must never load a half-written object
            tmp = f"{out}.{os.getpid()}.tmp"
            _build(src, tmp)
            os.replace(tmp, out)
        lib = ctypes.CDLL(out)
        _declare(lib)
        _lib = lib
        _tried = True
        return _lib


def _declare(lib: ctypes.CDLL) -> None:
    c = ctypes
    lib.chars_fill.argtypes = [c.c_void_p, c.c_void_p, c.c_void_p,
                               c.c_int64, c.c_int64, c.c_void_p]
    lib.chars_fill.restype = None
    for name in ("minmax_i64", "minmax_i32"):
        fn = getattr(lib, name)
        fn.argtypes = [c.c_void_p, c.c_int64, c.c_void_p, c.c_void_p]
        fn.restype = None
    for name in ("bias_encode8_i64", "bias_encode16_i64",
                 "bias_encode8_i32", "bias_encode16_i32"):
        fn = getattr(lib, name)
        fn.argtypes = [c.c_void_p, c.c_int64, c.c_int64, c.c_void_p]
        fn.restype = None
    lib.scaled_check_encode.argtypes = [c.c_void_p, c.c_int64, c.c_void_p]
    lib.scaled_check_encode.restype = ctypes.c_int
    lib.snappy_raw_decompress.argtypes = [c.c_void_p, c.c_int64,
                                          c.c_void_p, c.c_int64]
    lib.snappy_raw_decompress.restype = ctypes.c_int
    lib.rle_unpack_u32.argtypes = [c.c_void_p, c.c_int64, c.c_int,
                                   c.c_void_p, c.c_int64]
    lib.rle_unpack_u32.restype = ctypes.c_int
