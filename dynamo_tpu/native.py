"""Loader for the native (C++) components under csrc/.

SURVEY §2.4: where the reference runs native code (Rust `lib/tokens`,
the indexer's block hashing), we ship C++ — not Python stand-ins.  The
shared library is compiled on first use with the system g++ (the image's
baked toolchain); if compilation fails the pure-Python implementations
keep working and a warning records the degradation.

Binding is ctypes (no pybind11 in the image); the ABI is the short
extern-C surface of csrc/block_hash.cpp.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional

import numpy as np

logger = logging.getLogger(__name__)

CSRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "csrc")
BUILD_DIR = os.path.join(CSRC, "build")
SOURCES = ("block_hash.cpp", os.path.join("vendor", "xxhash.h"))

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def lib_path() -> str:
    """Where the library built from the CURRENT sources lives: the name
    carries a hash of them, so a library left on disk by an older
    checkout (csrc/build/ is untracked, and a copied tree brings it
    along) is never loaded in place of a rebuild."""
    digest = hashlib.sha256()
    for rel in SOURCES:
        with open(os.path.join(CSRC, rel), "rb") as f:
            digest.update(f.read())
    return os.path.join(BUILD_DIR,
                        f"libblockhash-{digest.hexdigest()[:16]}.so")


def _compile(out: str) -> bool:
    os.makedirs(BUILD_DIR, exist_ok=True)
    # Compile to a process-unique temp name, then rename atomically:
    # several processes on one host may race to build the shared path,
    # and CDLL-ing a half-written .so is a crash, not an error.
    tmp = f"{out}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-o", tmp,
           os.path.join(CSRC, SOURCES[0])]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, out)
        return True
    except (subprocess.CalledProcessError, subprocess.TimeoutExpired,
            FileNotFoundError, OSError) as e:
        detail = getattr(e, "stderr", b"")
        logger.warning("native block-hash build failed (%s); using the "
                       "Python path: %s", e,
                       detail.decode()[:500] if detail else "")
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False


def backend() -> str:
    """Which block-hash implementation this process uses (builds and
    loads the library if that has not been tried yet)."""
    return "native (%s)" % lib_path() if get_lib() is not None else "python"


async def warmup() -> bool:
    """Build/load the native library OFF the event loop.  Server
    entrypoints call this before serving: the lazy first-use build would
    otherwise run a multi-second g++ on the loop thread mid-request,
    freezing streams and lease keep-alives."""
    import asyncio

    ok = await asyncio.to_thread(lambda: get_lib() is not None)
    logger.info("block hashing: %s", backend())
    return ok


def get_lib() -> Optional[ctypes.CDLL]:
    """The loaded native library, building it on first call; None when
    unavailable (callers fall back to Python)."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        path = lib_path()
        if not os.path.exists(path) and not _compile(path):
            return None
        try:
            lib = ctypes.CDLL(path)
        except OSError as e:
            logger.warning("native block-hash load failed: %s", e)
            return None
        lib.chained_block_hashes.restype = ctypes.c_int64
        lib.chained_block_hashes.argtypes = [
            ctypes.POINTER(ctypes.c_uint32), ctypes.c_int64,
            ctypes.c_int64, ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint64)]
        lib.hash_one_block.restype = ctypes.c_uint64
        lib.hash_one_block.argtypes = [
            ctypes.POINTER(ctypes.c_uint32), ctypes.c_int64,
            ctypes.c_uint64]
        _lib = lib
        return _lib


def chained_block_hashes(tokens_u32: np.ndarray, block_size: int,
                         parent: int) -> Optional[np.ndarray]:
    """Native chained hashing; returns uint64 hashes for full blocks, or
    None when the native path is unavailable.  `tokens_u32` must already
    be a contiguous uint32 array (tokens._as_u32 output)."""
    lib = get_lib()
    if lib is None:
        return None
    arr = np.ascontiguousarray(tokens_u32, dtype=np.uint32)
    n_full = len(arr) // block_size
    out = np.empty((n_full,), np.uint64)
    got = lib.chained_block_hashes(
        arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)), len(arr),
        block_size, parent & 0xFFFFFFFFFFFFFFFF,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)))
    if got != n_full:
        logger.warning("native chained_block_hashes returned %d != %d",
                       got, n_full)
        return None
    return out


def hash_one_block(tokens_u32: np.ndarray, parent: int) -> Optional[int]:
    lib = get_lib()
    if lib is None:
        return None
    arr = np.ascontiguousarray(tokens_u32, dtype=np.uint32)
    return int(lib.hash_one_block(
        arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)), len(arr),
        parent & 0xFFFFFFFFFFFFFFFF))
