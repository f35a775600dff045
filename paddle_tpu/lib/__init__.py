"""Native runtime loader (C++ pieces, ctypes-bound).

The reference's runtime is C++ end-to-end; on TPU the device path is XLA, and
the host-side pieces that stay native live in csrc/ptpu_runtime.cpp
(TCPStore rendezvous, GIL-free batch collation). The shared library is a
build output, never a tracked file: it is built from the source with g++ on
first use, next to the source, under a name that carries the source's hash
— so a changed source builds a new library, and a copied checkout (whose
file times mean nothing) loads exactly the library its source describes.
"""
from __future__ import annotations

import ctypes
import functools
import hashlib
import os
import shutil
import subprocess
import threading

from ..framework.cache_dirs import CHECKOUT

_SRC = os.path.join(CHECKOUT, "csrc", "ptpu_runtime.cpp")

_lock = threading.Lock()
_lib = None


@functools.lru_cache(maxsize=1)
def _so_path() -> str:
    with open(_SRC, "rb") as f:
        tag = hashlib.sha256(f.read()).hexdigest()[:12]
    return os.path.join(CHECKOUT, "csrc", f"libptpu_runtime.{tag}.so")


def _build(so: str) -> None:
    # build beside the target and rename: another process (a spawned
    # rank, a test worker) never loads a half-written library
    tmp = f"{so}.{os.getpid()}.tmp"
    cmd = ["g++", "-O2", "-shared", "-fPIC", "-pthread", _SRC, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
        os.replace(tmp, so)
    except subprocess.CalledProcessError as e:
        raise RuntimeError(
            f"native runtime failed to build ({' '.join(cmd)}):\n"
            f"{e.stderr}") from e
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def native_lib():
    """Load the native runtime, building it from csrc/ptpu_runtime.cpp on
    first use; returns the ctypes CDLL. A build or load failure raises —
    whoever asked for the native path gets the error, not a quiet other
    path (see :func:`native_available`)."""
    global _lib
    with _lock:
        if _lib is not None:
            return _lib
        so = _so_path()
        if not os.path.exists(so):
            _build(so)
        lib = ctypes.CDLL(so)
        lib.ptpu_store_server_start.restype = ctypes.c_void_p
        lib.ptpu_store_server_start.argtypes = [ctypes.c_int]
        lib.ptpu_store_server_start2.restype = ctypes.c_void_p
        lib.ptpu_store_server_start2.argtypes = [ctypes.c_int, ctypes.c_char_p]
        lib.ptpu_store_server_port.restype = ctypes.c_int
        lib.ptpu_store_server_port.argtypes = [ctypes.c_void_p]
        lib.ptpu_store_server_stop.argtypes = [ctypes.c_void_p]
        lib.ptpu_store_client_connect.restype = ctypes.c_void_p
        lib.ptpu_store_client_connect.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_double]
        lib.ptpu_store_client_close.argtypes = [ctypes.c_void_p]
        lib.ptpu_store_set.restype = ctypes.c_int
        lib.ptpu_store_set.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int]
        lib.ptpu_store_get.restype = ctypes.c_int
        lib.ptpu_store_get.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int]
        lib.ptpu_store_wait.restype = ctypes.c_int
        lib.ptpu_store_wait.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int]
        lib.ptpu_store_add.restype = ctypes.c_longlong
        lib.ptpu_store_add.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_longlong]
        lib.ptpu_gather_rows.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_longlong), ctypes.c_int,
            ctypes.c_longlong, ctypes.c_char_p, ctypes.c_int]
        _lib = lib
        return _lib


def native_available() -> bool:
    """True where the native runtime can exist: it is built already, or
    there is a g++ to build it with. Says nothing about whether the build
    succeeds — a build that fails is an error raised by
    :func:`native_lib`, not an absence."""
    return os.path.exists(_so_path()) or shutil.which("g++") is not None
