"""C++ extension builder (parity: python/paddle/utils/cpp_extension/ —
load() JIT-compiles custom C++ ops; setup() for installed builds).

TPU-native: custom ops integrate as ctypes-callable shared libraries (the
framework's own native runtime uses the same path — paddle_tpu/lib). CUDA
sources are rejected with a clear error: device code belongs in Pallas.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
from typing import List, Optional

__all__ = ["load", "get_build_directory", "CppExtension", "CUDAExtension",
           "BuildExtension", "setup"]


def get_build_directory() -> str:
    from ..framework.cache_dirs import ARTIFACT_DIR

    d = os.environ.get("PADDLE_EXTENSION_DIR",
                       os.path.join(ARTIFACT_DIR, "extensions"))
    os.makedirs(d, exist_ok=True)
    return d


def load(name: str, sources: List[str], extra_cxx_cflags: Optional[List[str]]
         = None, extra_cuda_cflags=None, extra_ldflags=None,
         extra_include_paths=None, build_directory=None, verbose=False):
    """Compile sources into lib<name>.so and return the ctypes CDLL."""
    if any(s.endswith((".cu", ".cuh")) for s in sources):
        raise ValueError(
            "CUDA sources are not supported on the TPU build — write device "
            "code as Pallas kernels (paddle_tpu/kernels) and keep C++ "
            "extensions host-side")
    build_dir = build_directory or get_build_directory()
    out = os.path.join(build_dir, f"lib{name}.so")
    srcs = [os.path.abspath(s) for s in sources]
    if (not os.path.exists(out)
            or any(os.path.getmtime(s) > os.path.getmtime(out) for s in srcs)):
        cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", "-pthread"]
        for inc in (extra_include_paths or []):
            cmd += ["-I", inc]
        cmd += (extra_cxx_cflags or []) + srcs + ["-o", out]
        cmd += (extra_ldflags or [])
        if verbose:
            print(" ".join(cmd))
        subprocess.run(cmd, check=True, capture_output=not verbose)
    return ctypes.CDLL(out)


class CppExtension:
    def __init__(self, sources, *args, **kwargs):
        self.sources = sources
        self.kwargs = kwargs


def CUDAExtension(*args, **kwargs):
    raise ValueError("CUDAExtension is unavailable on TPU — use Pallas "
                     "kernels for device code")


class BuildExtension:
    @classmethod
    def with_options(cls, **options):
        return cls


def setup(**kwargs):
    """Minimal setup(): builds every CppExtension in-place."""
    exts = kwargs.get("ext_modules", [])
    libs = {}
    for ext in exts:
        name = kwargs.get("name", "custom_ext")
        libs[name] = load(name, ext.sources, **ext.kwargs)
    return libs
