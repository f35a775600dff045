"""Persistent compile-artifact cache for the jit layer.

The in-memory guard cache (``StaticFunction._cache``) dies with the
process; artifacts whose recomputation is *measured* rather than traced
— today the MoE grouped-matmul tiling winners
(:mod:`paddle_tpu.kernels.gmm_autotune`) — are worth keeping across
runs. This module is the one place that knows where such artifacts
live and how to write them without torn files:

* ``cache_dir()`` — ``FLAGS_jit_cache_dir`` > ``$PADDLE_TPU_CACHE_DIR``
  > ``<checkout>/.paddle_tpu_cache`` (a fixed, gitignored directory
  derived from the package's location — no run depends on ``$HOME``,
  which a sealed machine does not keep);
* ``load_json(name)`` / ``store_json(name, obj)`` — JSON documents
  committed with the resilience tier's temp+fsync+rename idiom
  (atomic_ckpt.py), so a crash mid-write leaves the previous version,
  never a truncated one. Corrupt/missing files read as ``{}``.

Documents may carry a **schema version**: ``store_json(name, obj,
schema=N)`` stamps the document with ``{"__schema__": N}`` and
``load_json(name, schema=N)`` returns ``{}`` for any document whose
stamp does not match — a process running older code silently starts
from an empty cache instead of misreading entries whose key format
changed (the gmm tiling keys gained dtype/kernel-variant fields this
way). ``schema=None`` (the default) keeps the historical unversioned
behaviour.

Deliberately tiny and stdlib-only: callers treat persistence as
best-effort (a read-only filesystem must never break compilation).
"""
from __future__ import annotations

import json
import os
import tempfile
from typing import Any, Dict

from ..framework.cache_dirs import ARTIFACT_DIR
from ..framework.flags import define_flag, get_flag

define_flag("jit_cache_dir", "",
            "directory for persistent compile artifacts (tiling autotune "
            "winners etc.); empty = $PADDLE_TPU_CACHE_DIR or "
            "<checkout>/.paddle_tpu_cache")

__all__ = ["cache_dir", "cache_path", "load_json", "store_json",
           "SCHEMA_KEY"]

SCHEMA_KEY = "__schema__"


def cache_dir() -> str:
    return (get_flag("jit_cache_dir")
            or os.environ.get("PADDLE_TPU_CACHE_DIR") or ARTIFACT_DIR)


def cache_path(name: str) -> str:
    return os.path.join(cache_dir(), name + ".json")


def load_json(name: str, schema: int = None) -> Dict[str, Any]:
    """Read a cached JSON document; missing or corrupt → ``{}``.

    With ``schema=N`` the document must carry ``{"__schema__": N}``
    (written by ``store_json(..., schema=N)``) — any other stamp, or a
    pre-versioning file, reads as ``{}`` so callers re-derive rather
    than misinterpret entries under an old key format. The stamp itself
    is stripped from the returned mapping."""
    try:
        with open(cache_path(name), "r") as f:
            obj = json.load(f)
        if not isinstance(obj, dict):
            return {}
    except (OSError, ValueError):
        return {}
    if schema is not None:
        if obj.get(SCHEMA_KEY) != schema:
            return {}
    obj.pop(SCHEMA_KEY, None)
    return obj


def store_json(name: str, obj: Dict[str, Any], schema: int = None) -> bool:
    """Atomically commit ``obj`` (temp file + fsync + rename). Returns
    False instead of raising on any I/O failure — persistence is an
    optimization, never a requirement. ``schema=N`` stamps the document
    for :func:`load_json` version checking."""
    if schema is not None:
        obj = dict(obj, **{SCHEMA_KEY: schema})
    path = cache_path(name)
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path),
                                   prefix=".tmp-" + name)
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(obj, f, indent=1, sort_keys=True)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)      # the commit point (atomic on POSIX)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        return True
    except OSError:
        return False
