"""Where a checkout keeps what it builds at run time.

A sealed machine's ``$HOME`` does not survive the run and is not part of
the checkout, and JAX's persistent compilation cache keys on its own
path, so a directory that moves never hits. Both caches therefore live
at fixed, gitignored paths inside the checkout, derived from the
package's location — never from a temp name, a pid or the time:

* ``.jax_cache/`` — JAX's persistent compilation cache
  (:func:`configure_compile_cache`), unless ``JAX_COMPILATION_CACHE_DIR``
  places it from outside;
* ``.paddle_tpu_cache/`` — this package's own artifacts: autotune
  winners (:mod:`paddle_tpu.jit.cache`), JIT-built C++ extensions,
  downloaded datasets.

Stdlib-only at import (jax is touched inside the one function that
needs it, and only its config — no backend is initialised).
"""
from __future__ import annotations

import os

__all__ = ["CHECKOUT", "JAX_CACHE_DIR", "ARTIFACT_DIR",
           "configure_compile_cache"]

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
JAX_CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")
ARTIFACT_DIR = os.path.join(CHECKOUT, ".paddle_tpu_cache")


def configure_compile_cache() -> None:
    """Place JAX's persistent compilation cache. With
    ``JAX_COMPILATION_CACHE_DIR`` set nothing is set in code — JAX reads
    the variable itself, and whoever set it owns the placement. Otherwise
    the cache goes to ``<checkout>/.jax_cache``. Called once, where the
    package starts; ``jax.config.jax_compilation_cache_dir`` then says
    where it is."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update("jax_compilation_cache_dir", JAX_CACHE_DIR)
