"""Measured tiling autotuner for the Mosaic grouped matmul.

The dropless-MoE grouped GEMMs (:func:`moe_dispatch.grouped_matmul`)
used to pick their ``(tm, tk, tn)`` tilings from a static heuristic
calibrated on v5e at the bench shapes. The optimum moves with device
generation, expert count, and dtype — so this module *measures*: on the
first encounter of each ``(m, k, n, E, dtype, full_rows)`` key on a TPU
backend it times a small candidate grid for all three passes (forward
gmm, dgrad gmm with ``transpose_rhs``, wgrad tgmm), keeps the winner
in-process, and persists it through the jit compile-cache machinery
(:mod:`paddle_tpu.jit.cache`, ``gmm_tilings.json``) so steady-state
steps — and future processes on the same device kind — pay zero tuning
cost.

Where measurement is impossible (CPU lane, ``FLAGS_moe_gmm_autotune``
off, or a candidate that fails to compile) the static heuristic answers
instead; unmeasured answers are cached in-process only, never
persisted, so the on-disk file holds nothing but measured winners.

Two trust guards (r05 postmortem, docs/moe.md):

* **Never-worse-than-heuristic**: the heuristic seed is always timed as
  candidate 0, and a measured winner is kept only when it beats the
  heuristic by more than the noise margin — otherwise the heuristic is
  served and ``moe_tiling_autotune_rejected_total`` counts the
  rejection. A noisy grid can therefore never regress below the static
  baseline it replaced.
* **Persisted entries are validated, not trusted**: entries whose
  tilings fall outside the Mosaic envelope (``_fits``) or alignment
  rules are dropped at load (counted as rejected) and re-measured on
  next encounter; the file carries a schema version
  (:data:`SCHEMA`) so a key-format change invalidates old documents
  wholesale instead of misreading them.

Tuning cost and cache traffic are visible in the observability catalog:
``moe_tiling_cache_{hits,misses}_total``, ``moe_tiling_autotune_seconds``
and the ``moe.autotune`` / ``moe.gmm`` spans (see docs/moe.md).
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, Optional, Tuple

import numpy as _np

from ..framework.flags import define_flag, get_flag
from ..observability import trace_span
from ..observability.catalog import instrument as _instrument

define_flag("moe_gmm_autotune", True,
            "measure grouped-matmul tilings on first encounter of each "
            "shape (TPU only); off = the static heuristic")

__all__ = [
    "heuristic_tilings", "get_tilings", "candidate_tilings", "clear",
    "entries", "PERSIST_NAME", "SCHEMA",
]

Tiling = Tuple[int, int, int]
TriTiling = Tuple[Tiling, Tiling, Tiling]          # (fwd, dgrad, wgrad)

PERSIST_NAME = "gmm_tilings"
# v2: keys gained the dtype itemsize envelope (int8 expert weights) and
# the kernel-variant field (plain gmm vs the fused gather-GMM kernel).
SCHEMA = 2
_PASSES = ("fwd", "dgrad", "wgrad")

# a non-heuristic winner must beat the heuristic by more than this
# fraction, else measurement noise could swap in a worse tiling
_NOISE_MARGIN = 0.03

_M_HITS = _instrument("moe_tiling_cache_hits_total")
_M_MISSES = _instrument("moe_tiling_cache_misses_total")
_M_TUNE = _instrument("moe_tiling_autotune_seconds")
_M_REJECTED = _instrument("moe_tiling_autotune_rejected_total")

_LOCK = threading.Lock()
_CACHE: Dict[str, dict] = {}
_LOADED = False

_TILES = (1408, 1024, 512, 256, 128)


def _fits(tm: int, tk: int, tn: int, itemsize: int = 2) -> bool:
    """Mosaic compile envelope, calibrated on v5e: double-buffered input
    tiles within scoped VMEM, and the f32 accumulator tile below the
    observed crash line (tm*tn*4 of 4 MiB fails, 2.88 MiB compiles).
    ``itemsize`` is the operand byte width (2 = bf16, 1 = int8 expert
    weights — int8 halves the input-tile footprint, so bigger tiles fit)."""
    return (2 * itemsize * (tm * tk + tk * tn) + 4 * tm * tn
            <= 15.5 * 2**20
            and 4 * tm * tn <= 3 * 2**20)


def _aligned(t) -> bool:
    """Sanity envelope for a (t1, t2, t3) tiling from an untrusted
    source (the persisted file): positive ints, sublane/lane aligned.
    Everything the candidate generator emits passes; hand-poisoned or
    bit-rotted entries do not."""
    try:
        t1, t2, t3 = (int(v) for v in t)
    except (TypeError, ValueError):
        return False
    return (t1 > 0 and t2 > 0 and t3 > 0
            and t1 % 8 == 0 and t2 % 128 == 0 and t3 % 128 == 0)


def heuristic_tilings(m: int, k: int, n: int) -> Optional[TriTiling]:
    """Static per-pass tilings, measured on v5e at the bench shapes
    (m=32768, E=16; % of bf16 peak):

      fwd  [m,2048]@[E,2048,2816]  (512,512,1408)  33.7%  (512-cubed: 22%)
      fwd  [m,1408]@[E,1408,2048]  (256,1408,2048) 20.7%
      dgrad (transpose_rhs)        whole-K, tn=512 ~31%
      wgrad (tgmm)                 (512,512,1408)  29.2%

    The stock megablox ops.gmm shares ONE tiling between forward, dgrad,
    and tgmm — the measured optimum differs per pass (the dgrad/wgrad
    contraction is the forward's n/m), worth ~1.5x on the routed FFN.
    Returns (fwd, dgrad, wgrad) or None for shapes the kernel doesn't
    like (odd alignments → ragged_dot). tgmm's first tile divides the
    contraction (m) — it must use the same m-aligned tm as the others.

    This is the autotuner's seed ordering and its fallback whenever
    measurement is unavailable."""
    if m % 256 or k % 128 or n % 128:
        return None
    tm = 512 if m % 512 == 0 else 256
    tn = next(t for t in _TILES if n % t == 0)
    if k % 512 == 0:
        fwd_cands = [(tm, 512, tn), (tm, 512, 512), (tm, 512, 128)]
    else:
        fwd_cands = [(256, k, n), (256, k, 1024), (256, k, 512)]
    cands = {
        "fwd": fwd_cands,
        "dgrad": [(tm, n, 512), (tm, 512, 512), (tm, 128, 512)],
        "wgrad": [(tm, 512, tn), (tm, 512, 512), (tm, 512, 128)],
    }
    picked = {}
    for pass_, cs in cands.items():
        picked[pass_] = next((c for c in cs if _fits(*c)), None)
        if picked[pass_] is None:
            return None
    return picked["fwd"], picked["dgrad"], picked["wgrad"]


def candidate_tilings(m: int, k: int, n: int,
                      cap: int = 8,
                      itemsize: int = 2) -> Optional[Dict[str, list]]:
    """Per-pass candidate grid, heuristic winner first. Every candidate
    satisfies the :func:`_fits` VMEM envelope at the operand ``itemsize``
    (int8 weights admit bigger tiles); the heuristic's alignment
    preconditions gate the whole shape. ``cap`` bounds measurement cost
    (first-encounter only, but each candidate is a fresh Mosaic compile)."""
    heur = heuristic_tilings(m, k, n)
    if heur is None:
        return None
    tm_opts = [t for t in (512, 256) if m % t == 0]
    k_tiles = [t for t in (1024, 512, 256) if k % t == 0] or [k]
    n_tiles = [t for t in _TILES if n % t == 0]
    grids = {
        # fwd gmm: [m,k] @ [E,k,n] — (m tile, k contraction tile, n tile)
        "fwd": [(tm, tk, tn)
                for tm in tm_opts for tk in k_tiles for tn in n_tiles],
        # dgrad gmm (transpose_rhs): [m,n] @ [E,n,k]^T — contraction is n
        "dgrad": [(tm, t2, t3)
                  for tm in tm_opts
                  for t2 in dict.fromkeys((n, 512, 128))
                  for t3 in (512, 256)],
        # wgrad tgmm: [k,m] x [m,n] — first tile divides the contraction m
        "wgrad": [(tm, t2, t3)
                  for tm in tm_opts for t2 in (512, 256, 128)
                  for t3 in dict.fromkeys((min(n_tiles[0], 1024), 512, 128))],
    }
    out = {}
    for i, pass_ in enumerate(_PASSES):
        seen = [heur[i]]
        for c in grids[pass_]:
            if c not in seen and _fits(*c, itemsize=itemsize):
                seen.append(c)
        out[pass_] = seen[:cap]
    return out


def _key(device: str, m: int, k: int, n: int, E: int, dtype: str,
         full_rows: bool, variant: str = "gmm") -> str:
    return (f"{device}|m={m}|k={k}|n={n}|E={E}|{dtype}"
            f"|full_rows={full_rows}|v={variant}")


def _ensure_loaded() -> None:
    """Merge the persisted winners into the in-process cache (once).

    Entries are *validated*, never trusted: a tiling outside the Mosaic
    alignment/VMEM envelope (hand-edited file, bit rot, or a winner
    measured under a different envelope calibration) is dropped — the
    next encounter of its key is a cache miss that re-measures — and
    counted in ``moe_tiling_autotune_rejected_total``."""
    global _LOADED
    if _LOADED:
        return
    from ..jit import cache as _jcache

    disk = _jcache.load_json(PERSIST_NAME, schema=SCHEMA)
    rejected = 0
    with _LOCK:
        if _LOADED:
            return
        for key, ent in disk.items():
            t = ent.get("tilings") if isinstance(ent, dict) else None
            if not (isinstance(t, dict) and all(p in t for p in _PASSES)):
                rejected += 1
                continue
            # key layout: device|m=..|k=..|n=..|E=..|<dtype>|full_rows=..|v=..
            try:
                itemsize = _np.dtype(key.split("|")[5]).itemsize
            except (IndexError, TypeError):
                itemsize = 2          # unparsable dtype: bf16 envelope
            if not all(_aligned(t[p]) and _fits(*(int(v) for v in t[p]),
                                                itemsize=itemsize)
                       for p in _PASSES):
                rejected += 1          # poisoned/stale: re-measure later
                continue
            if key not in _CACHE:
                _CACHE[key] = {
                    "tilings": {p: tuple(int(v) for v in t[p])
                                for p in _PASSES},
                    "source": ent.get("source", "measured"),
                }
        _LOADED = True
    for _ in range(rejected):
        _M_REJECTED.inc()


def _persist() -> None:
    from ..jit import cache as _jcache

    with _LOCK:
        doc = {key: {"tilings": {p: list(ent["tilings"][p])
                                 for p in _PASSES},
                     "source": ent["source"]}
               for key, ent in _CACHE.items()
               if ent["source"] == "measured"}
    _jcache.store_json(PERSIST_NAME, doc, schema=SCHEMA)


def _as_tri(ent: dict) -> TriTiling:
    t = ent["tilings"]
    return tuple(tuple(t[p]) for p in _PASSES)  # type: ignore[return-value]


def _default_measure(m, k, n, E, dtype, full_rows):
    """Build the on-device timing closure, or None when this backend
    can't run the Mosaic kernel (the CPU lane)."""
    import jax

    if jax.default_backend() != "tpu":
        return None
    import functools

    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    # get_tilings is usually reached while the model is being TRACED,
    # where every jnp op yields a tracer and nothing can be timed (a
    # tracer has no block_until_ready — every candidate "failed", and the
    # skip in get_tilings turned that into the heuristic, silently, until
    # PR 21's chip lane). Trace state is per thread, so the operands are
    # built and the calls timed on a thread of their own, where they run
    # for real. (jax.ensure_compile_time_eval does not do: under it the
    # megablox index maps capture constants, which Pallas refuses.)
    ops = {}

    def operands():
        if not ops:
            ks = jax.random.split(jax.random.PRNGKey(0), 3)
            ops["lhs"] = jax.random.normal(
                ks[0], (m, k), jnp.float32).astype(dtype)
            ops["rhs"] = jax.random.normal(
                ks[1], (E, k, n), jnp.float32).astype(dtype)
            ops["grad"] = jax.random.normal(
                ks[2], (m, n), jnp.float32).astype(dtype)
            # balanced groups summing to m — the load the aux loss
            # maintains
            ops["gs"] = jnp.full((E,), m // E, jnp.int32).at[0].add(
                m - E * (m // E))
        return ops["lhs"], ops["rhs"], ops["grad"], ops["gs"]

    def run(pass_: str, tiling: Tiling) -> float:
        with ThreadPoolExecutor(1) as off_trace:
            return off_trace.submit(timed, pass_, tiling).result()

    def timed(pass_: str, tiling: Tiling) -> float:
        lhs, rhs, grad, gs = operands()
        if pass_ == "fwd":
            f = jax.jit(functools.partial(
                gmm, preferred_element_type=lhs.dtype, tiling=tiling))
            args = (lhs, rhs, gs)
        elif pass_ == "dgrad":
            f = jax.jit(functools.partial(
                gmm, preferred_element_type=lhs.dtype, tiling=tiling,
                transpose_rhs=True))
            args = (grad, rhs, gs)
        else:
            f = jax.jit(functools.partial(
                tgmm, preferred_element_type=rhs.dtype, tiling=tiling,
                num_actual_groups=E))
            args = (lhs.swapaxes(0, 1), grad, gs)
        f(*args).block_until_ready()          # compile + warm
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            f(*args).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best

    return run


def get_tilings(m: int, k: int, n: int, E: int, dtype, full_rows: bool,
                *, measure: Optional[Callable] = None,
                variant: str = "gmm") -> Optional[TriTiling]:
    """(fwd, dgrad, wgrad) tilings for one ``grouped_matmul`` call site.

    Cache hit → the remembered winner (persisted winners count as hits:
    the whole point is that a warmed cache makes every step steady-state).
    Miss → measure the candidate grid when possible, else the heuristic.
    ``measure(pass_, tiling) -> seconds`` is injectable for tests and for
    :mod:`tools.moe_tune`; pass a factory result, not a factory.
    ``variant`` keys the kernel family ("gmm" = stock megablox,
    "fused" = the gather-fused kernel in :mod:`.moe_fused`) — their
    optima differ, so they never share cache entries.

    Never-worse guard: the heuristic is always timed (candidate 0), and
    a different winner is kept only when it beats the heuristic by more
    than the noise margin; rejected winners increment
    ``moe_tiling_autotune_rejected_total``.

    Returns None for shapes the Mosaic kernel doesn't like — the caller
    falls back to ``ragged_dot``."""
    heur = heuristic_tilings(m, k, n)
    if heur is None:
        return None
    if not get_flag("moe_gmm_autotune"):
        return heur
    _ensure_loaded()
    np_dtype = _np.dtype(dtype)
    dtype_s = np_dtype.name
    key = _key(_device_tag(), m, k, n, E, dtype_s, bool(full_rows),
               variant)
    with _LOCK:
        ent = _CACHE.get(key)
    if ent is not None:
        _M_HITS.inc()
        return _as_tri(ent)
    _M_MISSES.inc()

    runner = measure if measure is not None else _default_measure(
        m, k, n, E, dtype, full_rows)
    if runner is None:
        # nothing to time here: serve the heuristic, remember it
        # in-process only (never persisted — the disk file is
        # measured-winners-only)
        with _LOCK:
            _CACHE.setdefault(
                key, {"tilings": dict(zip(_PASSES, heur)),
                      "source": "heuristic"})
        return heur

    cands = candidate_tilings(m, k, n, itemsize=np_dtype.itemsize)
    picked: Dict[str, Tiling] = {}
    all_measured = True
    t_start = time.perf_counter()
    with trace_span("moe.autotune", m=m, k=k, n=n, E=E, dtype=dtype_s):
        for i, pass_ in enumerate(_PASSES):
            best, best_t = heur[i], float("inf")
            heur_t = float("inf")
            for tiling in cands[pass_]:
                try:
                    with trace_span("moe.gmm", pass_=pass_,
                                    tiling=str(tiling)):
                        dt = runner(pass_, tiling)
                except Exception:
                    continue      # candidate fails to compile/run: skip
                if tiling == heur[i]:
                    heur_t = dt
                if dt < best_t:
                    best, best_t = tiling, dt
            if best_t == float("inf"):
                # every candidate failed: the default-win heuristic was
                # never validated — do NOT let it persist as "measured"
                # (a toolchain fix should re-trigger measurement)
                all_measured = False
            elif (tuple(best) != tuple(heur[i])
                    and best_t > heur_t * (1.0 - _NOISE_MARGIN)):
                # winner inside the noise band of the heuristic: the
                # measurement proved nothing — keep the static pick
                best = heur[i]
                _M_REJECTED.inc()
            picked[pass_] = tuple(best)
    _M_TUNE.observe(time.perf_counter() - t_start)
    source = "measured" if all_measured else "heuristic"
    with _LOCK:
        _CACHE.setdefault(key, {"tilings": picked, "source": source})
        ent = _CACHE[key]
    if all_measured:
        _persist()
    return _as_tri(ent)


def _device_tag() -> str:
    """Tilings are device-generation-specific: the cache key leads with
    the accelerator kind so a v5e file never answers for a v6e."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        return backend
    try:
        return jax.devices()[0].device_kind.replace(" ", "_")
    except Exception:
        return "tpu"


def clear(persisted: bool = False) -> None:
    """Drop the in-process cache; ``persisted=True`` also truncates the
    on-disk file (documented escape hatch after a toolchain upgrade)."""
    global _LOADED
    with _LOCK:
        _CACHE.clear()
        _LOADED = False     # next access re-reads the persisted winners
    if persisted:
        from ..jit import cache as _jcache

        _jcache.store_json(PERSIST_NAME, {}, schema=SCHEMA)


def entries():
    """Snapshot of (key, source, {pass: tiling}) — the tools/moe_tune.py
    table."""
    _ensure_loaded()
    with _LOCK:
        return [(key, ent["source"], dict(ent["tilings"]))
                for key, ent in sorted(_CACHE.items())]
