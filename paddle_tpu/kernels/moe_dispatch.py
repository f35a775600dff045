"""Dropless (capacity-less) MoE token dispatch.

Capability parity: the reference's capacity-less MoE all-to-all —
`global_scatter`/`global_gather` (incubate/distributed/models/moe/
moe_layer.py:105-188) exchanges a *ragged* number of tokens per expert and
drops nothing; its fused grouped-GEMM path
(phi/kernels/fusion/cutlass_kernels/moe_gemm/) batches the per-expert FFNs
into one kernel.

TPU-native re-design (three strategies, one semantic):

* ``dropless_moe_ffn``     — single-program GSPMD form: stable-sort the
  ``T*k`` (token, slot) assignments by expert, then three
  ``jax.lax.ragged_dot`` grouped GEMMs (the MXU analogue of the cutlass
  grouped GEMM). No capacity buffer exists, so no token is ever dropped.
* ``dropless_moe_ffn_ep``  — explicit expert-parallel form under
  ``jax.shard_map`` (partial-manual over the token + 'ep' axes): every ep
  rank keeps its expert shard, computes the assignments that route to its
  local experts with a local sort + ``ragged_dot``, and the combine is one
  ``psum`` over 'ep'. Token→expert traffic never leaves the rank (the
  tokens are ep-replicated already); the only collective is the [T,h]
  allreduce of the routed outputs — an ICI-friendly trade of the
  reference's two ragged all-to-alls.
* ``dropless_moe_ffn_a2a`` — the literal reference shape: tokens sharded
  over 'ep', exchanged with ``jax.lax.ragged_all_to_all`` (sizes exchanged
  via ``all_gather``), grouped-GEMM'd on the owner, and returned with the
  reverse ragged all-to-all. XLA:CPU has no ragged-all-to-all lowering, so
  this path is for real TPU meshes; the CPU test lane covers the other two.

All three differentiate: ``ragged_dot`` has jvp/transpose rules, the sorts
and scatters transpose to gathers, and the collectives transpose to
themselves (psum) or the reverse exchange.

Hot-path structure (see docs/moe.md):

* :func:`fused_routing` is the dispatch *prologue*: the fp32 router
  matmul, top-k gating, aux loss, AND the expert-sort scatter metadata
  come out of one shared one-hot/argsort — the router never round-trips
  through separate computations, and every dispatch form below accepts
  the precomputed ``routing=`` so nothing is derived twice.
* :func:`plan_dispatch` memoizes the shape-derived plan (slot count Q,
  dense-vs-gmm decision) per routing shape — every MoE layer of a model
  shares one plan, visible in ``moe_plan_cache_{hits,misses}_total``.
* ``grouped_matmul`` tilings come from the *measured* autotuner
  (:mod:`.gmm_autotune`) with the v5e heuristic as seed and fallback.
* The expert-parallel forms overlap their collectives with the shared-
  expert FFN: pass ``shared=(s_gate, s_up, s_down)`` and the token batch
  is processed as double-buffered halves, each half's collective hiding
  behind the other half's grouped GEMM and the shared-expert compute.
"""
from __future__ import annotations

import functools
import threading
import time
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..framework.flags import define_flag, get_flag
from ..observability import numerics as _numerics
from ..observability import trace_span
from ..observability.catalog import instrument as _instrument
from .gmm_autotune import (  # noqa: F401  (re-exported for back-compat)
    _fits, get_tilings, heuristic_tilings, heuristic_tilings as
    _pick_tilings,
)

define_flag("moe_dispatch_autotune", True,
            "measure dense vs gmm vs fused dispatch once per routing "
            "shape on TPU and use the winner (never worse than the "
            "static default); off = the static choice")
define_flag("moe_overlap_min_tokens", 1024,
            "expert-parallel double-buffered overlap is bypassed below "
            "this per-rank token count (halving overhead beats the "
            "collective hiding on small slices; see docs/moe.md)")

__all__ = [
    "dropless_moe_ffn", "dropless_moe_ffn_dense", "dropless_moe_ffn_ep",
    "dropless_moe_ffn_a2a", "dropless_moe_ffn_fused", "sort_by_expert",
    "fused_routing", "Routing", "plan_dispatch", "DispatchPlan",
    "clear_plan_cache", "pick_dispatch_form", "clear_form_cache",
    "make_moe_operands", "time_best", "group_limited_routing",
    "sigmoid_bias_routing", "held_expert_ffn",
]

_M_PLAN_HITS = _instrument("moe_plan_cache_hits_total")
_M_PLAN_MISSES = _instrument("moe_plan_cache_misses_total")
_M_FALLBACKS = _instrument("moe_dispatch_fallbacks_total")
_M_OVERLAP_BYPASS = _instrument("moe_overlap_bypass_total")


def sort_by_expert(idx: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Flatten top-k assignments [T,k] → stable expert-sorted order.

    Returns (order [T*k] assignment permutation, tok [T*k] source token of
    each sorted assignment, flat_e [T*k] unsorted expert ids)."""
    T, k = idx.shape
    flat_e = idx.reshape(T * k)
    order = jnp.argsort(flat_e)           # stable → deterministic combine
    tok = order // k
    return order, tok, flat_e


# ---------------------------------------------------------------------------
# fused routing prologue — router matmul + gating + aux loss + sort metadata
# from ONE shared one-hot/argsort (the reference computes these as separate
# gate / scatter-prep passes; here they are one XLA computation feeding
# every dispatch strategy below)
# ---------------------------------------------------------------------------

class Routing(NamedTuple):
    """Everything the router run produces, computed once per MoE layer.

    ``weights``/``idx``/``aux`` match :func:`models.moe.top_k_gating`
    bit-for-bit at fp32; ``order``/``tok``/``flat_e``/``gs`` are the
    expert-sort scatter metadata the single-program dispatch forms would
    otherwise re-derive."""

    weights: jax.Array   # [T,k] f32, renormalized top-k gate weights
    idx: jax.Array       # [T,k] int32 expert ids
    aux: jax.Array       # scalar f32 load-balance aux loss (GShard eq. 4)
    order: jax.Array     # [T*k] expert-sorted assignment permutation
    tok: jax.Array       # [T*k] source token of each sorted assignment
    flat_e: jax.Array    # [T*k] unsorted expert ids
    gs: jax.Array        # [E] int32 per-expert assignment counts


def routing_from_logits(logits: jax.Array, top_k: int) -> Routing:
    """Gating + metadata from precomputed router logits (fp32)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)   # [T,E]
    weights, idx = jax.lax.top_k(probs, top_k)                    # [T,k]
    weights = weights / jnp.sum(weights, -1, keepdims=True)
    T, E = logits.shape
    A = T * top_k
    flat_e = idx.reshape(A)
    # ONE one-hot feeds the group sizes AND the aux-loss expert fractions
    onehot = (flat_e[:, None] == jnp.arange(E, dtype=flat_e.dtype)[None, :]
              ).astype(jnp.int32)                                 # [A,E]
    gs = onehot.sum(axis=0)
    me = jnp.mean(probs, axis=0)                                  # [E]
    # rows 0, k, 2k, ... of the flat one-hot are the top-1 assignments
    ce = jnp.mean(
        onehot.reshape(T, top_k, E)[:, 0].astype(jnp.float32), axis=0)
    aux = E * jnp.sum(me * ce)
    order = jnp.argsort(flat_e)           # stable → deterministic combine
    tok = order // top_k
    return Routing(weights, idx, aux, order, tok, flat_e, gs)


def fused_routing(x: jax.Array, router_w: jax.Array,
                  top_k: int) -> Routing:
    """The dispatch prologue: fp32 router matmul → :class:`Routing`.

    Numerically identical to ``top_k_gating(x.astype(f32) @
    router_w.astype(f32), top_k)`` (same op sequence), plus the sort
    metadata every single-program dispatch form consumes via
    ``routing=`` — so the router, the aux loss, and the scatter prep
    are one fused computation instead of three."""
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    # numerics probe on the router logits (trace-time gated, zero ops
    # when off): a diverging router is the classic MoE blowup source,
    # and its NaNs surface HERE before they smear across every expert.
    # Visibility contract: this site sits inside the scanned layer
    # body, so it lands in forward/serving programs and in remat'd
    # training bodies (the recompute re-runs it) — an un-checkpointed
    # grad drops it (see numerics.record_stats); the per-layer ladder
    # in models/ covers training regardless.
    _numerics.record_stats("moe.router_logits", logits)
    return routing_from_logits(logits, top_k)


# ---------------------------------------------------------------------------
# dispatch plan — shape-derived constants, one per routing shape
# ---------------------------------------------------------------------------

class DispatchPlan(NamedTuple):
    """Static dispatch decisions for one routing shape (T, k, E, h).

    Everything here is derivable from shapes alone — it is *host-side*
    metadata (slot count Q, dense-base eligibility), computed once and
    shared by every MoE layer and every step with the same shape instead
    of being re-derived per layer."""

    T: int
    k: int
    E: int
    h: int
    Q: int               # dense-base slots per expert (A/E + slack, /128)
    use_dense: bool      # dense [E,Q,h] staging beats the gmm sort here


_PLAN_CACHE: Dict[tuple, DispatchPlan] = {}
_PLAN_LOCK = threading.Lock()


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def plan_dispatch(T: int, k: int, E: int, h: int,
                  slack: float = 0.125,
                  dense_base: bool = True) -> DispatchPlan:
    """The memoized plan for one routing shape (hit = every MoE layer
    after the first, and every later step)."""
    key = (T, k, E, h, float(slack), bool(dense_base))
    with _PLAN_LOCK:
        plan = _PLAN_CACHE.get(key)
    if plan is not None:
        _M_PLAN_HITS.inc()
        return plan
    _M_PLAN_MISSES.inc()
    A = T * k
    Q = min(_round_up(max(int(A / E * (1 + slack)), 1), 128), A)
    use_dense = bool(dense_base) and E * Q <= 4 * A
    if dense_base and not use_dense:
        # tiny/test shapes: the base buffer would dwarf the real work
        _M_FALLBACKS.labels(reason="dense_buffer_too_big").inc()
    plan = DispatchPlan(T, k, E, h, Q, use_dense)
    with _PLAN_LOCK:
        _PLAN_CACHE.setdefault(key, plan)
    return plan


def clear_plan_cache() -> None:
    with _PLAN_LOCK:
        _PLAN_CACHE.clear()


# ---------------------------------------------------------------------------
# measured dispatch-form selection — the r05 regression fix
#
# r04 made the dense-base staging form the static default on the strength
# of a forward-only MXU measurement; under the full train step it lost
# ~7% to the grouped-GEMM form at a 16-expert top-2 train shape (round
# 5's chip runs). Shape heuristics keep getting this wrong, so
# the form is now MEASURED once per routing shape on TPU — fwd+bwd, the
# quantity the bench actually pays — and the winner is persisted through
# the jit artifact cache. The static default ("fused") is always among
# the candidates, so the pick is never worse than the fallback.
# ---------------------------------------------------------------------------

_FORM_PERSIST = "moe_dispatch_forms"
# v2: keys gained the dense_ok candidate-set field — an entry measured
# with the dense form admitted must never answer for a caller that
# excluded it (dense staging can OOM where fused/gmm cannot)
_FORM_SCHEMA = 2
_FORM_STATIC = "fused"
_FORM_CACHE: Dict[str, dict] = {}
_FORM_LOADED = False


def _forms_ensure_loaded() -> None:
    global _FORM_LOADED
    if _FORM_LOADED:
        return
    from ..jit import cache as _jcache

    disk = _jcache.load_json(_FORM_PERSIST, schema=_FORM_SCHEMA)
    with _PLAN_LOCK:
        if _FORM_LOADED:
            return
        for key, ent in disk.items():
            if (isinstance(ent, dict)
                    and ent.get("winner") in ("fused", "gmm", "dense")
                    and key not in _FORM_CACHE):
                _FORM_CACHE[key] = ent
        _FORM_LOADED = True


def _forms_persist() -> None:
    from ..jit import cache as _jcache

    with _PLAN_LOCK:
        doc = {k: dict(e) for k, e in _FORM_CACHE.items()
               if e.get("source") == "measured"}
    _jcache.store_json(_FORM_PERSIST, doc, schema=_FORM_SCHEMA)


def clear_form_cache() -> None:
    global _FORM_LOADED
    with _PLAN_LOCK:
        _FORM_CACHE.clear()
        _FORM_LOADED = False


def make_moe_operands(T: int, h: int, E: int, f: int, dtype, seed: int = 0):
    """The shared synthetic routed-FFN operand recipe: ``(x [T,h],
    router_w [h,E] f32, e_gate [E,h,f], e_up [E,h,f], e_down [E,f,h])``
    with weights scaled 0.1. Every measurement/parity surface (the
    dispatch-form autotuner here, ``bench.moe_phase_breakdown``, the
    ``tests_tpu/`` lane) builds operands through THIS function so they
    time and compare the same problem."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (T, h), jnp.float32).astype(dtype)
    rw = jax.random.normal(ks[1], (h, E), jnp.float32) * 0.1
    eg = (jax.random.normal(ks[2], (E, h, f), jnp.float32) * 0.1
          ).astype(dtype)
    eu = (jax.random.normal(ks[3], (E, h, f), jnp.float32) * 0.1
          ).astype(dtype)
    ed = (jax.random.normal(ks[4], (E, f, h), jnp.float32) * 0.1
          ).astype(dtype)
    return x, rw, eg, eu, ed


def time_best(fn, *args, n: int = 3) -> float:
    """Best-of-``n`` wall-clock seconds of ``jax.jit(fn)(*args)`` after a
    compile+warm call — the shared timing discipline of the dispatch-form
    and phase-breakdown measurements."""
    f_jit = jax.jit(fn)
    jax.block_until_ready(f_jit(*args))             # compile + warm
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(f_jit(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _default_form_measure(T: int, k: int, E: int, h: int, f: int, dtype
                          ) -> Optional[Callable]:
    """fwd+bwd timing closure for one dispatch form at the real routing
    shape, or None off-TPU (the static default answers there)."""
    if jax.default_backend() != "tpu":
        return None

    def run(form: str) -> float:
        from . import moe_fused as _mf

        fns = {"fused": _mf.fused_moe_ffn,
               "gmm": dropless_moe_ffn,
               "dense": dropless_moe_ffn_dense}
        fn = fns[form]
        x, rw, eg, eu, ed = make_moe_operands(T, h, E, f, dtype)

        def loss(x, eg, eu, ed):
            r = fused_routing(x, rw, k)
            y = fn(x, r.weights, r.idx, eg, eu, ed, routing=r)
            return jnp.sum(jnp.square(y.astype(jnp.float32)))

        return time_best(jax.grad(loss, argnums=(0, 1, 2, 3)),
                         x, eg, eu, ed)

    return run


def pick_dispatch_form(T: int, k: int, E: int, h: int, f: int, dtype,
                       *, dense_ok: bool = False,
                       measure: Optional[Callable] = None) -> str:
    """'fused' | 'gmm' | 'dense' for one single-program routing shape.

    TPU: first encounter measures fwd+bwd of each candidate form at the
    real shape, keeps the winner (never worse than the static default —
    the default is always a candidate, and a winner inside the noise
    band of the default is rejected in its favor), and persists it.
    Elsewhere, or with ``FLAGS_moe_dispatch_autotune`` off: the static
    default. ``measure(form) -> seconds`` is injectable for tests."""
    static = _FORM_STATIC
    if not get_flag("moe_dispatch_autotune"):
        return static
    runner = measure if measure is not None else _default_form_measure(
        T, k, E, h, f, dtype)
    if runner is None:
        return static
    from .gmm_autotune import _device_tag

    cands = ["fused", "gmm"] + (["dense"] if dense_ok else [])
    _forms_ensure_loaded()
    key = (f"{_device_tag()}|T={T}|k={k}|E={E}|h={h}|f={f}|"
           f"{np.dtype(dtype).name}|dense_ok={bool(dense_ok)}")
    with _PLAN_LOCK:
        ent = _FORM_CACHE.get(key)
    if ent is not None and ent["winner"] in cands:
        return ent["winner"]
    times: Dict[str, float] = {}
    with trace_span("moe.autotune", kind="dispatch_form", T=T, E=E):
        for form in cands:
            try:
                times[form] = runner(form)
            except Exception:
                continue              # a form that fails to build loses
    if static not in times:
        return static
    winner = min(times, key=times.get)
    if winner != static and times[winner] > times[static] * 0.98:
        winner = static               # within noise: keep the default
    ent = {"winner": winner,
           "ms": {fm: round(v * 1e3, 3) for fm, v in times.items()},
           "source": "measured"}
    with _PLAN_LOCK:
        # a concurrent measurement may have raced us — keep the existing
        # entry only if its winner is admissible HERE, else overwrite (a
        # stale record must never answer with an excluded form)
        existing = _FORM_CACHE.get(key)
        if existing is not None and existing.get("winner") in cands:
            ent = existing
        else:
            _FORM_CACHE[key] = ent
    _forms_persist()
    return ent["winner"]


def _zero_tail(out, gs):
    """Zero output rows >= sum(gs). The Mosaic gmm never visits row tiles
    past the last group (make_group_metadata, visit_empty_groups=False), so
    those rows are UNINITIALIZED memory — unlike ragged_dot, which defines
    them as zeros. The EP paths rely on zeroed tails (foreign assignments
    sort to the tail with combine weight 0; garbage NaN * 0 = NaN would
    poison the psum combine, and the take-vjp scatter-add would mix garbage
    into real token grads in backward)."""
    valid = jax.lax.broadcasted_iota(jnp.int32, (out.shape[0], 1), 0) \
        < jnp.sum(gs)
    return jnp.where(valid, out, 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gmm_tuned(lhs, rhs, gs, tilings, full_rows):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as _gmm
    out = _gmm(lhs, rhs, gs, preferred_element_type=lhs.dtype,
               tiling=tilings[0])
    return out if full_rows else _zero_tail(out, gs)


def _gmm_tuned_fwd(lhs, rhs, gs, tilings, full_rows):
    return _gmm_tuned(lhs, rhs, gs, tilings, full_rows), (lhs, rhs, gs)


def _gmm_tuned_bwd(tilings, full_rows, res, grad):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        gmm as _gmm, tgmm as _tgmm)
    lhs, rhs, gs = res
    dlhs = _gmm(grad, rhs, gs, preferred_element_type=lhs.dtype,
                tiling=tilings[1], transpose_rhs=True)
    if not full_rows:
        dlhs = _zero_tail(dlhs, gs)
    drhs = _tgmm(lhs.swapaxes(0, 1), grad, gs,
                 preferred_element_type=rhs.dtype, tiling=tilings[2],
                 num_actual_groups=rhs.shape[0])
    return dlhs, drhs, None


_gmm_tuned.defvjp(_gmm_tuned_fwd, _gmm_tuned_bwd)


def grouped_matmul(xs, w, gs, full_rows: bool = False):
    """[m, k] @ per-group [E, k, n] over expert-sorted rows. On TPU this is
    the Mosaic block-sparse grouped matmul (megablox-style: only row
    blocks that exist are computed — the analogue of the reference's
    cutlass moe_gemm), with per-pass tilings from the measured autotuner
    (:func:`gmm_autotune.get_tilings`: first encounter of each
    ``(m, k, n, E, dtype, full_rows)`` key times a candidate grid, the
    winner is cached in-process and persisted); elsewhere
    jax.lax.ragged_dot.

    ``full_rows=True`` asserts sum(gs) == m statically (every row belongs
    to a group), skipping the tail-zeroing pass (``_zero_tail``).

    Note: the TPU path is reverse-mode only (custom_vjp) — forward-mode
    jvp/linearize of a dropless MoE falls back to the CPU/ragged_dot form.
    """
    m, k = xs.shape
    n = w.shape[-1]
    if jax.default_backend() == "tpu":
        tilings = get_tilings(m, k, n, w.shape[0], xs.dtype, full_rows)
        if tilings is not None:
            return _gmm_tuned(xs, w, gs, tilings, full_rows)
        _M_FALLBACKS.labels(reason="shape_unaligned").inc()
    return jax.lax.ragged_dot(xs, w, gs)


def _expert_ffn(xs, gs, e_gate, e_up, e_down, dt, full_rows=False):
    """Grouped-GEMM SwiGLU over expert-sorted rows (rows ≥ sum(gs) are
    zeroed — the caller additionally masks their combine weight to zero).

    gate and up ride ONE grouped GEMM over a width-2f concat of the weights
    (the reference's cutlass moe_gemm batches them the same way): one pass
    over xs instead of two, and the wider N keeps the MXU fed — measured
    +60% utilization on the first GEMM at the bench shapes."""
    f = e_gate.shape[-1]
    gu = grouped_matmul(
        xs, jnp.concatenate([e_gate, e_up], axis=-1).astype(dt), gs,
        full_rows=full_rows)
    return grouped_matmul(
        jax.nn.silu(gu[..., :f]) * gu[..., f:], e_down.astype(dt), gs,
        full_rows=full_rows)


def _shared_swiglu(x, s_gate, s_up, s_down, dt):
    """The always-on shared-expert FFN — computed inside the expert-
    parallel dispatch bodies so its MXU work hides the collectives."""
    xc = x.astype(dt)
    g = jax.nn.silu(xc @ s_gate.astype(dt))
    return (g * (xc @ s_up.astype(dt))) @ s_down.astype(dt)


def _dense_meta(idx, E: int, Q: int):
    """Branch-free routing metadata for the dense-base dispatch.

    Returns (r [A] slot id per flat assignment, src_tok [E*Q] source token
    per slot (0 for empty), w_sel [E*Q] assignment id per slot (A for
    empty), ok scalar bool: every expert's load fits Q).

    No sort: each assignment's rank within its expert is the exclusive
    prefix count of its expert's one-hot column — dense vector math the
    VPU chews through, vs. the bitonic argsort of the gmm path."""
    T, k = idx.shape
    A = T * k
    flat_e = idx.reshape(A)
    onehot = (flat_e[:, None] == jnp.arange(E, dtype=flat_e.dtype)[None, :]
              ).astype(jnp.int32)
    pos = jnp.take_along_axis(
        jnp.cumsum(onehot, axis=0) - onehot, flat_e[:, None], axis=1)[:, 0]
    gs = onehot.sum(axis=0)
    r = flat_e * Q + pos                       # slot per assignment
    ok = jnp.max(gs) <= Q
    # Overflow (pos >= Q, only when !ok) is clamped to E*Q so it truly
    # drops out of the scatter below — without the clamp an overflowing
    # assignment of expert e < E-1 would land inside expert e+1's slot
    # range and overwrite a valid slot. The cond still takes the gmm
    # branch when !ok; the clamp just keeps the metadata well-formed.
    r = jnp.where(pos < Q, r, E * Q)
    # slot -> flat assignment id (A = empty)
    w_sel = jnp.full((E * Q,), A, jnp.int32).at[r].set(
        jnp.arange(A, dtype=jnp.int32), mode="drop")
    src_tok = jnp.where(w_sel < A, w_sel // k, 0)
    return r, src_tok, w_sel, ok


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def _dense_base_ffn(x, weights, e_gate, e_up, e_down, r, src_tok, w_sel, k):
    y, _ = _dense_base_fwd_impl(x, weights, e_gate, e_up, e_down, r,
                                src_tok, w_sel, k)
    return y


def _dense_base_fwd_impl(x, weights, e_gate, e_up, e_down, r, src_tok,
                         w_sel, k):
    """Routed SwiGLU over a dense [E*Q, h] base buffer; gathers only.

    Every data-movement op here — and in the hand-written vjp below — is a
    gather: the combine uses the fact that slots r[t*k:(t+1)*k] enumerate
    exactly token t's assignments, so both y (fwd) and dx (bwd) are k-way
    gathered sums instead of the scatter-add the autodiff of jnp.take
    would emit (measured 3 ms/layer on v5e — the single hottest op of the
    r3 MoE step)."""
    T, h = x.shape
    E, _, f = e_gate.shape
    dt = x.dtype
    xb = jnp.take(x, src_tok, axis=0)                    # [E*Q, h]
    gu = jnp.einsum("eqh,ehf->eqf", xb.reshape(E, -1, h),
                    jnp.concatenate([e_gate, e_up], axis=-1).astype(dt),
                    preferred_element_type=dt)
    z = jax.nn.silu(gu[..., :f]) * gu[..., f:]
    yb = jnp.einsum("eqf,efh->eqh", z, e_down.astype(dt),
                    preferred_element_type=dt)
    ycat = yb.reshape(-1, h)
    yg = jnp.take(ycat, r, axis=0).reshape(T, k, h).astype(jnp.float32)
    w = weights.reshape(T, k).astype(jnp.float32)
    y = jnp.sum(yg * w[..., None], axis=1).astype(dt)
    return y, (x, weights, e_gate, e_up, e_down, r, src_tok, w_sel, xb,
               gu, z, ycat)


def _dense_base_fwd(x, weights, e_gate, e_up, e_down, r, src_tok, w_sel, k):
    return _dense_base_fwd_impl(x, weights, e_gate, e_up, e_down, r,
                                src_tok, w_sel, k)


def _dense_base_bwd(k, res, dy):
    (x, weights, e_gate, e_up, e_down, r, src_tok, w_sel, xb, gu, z,
     ycat) = res
    T, h = x.shape
    E, _, f = e_gate.shape
    dt = x.dtype
    A = T * k
    w = weights.reshape(A).astype(jnp.float32)

    # router-weight grad: d_w[a] = <dy[tok(a)], ycat[r[a]]>
    yg = jnp.take(ycat, r, axis=0).reshape(T, k, h).astype(jnp.float32)
    d_w = jnp.einsum("th,tkh->tk", dy.astype(jnp.float32), yg)

    # d_ycat: per-slot weight via the slot->assignment map from the
    # residuals (0 for empty slots), dy row via src_tok — gathers, not
    # the take-vjp scatter.
    w_slot = jnp.where(w_sel < A, jnp.take(w, jnp.minimum(w_sel, A - 1)),
                       0.0)
    d_yb = (jnp.take(dy, src_tok, axis=0).astype(jnp.float32)
            * w_slot[:, None]).astype(dt).reshape(E, -1, h)

    dz = jnp.einsum("eqh,efh->eqf", d_yb, e_down.astype(dt),
                    preferred_element_type=dt)
    d_down = jnp.einsum("eqf,eqh->efh", z, d_yb,
                        preferred_element_type=jnp.float32)
    g, u = gu[..., :f], gu[..., f:]
    sg = jax.nn.sigmoid(g.astype(jnp.float32)).astype(dt)
    silu_g = g * sg
    d_u = dz * silu_g
    d_g = dz * u * (sg + silu_g * (1 - sg)).astype(dt)
    dgu = jnp.concatenate([d_g, d_u], axis=-1)
    xbr = xb.reshape(E, -1, h)
    d_w1 = jnp.einsum("eqh,eqf->ehf", xbr, dgu,
                      preferred_element_type=jnp.float32)
    d_gate, d_up = d_w1[..., :f], d_w1[..., f:]
    d_xb = jnp.einsum("eqf,ehf->eqh",
                      dgu, jnp.concatenate([e_gate, e_up],
                                           axis=-1).astype(dt),
                      preferred_element_type=dt).reshape(-1, h)
    # dx[t] = sum_j d_xb[slot of assignment (t, j)] — gather by r again
    dx = jnp.sum(jnp.take(d_xb, r, axis=0).reshape(T, k, h)
                 .astype(jnp.float32), axis=1).astype(dt)
    return (dx, d_w.reshape(weights.shape),
            d_gate.astype(e_gate.dtype), d_up.astype(e_up.dtype),
            d_down.astype(e_down.dtype), None, None, None)


_dense_base_ffn.defvjp(_dense_base_fwd, _dense_base_bwd)


def dropless_moe_ffn_dense(x, weights, idx, e_gate, e_up, e_down,
                           slack: float = 0.125,
                           routing: Optional[Routing] = None,
                           plan: Optional[DispatchPlan] = None):
    """Capacity-less routed FFN, dense-base form (single program).

    The TPU-first reshape of the reference's unbounded global_scatter
    (moe_layer.py:105-188): instead of ragged grouped GEMMs over
    expert-sorted rows, scatter-free gathers stage each expert's tokens
    into a static [E, Q, h] buffer (Q = A/E rounded up with ``slack``
    headroom) and the expert FFN runs as *dense batched einsums* — 92% MXU
    on v5e vs 63% for the best-tiled Mosaic grouped matmul at the bench
    shapes, because XLA tiles a fixed-shape batched dot far better than
    any ragged kernel. Nothing is dropped: a lax.cond falls back to the
    sort+gmm path (`dropless_moe_ffn`) for the rare batch whose expert
    load exceeds Q, so the fast path's capacity is a *performance* bound,
    never a semantic one (vs. the reference's GShard capacity which
    silently drops — see MoEConfig.routing="capacity").

    Cost of the headroom: Q/(A/E)-1 wasted dense FLOPs (12.5% default) on
    empty slots whose outputs are never gathered; with balanced routing
    (what the aux loss maintains) the fallback fires with probability
    ~Phi(-5 sigma) per step.

    ``routing`` (from :func:`fused_routing`) is reused when this shape
    skips the dense base entirely; ``plan`` skips re-deriving Q when the
    caller already holds the shared :class:`DispatchPlan`."""
    T, h = x.shape
    E = e_gate.shape[0]
    k = idx.shape[1]
    if plan is None:
        plan = plan_dispatch(T, k, E, h, slack=slack)
    Q = plan.Q
    if not plan.use_dense:
        return dropless_moe_ffn(x, weights, idx, e_gate, e_up, e_down,
                                routing=routing)
    r, src_tok, w_sel, ok = _dense_meta(idx, E, Q)
    # the overflow fallback must NOT capture routing.order/tok: cond
    # operands are computed unconditionally every step, while work inside
    # the untaken branch is not — re-deriving the sort in the ~never-taken
    # branch keeps the argsort off the steady-state dense path (the
    # prologue's sort metadata is DCE'd when nothing else consumes it)
    return jax.lax.cond(
        ok,
        lambda x, w, i: _dense_base_ffn(x, w, e_gate, e_up, e_down, r,
                                        src_tok, w_sel, k),
        lambda x, w, i: dropless_moe_ffn(x, w, i, e_gate, e_up, e_down),
        x, weights, idx)


def dropless_moe_ffn(x, weights, idx, e_gate, e_up, e_down,
                     routing: Optional[Routing] = None):
    """Capacity-less routed FFN, single-program (GSPMD) form.

    x: [T,h]; weights/idx: [T,k] from the router; experts [E,h,f]/[E,f,h].
    Every assignment is computed — there is no capacity C and nothing to
    drop (reference semantics: moe_layer.py global_scatter with unbounded
    per-expert counts).

    With ``routing`` (the :func:`fused_routing` prologue) the sort
    permutation and group sizes are reused instead of re-derived."""
    T, h = x.shape
    E = e_gate.shape[0]
    dt = x.dtype
    if routing is None:
        order, tok, flat_e = sort_by_expert(idx)
        gs = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)
    else:
        order, tok, gs = routing.order, routing.tok, routing.gs
    xs = jnp.take(x, tok, axis=0)                         # [T*k, h]
    # every assignment belongs to a real expert → sum(gs) == T*k
    ys = _expert_ffn(xs, gs, e_gate, e_up, e_down, dt, full_rows=True)
    ws = weights.reshape(T * idx.shape[1])[order].astype(jnp.float32)
    y = jnp.zeros((T, h), jnp.float32).at[tok].add(
        ys.astype(jnp.float32) * ws[:, None])
    return y.astype(dt)


def dropless_moe_ffn_fused(x, weights, idx, e_gate, e_up, e_down,
                           routing: Optional[Routing] = None):
    """Capacity-less routed FFN, fused scatter-free form — see
    :func:`paddle_tpu.kernels.moe_fused.fused_moe_ffn` (same grouped
    GEMMs as :func:`dropless_moe_ffn`, gather-only data movement in both
    directions, Pallas gather-GMM kernel on TPU, int8 expert dicts)."""
    from .moe_fused import fused_moe_ffn

    return fused_moe_ffn(x, weights, idx, e_gate, e_up, e_down,
                         routing=routing)


def _ep_partial(x_l, w_l, idx_l, eg_l, eu_l, ed_l, *, El, me, dt):
    """Routed partial sums for one token slice: local tokens × local
    expert shard, pre-psum [T_slice, h] f32.

    Assignments routed to foreign experts sort to the tail and get combine
    weight 0; the caller's psum sums each token's k partial expert outputs
    across the ep ranks that own them."""
    Tl, k = idx_l.shape
    A = Tl * k

    flat_e = idx_l.reshape(A)
    lid = flat_e - me * El
    mine = (lid >= 0) & (lid < El)
    order = jnp.argsort(jnp.where(mine, lid, El))         # foreign → tail
    tok = order // k
    xs = jnp.take(x_l.astype(dt), tok, axis=0)
    gs = jnp.zeros((El,), jnp.int32).at[jnp.where(mine, lid, 0)].add(
        mine.astype(jnp.int32))
    ys = _expert_ffn(xs, gs, eg_l, eu_l, ed_l, dt)
    ws = jnp.where(mine, w_l.reshape(A), 0.0)[order].astype(jnp.float32)
    return jnp.zeros((Tl, x_l.shape[1]), jnp.float32).at[tok].add(
        ys.astype(jnp.float32) * ws[:, None])


def _overlap_bypassed(shared_w, Tl: int) -> bool:
    """True when the double-buffered-halves overlap should not run for a
    per-rank token slice of ``Tl``: no shared-expert FFN to hide behind,
    an un-halvable slice, or a slice below ``FLAGS_moe_overlap_min_tokens``
    — on small slices the halved grouped GEMMs lose more MXU efficiency
    than the collective hiding buys (the r05 bisect lever), so single
    buffering wins. Threshold bypasses are counted per traced call site
    in ``moe_overlap_bypass_total``."""
    if shared_w is None or Tl < 2 or Tl % 2:
        return True
    if Tl < int(get_flag("moe_overlap_min_tokens")):
        _M_OVERLAP_BYPASS.inc()
        return True
    return False


def _ep_local(x_l, w_l, idx_l, eg_l, eu_l, ed_l, shared_w=None, *,
              num_experts_local, compute_dtype):
    """Per-(data,ep)-rank body of the psum strategy. Boundary tensors are
    f32 (see the caller); the grouped GEMMs run in ``compute_dtype``
    (bf16 on TPU → MXU).

    With ``shared_w`` the token slice is processed as double-buffered
    halves: half 0's combine psum is issued while half 1's grouped GEMMs
    run, and the shared-expert FFN fills the remaining collective
    shadow — the psum never sits on the critical path alone."""
    El = num_experts_local
    me = jax.lax.axis_index("ep")
    dt = compute_dtype
    Tl = x_l.shape[0]
    part = functools.partial(_ep_partial, eg_l=eg_l, eu_l=eu_l, ed_l=ed_l,
                             El=El, me=me, dt=dt)
    if _overlap_bypassed(shared_w, Tl):
        y = jax.lax.psum(part(x_l, w_l, idx_l), "ep")
        if shared_w is not None:
            y = y + _shared_swiglu(x_l, *shared_w, dt).astype(jnp.float32)
        return y
    H = Tl // 2
    y0 = part(x_l[:H], w_l[:H], idx_l[:H])
    p0 = jax.lax.psum(y0, "ep")           # in flight while half 1 computes
    y1 = part(x_l[H:], w_l[H:], idx_l[H:])
    p1 = jax.lax.psum(y1, "ep")           # hidden by the shared FFN below
    s = _shared_swiglu(x_l, *shared_w, dt).astype(jnp.float32)
    return jnp.concatenate([p0, p1], axis=0) + s


def dropless_moe_ffn_ep(x, weights, idx, e_gate, e_up, e_down, mesh: Mesh,
                        token_axes: Tuple[str, ...] = ("dp",),
                        shared: Optional[Tuple] = None):
    """Explicit expert-parallel dropless FFN (partial-manual shard_map).

    Token tensors are sharded over ``token_axes`` and replicated over 'ep';
    experts are sharded over 'ep' on their leading axis. Axes not named
    ('tp' fsdp etc.) stay under GSPMD control, so this nests inside a fully
    sharded train step.

    ``shared=(s_gate, s_up, s_down)`` moves the always-on shared-expert
    FFN *inside* the shard_map body so its compute overlaps the combine
    psum (double-buffered halves, see :func:`_ep_local`); the return
    value is then routed + shared.

    The shard_map boundary is kept f32: differentiating a bf16-carrying
    partial-manual shard_map inside ``lax.scan`` hits an XLA:CPU compiler
    check failure ("Invalid binary instruction opcode copy"); f32 in/out
    with bf16 compute inside the body sidesteps it, costs one fused convert
    on TPU, and makes the k-way combine psum f32-accurate."""
    E = e_gate.shape[0]
    ep = dict(mesh.shape).get("ep", 1)
    if ep <= 1 or E % ep != 0:
        _M_FALLBACKS.labels(reason="ep_shape_mismatch").inc()
        y = dropless_moe_ffn(x, weights, idx, e_gate, e_up, e_down)
        if shared is not None:
            y = y + _shared_swiglu(x, *shared, x.dtype)
        return y
    dt = x.dtype
    tok_axes = tuple(a for a in token_axes if dict(mesh.shape).get(a, 1) > 1)
    tok_spec = P(tok_axes if tok_axes else None)
    body = functools.partial(_ep_local, num_experts_local=E // ep,
                             compute_dtype=dt)
    if shared is None:
        fn = jax.shard_map(
            lambda xl, wl, il, g, u, d: body(xl, wl, il, g, u, d),
            mesh=mesh,
            in_specs=(tok_spec, tok_spec, tok_spec, P("ep"), P("ep"),
                      P("ep")),
            out_specs=tok_spec,
            axis_names=set(tok_axes) | {"ep"},
            check_vma=False)
        return fn(x.astype(jnp.float32), weights, idx,
                  e_gate, e_up, e_down).astype(dt)
    fn = jax.shard_map(
        lambda xl, wl, il, g, u, d, sg, su, sd: body(
            xl, wl, il, g, u, d, (sg, su, sd)),
        mesh=mesh,
        in_specs=(tok_spec, tok_spec, tok_spec, P("ep"), P("ep"), P("ep"),
                  P(None), P(None), P(None)),
        out_specs=tok_spec,
        axis_names=set(tok_axes) | {"ep"},
        check_vma=False)
    return fn(x.astype(jnp.float32), weights, idx, e_gate, e_up, e_down,
              *shared).astype(dt)


def _a2a_exchange(x_h, w_h, idx_h, *, E, El, R):
    """Stage 1 of the ragged exchange for one token slice: expert-sort,
    size all_gather, and the payload + expert-id ragged all-to-alls
    (both in flight when this returns — consume late)."""
    me = jax.lax.axis_index("ep")
    Tl, k = idx_h.shape
    A = Tl * k
    Amax = A * R
    h = x_h.shape[1]
    dt = x_h.dtype

    flat_e = idx_h.reshape(A)
    order = jnp.argsort(flat_e)                    # expert order == rank order
    tok = order // k
    xs = jnp.take(x_h, tok, axis=0)                # [A,h] send buffer
    eid_send = flat_e[order]

    dest = flat_e // El
    send_sizes = jnp.zeros((R,), jnp.int32).at[dest].add(1)
    sizes = jax.lax.all_gather(send_sizes, "ep")   # [sender, dest]
    in_off = jnp.cumsum(send_sizes) - send_sizes
    recv_sizes = sizes[:, me]
    out_off = (jnp.cumsum(sizes, axis=0) - sizes)[me]

    xr = jax.lax.ragged_all_to_all(
        xs, jnp.zeros((Amax, h), dt),
        in_off, send_sizes, out_off, recv_sizes, axis_name="ep")
    er = jax.lax.ragged_all_to_all(
        eid_send, jnp.full((Amax,), E, jnp.int32),
        in_off, send_sizes, out_off, recv_sizes, axis_name="ep")
    state = (order, tok, w_h, sizes, send_sizes, recv_sizes)
    return xr, er, state


def _a2a_ffn(xr, er, eg_l, eu_l, ed_l, *, E, El):
    """Stage 2: group the received rows by local expert and run the
    grouped-GEMM SwiGLU (padding rows sort to a zero-weight tail)."""
    dt = xr.dtype
    me = jax.lax.axis_index("ep")
    lid = jnp.where(er < E, er - me * El, El)      # padding → tail group
    order2 = jnp.argsort(lid)
    xg = jnp.take(xr, order2, axis=0)
    valid = lid < El
    gs = jnp.zeros((El,), jnp.int32).at[jnp.where(valid, lid, 0)].add(
        valid.astype(jnp.int32))
    yg = _expert_ffn(xg, gs, eg_l, eu_l, ed_l, dt)
    return jnp.zeros_like(yg).at[order2].set(yg)   # back to receive order


def _a2a_combine(yr, state, *, h):
    """Stage 3: reverse ragged all-to-all + gate-weighted combine for one
    token slice. Returns [T_slice, h] f32."""
    me = jax.lax.axis_index("ep")
    order, tok, w_h, sizes, send_sizes, recv_sizes = state
    Tl, k = w_h.shape
    A = Tl * k
    dt = yr.dtype
    rev_in_off = jnp.cumsum(recv_sizes) - recv_sizes
    rev_out_off = (jnp.cumsum(sizes, axis=1) - sizes)[:, me]
    ys = jax.lax.ragged_all_to_all(
        yr, jnp.zeros((A, h), dt),
        rev_in_off, recv_sizes, rev_out_off, send_sizes, axis_name="ep")
    ws = w_h.reshape(A)[order].astype(jnp.float32)
    return jnp.zeros((Tl, h), jnp.float32).at[tok].add(
        ys.astype(jnp.float32) * ws[:, None])


def _a2a_local(x_l, w_l, idx_l, eg_l, eu_l, ed_l, shared_w=None, *,
               num_experts, num_experts_local, ep_size):
    """Per-ep-rank body of the ragged-all-to-all exchange (reference's
    global_scatter → grouped GEMM → global_gather, TPU collectives).

    With ``shared_w`` the slice is processed as double-buffered halves:
    both halves' forward exchanges are issued back to back, the shared-
    expert FFN computes in their shadow, and half 0's reverse exchange
    hides behind half 1's grouped GEMMs."""
    E, El, R = num_experts, num_experts_local, ep_size
    Tl = x_l.shape[0]
    h = x_l.shape[1]
    dt = x_l.dtype

    def one(x_h, w_h, idx_h):
        xr, er, st = _a2a_exchange(x_h, w_h, idx_h, E=E, El=El, R=R)
        yr = _a2a_ffn(xr, er, eg_l, eu_l, ed_l, E=E, El=El)
        return _a2a_combine(yr, st, h=h)

    if _overlap_bypassed(shared_w, Tl):
        y = one(x_l, w_l, idx_l)
        if shared_w is not None:
            y = y + _shared_swiglu(x_l, *shared_w, dt).astype(jnp.float32)
        return y.astype(dt)
    H = Tl // 2
    xr0, er0, st0 = _a2a_exchange(x_l[:H], w_l[:H], idx_l[:H],
                                  E=E, El=El, R=R)
    xr1, er1, st1 = _a2a_exchange(x_l[H:], w_l[H:], idx_l[H:],
                                  E=E, El=El, R=R)
    s = _shared_swiglu(x_l, *shared_w, dt)         # hides both exchanges
    yr0 = _a2a_ffn(xr0, er0, eg_l, eu_l, ed_l, E=E, El=El)
    y0 = _a2a_combine(yr0, st0, h=h)               # reverse a2a of half 0…
    yr1 = _a2a_ffn(xr1, er1, eg_l, eu_l, ed_l, E=E, El=El)  # …hides here
    y1 = _a2a_combine(yr1, st1, h=h)
    y = jnp.concatenate([y0, y1], axis=0) + s.astype(jnp.float32)
    return y.astype(dt)


def dropless_moe_ffn_a2a(x, weights, idx, e_gate, e_up, e_down, mesh: Mesh,
                         token_axes: Tuple[str, ...] = ("dp", "ep"),
                         shared: Optional[Tuple] = None):
    """Ragged-all-to-all dropless FFN: tokens sharded over ``token_axes``
    (which always includes 'ep'), exchanged to expert owners within each ep
    group and back (the literal global_scatter/global_gather shape — only
    ~T*k/ep assignments are GEMM'd per rank, vs the psum strategy's T*k).
    Requires a backend with a ragged-all-to-all lowering — real TPU;
    XLA:CPU raises UNIMPLEMENTED, so CPU tests use the _ep/psum strategy
    (a lowering-only test pins the wiring).

    ``shared=(s_gate, s_up, s_down)`` fuses the shared-expert FFN into the
    body so the exchanges hide behind it (see :func:`_a2a_local`)."""
    E = e_gate.shape[0]
    ep = dict(mesh.shape).get("ep", 1)
    T = x.shape[0]
    tok_axes = tuple(dict.fromkeys(
        a for a in (*token_axes, "ep") if dict(mesh.shape).get(a, 1) > 1))
    n_tok_shards = int(np.prod([dict(mesh.shape)[a] for a in tok_axes])) \
        if tok_axes else 1
    if ep <= 1 or E % ep != 0 or T % max(n_tok_shards, 1) != 0:
        _M_FALLBACKS.labels(reason="ep_shape_mismatch").inc()
        y = dropless_moe_ffn(x, weights, idx, e_gate, e_up, e_down)
        if shared is not None:
            y = y + _shared_swiglu(x, *shared, x.dtype)
        return y
    tok_spec = P(tok_axes)
    body = functools.partial(_a2a_local, num_experts=E,
                             num_experts_local=E // ep, ep_size=ep)
    if shared is None:
        fn = jax.shard_map(
            lambda xl, wl, il, g, u, d: body(xl, wl, il, g, u, d),
            mesh=mesh,
            in_specs=(tok_spec, tok_spec, tok_spec, P("ep"), P("ep"),
                      P("ep")),
            out_specs=tok_spec,
            axis_names=set(tok_axes) | {"ep"},
            check_vma=False)
        return fn(x, weights, idx, e_gate, e_up, e_down)
    fn = jax.shard_map(
        lambda xl, wl, il, g, u, d, sg, su, sd: body(
            xl, wl, il, g, u, d, (sg, su, sd)),
        mesh=mesh,
        in_specs=(tok_spec, tok_spec, tok_spec, P("ep"), P("ep"), P("ep"),
                  P(None), P(None), P(None)),
        out_specs=tok_spec,
        axis_names=set(tok_axes) | {"ep"},
        check_vma=False)
    return fn(x, weights, idx, e_gate, e_up, e_down, *shared)


# ---------------------------------------------------------------------------
# the served expert layer: one chip's share of an expert-parallel deployment
# ---------------------------------------------------------------------------
def group_limited_routing(probs, n_group: int, topk_group: int, top_k: int,
                          scale: float):
    """Group-limited greedy routing (DeepSeek-V2's device-limited routing):
    ``probs`` [T, E] are the router's softmax over ALL experts; the experts
    form ``n_group`` groups of E / n_group consecutive ones; a group scores
    the max of its experts; the best ``topk_group`` groups stay, the rest
    are masked to 0; the ``top_k`` best of what is left are chosen. Gates
    are ``scale`` times the chosen probabilities, not renormalised. Ties go
    to the lower index (``lax.top_k``), groups and experts alike. Returns
    (gates [T, top_k] f32, idx [T, top_k] int32)."""
    T, E = probs.shape
    per = E // n_group
    group = probs.reshape(T, n_group, per).max(axis=-1)
    masked = jnp.where(_kept_groups(group, topk_group, per), probs, 0.0)
    w, idx = jax.lax.top_k(masked, top_k)
    return w * scale, idx.astype(jnp.int32)


def _kept_groups(group, topk_group: int, per: int):
    """[T, n_group * per] bool: the experts of each row's best
    ``topk_group`` groups by ``group`` [T, n_group] (ties to the lower
    index)."""
    T, n_group = group.shape
    _, keep = jax.lax.top_k(group, topk_group)                    # [T, g]
    kept = jnp.zeros((T, n_group), bool).at[
        jnp.arange(T)[:, None], keep].set(True)
    return jnp.repeat(kept, per, axis=1)


def sigmoid_bias_routing(scores, bias, top_k: int, scale: float = 1.0,
                         renorm: bool = True, n_group: int = 1,
                         topk_group: int = 1, eps: float = 1e-6):
    """Sigmoid routing with a selection bias (LFM2's, and the
    auxiliary-loss-free balancing it comes from): ``scores`` [T, E] are
    the router's sigmoids in float32, each expert's own; ``bias`` [E]
    float32 is added for the SELECTION only (``top_k`` of ``scores +
    bias``, ties to the lower index) and enters no weight; the gates are
    the chosen experts' unbiased scores, divided by their sum plus
    ``eps`` (LFM2's and Ling's 1e-6; Trinity's published 1e-20) where
    ``renorm``, times ``scale``. Returns (gates [T, top_k] f32, idx [T,
    top_k] int32).

    ``n_group`` > 1 limits the selection to groups first (DeepSeek-V3's
    ``noaux_tc``): the experts form ``n_group`` groups of consecutive
    ones, a group scores the SUM OF ITS TOP TWO biased scores, the best
    ``topk_group`` groups stay (ties to the lower index) and the ``top_k``
    are chosen among their experts only. The sum that renormalises runs
    over all the chosen, wherever they are held."""
    biased = scores + bias[None, :].astype(scores.dtype)
    if n_group > 1:
        T, E = scores.shape
        per = E // n_group
        top2, _ = jax.lax.top_k(biased.reshape(T, n_group, per), 2)
        biased = jnp.where(
            _kept_groups(jnp.sum(top2, axis=-1), topk_group, per), biased,
            -jnp.inf)
    _, idx = jax.lax.top_k(biased, top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if renorm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + eps)
    return w * scale, idx.astype(jnp.int32)


# tokens of a call up to which ALL its pairs are gathered at once, whatever
# the top-k: a prefill piece with the decode rows that ride in its program
# (Mellum2's 1,024 + 32 tokens x 8 = 8,448 pairs), where k times the call is
# still small; a wider wave walks its sorted pairs in passes of about one
# row a token, so that memory is bounded by the wave and not by k times it
_HELD_PASS_TOKENS = 2048
# pairs of a pass up to which a call is a decode step: its pairs stay packed
# one after another; over it (a prefill piece) every held expert's rows start
# on a row-tile boundary of the grouped matmul
_HELD_SMALL_ROWS = 1024
# the longest side of an expert's matrices up to which the whole contraction
# is one tile (DeepSeek-V2's 5120 x 1536 lies above it and keeps its tiles)
_HELD_NARROW = 4096
# the fast memory a Mosaic kernel may take on a v5e unless its caller raises
# the limit, which ``megablox.gmm`` offers no argument for
_GMM_VMEM = 16 << 20


def _mosaic() -> bool:
    """Whether the grouped matmul lowers to the Mosaic kernel (a TPU)."""
    return jax.default_backend() == "tpu"


def _row_tile() -> int:
    """Rows of the grouped matmul's row tile under ``held_expert_ffn``:
    the Mosaic kernel's 128, ``ragged_dot``'s nominal 8 off a TPU."""
    return 128 if _mosaic() else 8


def _static_gmm(xs, w, gs):
    """Grouped matmul with its tiling chosen by a RULE from the shapes,
    never timed: two runs of one commit run the same kernel and no set-up
    carries a tuning. Off a TPU, ``ragged_dot``. Rows past sum(gs) come
    back zero.

    The row tile is 128 whatever the rows: a decode step has a few rows an
    expert, and a piece's experts each start on a tile boundary
    (``held_expert_ffn``), so a taller tile only adds rows of padding for
    the MXU to work through. What the tile's other sides are follows from
    the expert's width. Read on the chip (PRs 28, 32, 33, 41; scratch
    ``output/chip*_micro*.py``):

    - a narrow expert (no side of its matrices over 4096: LFM2's 2048 x
      1792, Mellum2's 2304 x 896, Ling's 2560 x 768): the whole contraction
      in one tile, so an expert's weights stream once and no accumulator is
      revisited, and the widest column tile that divides the output side
      and fits (``_column_tile``). The kernel's grid has the column tile
      OUTERMOST: every row tile of ``x`` is read again for every column
      tile (``_gmm_bytes``), which a decode step's two row tiles do not
      feel and a piece's hundred do. Until PR 41 the column tile was the
      widest of (512, 256, 128) that divides the side, 256 for Mellum2's
      1792 and 2304: seven and nine passes over a piece's rows. Read on the
      chip, ms a call as the mean of 30 dispatched back to back (my chip
      runs, PR 41, ``output/chip41_micro.py``; a single call waited for
      reads 0.6 to 1.0 ms more, as PR 35's 2.16 / 1.63 did), the old
      rule's tile -> this one's, gate|up + down alone and then the WHOLE
      ``held_expert_ffn`` of a layer:
      Mellum2, a piece (13,440 rows visited of 16,640): 1.608 + 1.049 ->
      1.225 + 0.676, the function 2.90 -> 2.49; a decode step (256 packed
      rows): 0.766 + 0.501 -> 0.761 + 0.405, the function 1.23 -> 1.14.
      LFM2, a piece (7,040 of 8,448): 1.243 + 0.678 -> 1.137 + 0.610, the
      function 2.02 -> 1.97; a decode step (256): 0.717 + 0.382 -> 0.696 +
      0.382, the function 1.045 -> 1.050 (no gain, inside the noise).
      Ling, a piece (16,384 of 25,088): 1.844 + 1.493 -> 1.740 + 1.300, the
      function 4.14 -> 3.97; a decode step (512): 0.875 + 0.497 -> 0.886 +
      0.462, the function 1.36 -> 1.34. Alone the kernel follows its bytes
      to the digit; in the function it gains about half of that, because
      XLA keeps the gathered rows and the activation in the chip's fast
      memory (``S(1)`` in the compiled text), so the re-reads saved were
      not all HBM's. One rule serves a piece and a decode step: no branch
      on the row count. PR 33 read LFM2's (128, 512, 3584) and (128, 1792,
      2048) as 0.83 + 0.44 alone against 0.95 + 0.50 and as nothing in the
      whole function; the second reading stands (2.08 ms against 2.02 at
      the old rule), the first's gate|up half does not repeat (1.251
      against 1.243). Earlier readings of the same kernel (PRs 32, 33): a
      decode step's 256 rows 0.68 + 0.37 ms with the whole contraction in
      one tile against 0.92 + 0.46 at (128, 512, 1024); a piece's 2,948
      real pairs 0.95 + 0.50 on 8,192 aligned rows against 1.04 + 0.56
      packed: the layout wins a tenth.
    - any other (DeepSeek-V2's 5120 x 1536): (128, 512, 1024), or what of
      it divides the expert's sides. A decode step's 144 pairs: 4.2 ms of
      bytes a step against 11.7 at a 512-row tile (PR 28). A piece's ~840
      real pairs over 20 experts: 1.06 + 0.65 ms aligned against 1.99 +
      1.10 packed under ``heuristic_tilings``' (512, 512, 1024), where
      every one of ~21 visits works through 512 rows; (128, 512, 512) 1.26
      + 0.76."""
    m, k = xs.shape
    n = w.shape[-1]
    if _mosaic():
        if k % 128 or n % 128:
            raise ValueError(f"no static gmm tiling for rows={m} k={k} n={n}")
        if max(k, n) <= _HELD_NARROW:
            tile = (_row_tile(), k, _column_tile(k, n, xs.dtype.itemsize))
        else:
            tile = (_row_tile(),
                    next(t for t in (512, 256, 128) if k % t == 0),
                    next(t for t in (1024, 512, 256, 128) if n % t == 0))
        return _gmm_tuned(xs, w, gs, (tile, tile, tile), False)
    return jax.lax.ragged_dot(xs, w, gs)


def _gmm_footprint(k: int, tn: int, item: int) -> int:
    """Bytes of the chip's fast memory that ``megablox.gmm`` holds at a tile
    of ``(128, k, tn)``, reckoned from the kernel's text: the weight block
    and the row tile and the output tile twice each (the pipeline fetches
    the next while this one is worked on), the float32 accumulator, and the
    masked store's two float32 temporaries of the accumulator's size (the
    accumulator as read and the output tile widened to be selected
    against)."""
    tm = 128
    return 2 * item * (k * tn + tm * k + tm * tn) + 3 * 4 * tm * tn


def _column_tile(k: int, n: int, item: int) -> int:
    """The widest multiple of 128 that divides the output side ``n`` and
    whose tile, the whole contraction ``k`` deep, fits ``_GMM_VMEM``: the
    kernel reads every row tile of ``x`` again for every column tile, so
    the fewest column tiles move the fewest bytes, and a divisor leaves no
    side padded in HBM. The MXU asks for a multiple of 128, not for a
    power of two (Mellum2's 1792 takes 896 and its 2304 goes whole)."""
    return max(t for t in range(128, n + 1, 128)
               if n % t == 0 and _gmm_footprint(k, t, item) <= _GMM_VMEM)


def _gmm_bytes(rows: int, groups: int, k: int, n: int, tn: int,
               item: int = 2) -> int:
    """Bytes a call of the grouped matmul moves by ``megablox.gmm``'s own
    ``cost_estimate``, for ``rows`` rows in the row tiles it visits and
    ``groups`` groups with a row: the column tile is the grid's OUTERMOST
    side, so ``x`` is read once a column tile, a group's weights once (the
    whole contraction is one tile) and the output written once. Used by no
    program: it holds the arithmetic behind ``_column_tile``."""
    return item * (rows * k * (n // tn) + groups * k * n + rows * n)


def _tile_visits(gs, tile: int):
    """Row tiles the grouped matmul works through for groups of ``gs``
    rows laid one after another (``megablox.gmm.make_group_metadata``): a
    group with a row visits every tile it touches, so a tile that holds
    rows of two groups is worked through twice."""
    ends = jnp.cumsum(gs)
    starts = ends - gs
    return jnp.sum(jnp.where(gs > 0, -(-ends // tile) - starts // tile, 0))


def held_expert_ffn(x, gates, idx, valid, e_gu, e_down, first: int):
    """The routed part of an expert layer for the experts THIS chip holds:
    ``sum_{e in top-k(x), e held} gate_e * Expert_e(x)`` for every row of
    ``x`` [T, h]. ``idx``/``gates`` [T, k] come from a router over all
    experts; the held ones are ``first .. first + E_held - 1`` (``e_gu``
    [E_held, h, 2f] is gate and up side by side, ``e_down`` [E_held, f,
    h]); a pair routed elsewhere, or of a row that is not ``valid``
    (padding of a wave, an idle slot), is dropped here and loads no
    expert: other chips compute it, and nothing stands in for them.

    The pairs are sorted by held expert (foreign ones last), the rows of
    the held pairs gathered, one grouped matmul form run over them and the
    results combined. A call of up to ``_HELD_PASS_TOKENS`` tokens (a
    decode step, a prefill piece, a piece with the decode rows that ride in
    its program) gathers all its pairs at once; a wider prefill wave walks
    the sorted pairs in passes of about T rows, each under a ``cond`` on
    whether any held pair is left, so that memory is bounded by the wave
    and not by k times it, and no pair is dropped however skewed the
    routing (with even routing one pass in k runs). The bound is on the
    call's TOKENS: a bound on its pairs (8,192 until PR 36) stood exactly
    at Mellum2's piece of 1,024 x 8, and 32 decode rows more would have
    sent it to ~8 passes with the whole call's gather in each.

    The row layout of a pass follows from its static pair count M. A
    decode step (M up to ``_HELD_SMALL_ROWS``: a few rows an expert) keeps
    the sorted pairs packed, M rows, and scatter-adds the results: every
    expert's tile is visited once whatever the layout, and nothing around
    the kernel grows. A prefill piece (M over it: ~100 rows an expert)
    gives each held expert ``ceil(rows / tile) * tile`` rows, M + E x tile
    rows in all, so that every expert STARTS on a tile boundary and no tile
    is worked through for two experts; each token then gathers its own k
    rows of the result and sums them in float32, so nothing is
    scatter-added and the rows between the experts are never read (on the
    chip, a layer of LFM2's piece: 1.92 ms packed with the scatter-add,
    2.13 aligned with it, 1.73 packed with the gather, 1.45 aligned with
    it; PR 33).

    Returns (y [T, h] in x's dtype, counts): ``counts`` f32 [5] =
    [pairs routed by valid rows, pairs held here, held experts with a row,
    rows of the fullest held expert x held experts, row tiles the grouped
    matmul visits] (the fourth over the second is the fullest expert's
    load over the mean, and stays so when layers' counts are summed; the
    second over the fifth x the tile is the share of the MXU's rows that
    are real)."""
    T, h = x.shape
    k = idx.shape[1]
    E = e_gu.shape[0]
    f = e_down.shape[1]
    dt = x.dtype
    local = idx - first
    held = (local >= 0) & (local < E) & valid[:, None]
    local = jnp.where(held, local, E).reshape(T * k)              # E: foreign
    hot = local[:, None] == jnp.arange(E, dtype=local.dtype)[None, :]
    gs = jnp.sum(hot, axis=0).astype(jnp.int32)                   # [E]
    ends = jnp.cumsum(gs)
    total = ends[-1]
    gate = jnp.where(held, gates, 0.0)
    tile = _row_tile()
    M = -(-(T * k if T <= _HELD_PASS_TOKENS else T) // tile) * tile
    aligned = M > _HELD_SMALL_ROWS
    n_pass = -(-T * k // M)
    if aligned:
        # where each pair stands among the pairs sorted by expert and then
        # by row: its expert's first place + the earlier pairs of that expert
        mine = jnp.minimum(local, E - 1)
        place = (ends - gs)[mine] - 1 + jnp.sum(
            jnp.where(hot, jnp.cumsum(hot, axis=0, dtype=jnp.int32), 0),
            axis=1)
        tok = jnp.arange(T * k, dtype=jnp.int32) // k
    else:
        order = jnp.argsort(local)                # stable: by expert, by row
        pad = (0, n_pass * M - T * k)
        tok = jnp.pad((order // k).astype(jnp.int32), pad)
        gate = jnp.pad(gate.reshape(T * k)[order], pad)

    def one_pass(carry, p):
        y, visits = carry
        lo = p * M
        # this pass's slice of each group: [lo, lo + M) cut out of the
        # sorted pairs' group boundaries
        cut = jnp.clip(ends, lo, lo + M)
        starts = jnp.concatenate([jnp.clip(lo, 0, total)[None], cut[:-1]])
        gs_p = cut - starts
        if aligned:
            # a pair of this pass lies at its group's padded offset + its
            # rank in the cut; the rows between the groups gather row 0
            gs_p = -(-gs_p // tile) * tile
            here = held.reshape(T * k) & (place >= lo) & (place < lo + M)
            at = (jnp.cumsum(gs_p) - gs_p - starts)[mine] + place
            rows = jnp.zeros((M + E * tile,), jnp.int32).at[
                jnp.where(here, at, M + E * tile)].set(tok, mode="drop")
        else:
            rows = jax.lax.dynamic_slice(tok, (lo,), (M,))
        gu = _static_gmm(x[rows], e_gu.astype(dt), gs_p)
        out = _static_gmm(jax.nn.silu(gu[:, :f]) * gu[:, f:],
                          e_down.astype(dt), gs_p)
        visits = visits + _tile_visits(gs_p, tile)
        if aligned:
            # each token takes its own pairs' rows of ``out`` (a gather: no
            # row is added to another, and the padding is never read)
            here = here.reshape(T, k)
            part = jnp.einsum(
                "tkh,tk->th",
                out[jnp.where(here, at.reshape(T, k), 0)].astype(jnp.float32),
                jnp.where(here, gate, 0.0))
            return y + part.astype(dt), visits
        g = jax.lax.dynamic_slice(gate, (lo,), (M,))
        return y.at[rows].add(out * g[:, None].astype(dt)), visits

    carry = (jnp.zeros((T, h), dt), jnp.zeros((), jnp.int32))
    for p in range(n_pass):
        carry = one_pass(carry, p) if n_pass == 1 else jax.lax.cond(
            p * M < total, functools.partial(one_pass, p=p), lambda c: c,
            carry)
    y, visits = carry
    counts = jnp.stack([jnp.sum(valid) * k, total, jnp.sum(gs > 0),
                        jnp.max(gs) * E, visits]).astype(jnp.float32)
    return y, counts
