"""Fused dropless-MoE dispatch: the scatter-free grouped-GEMM hot path.

The r05 bisect (docs/moe.md, "r05 regression postmortem") localized the
MoE step's overhead to the dispatch data movement around the grouped
GEMMs: the gather→GEMM→scatter round-trips through HBM that the
FlashFuser line of work (PAPERS.md) argues should be fused across the
dispatch boundary. This module is that fusion, in two layers:

* **Portable XLA rewrite** (`fused_moe_ffn`, every backend): the routed
  FFN is restructured so that *no scatter exists in forward or backward*:

  - the combine weight ``w`` (and the int8 down-projection scales) are
    folded into the elementwise silu chain BEFORE the down GEMM — the
    post-GEMM ``[A, h]`` f32 weighting multiply disappears into an
    elementwise chain XLA already fuses;
  - the gate-weighted combine-scatter becomes a **gather**: token ``t``'s
    ``k`` routed outputs sit at known sorted positions (the inverse of the
    expert-sort permutation), so ``y[t] = Σ_j ys[inv[t, j]]`` — the same
    scatter→gather trade that made the dense-base form's combine 3 ms/layer
    cheaper on v5e, now applied to the grouped-GEMM form;
  - both gathers carry hand-written VJPs whose backward is *also* a pure
    gather (``d_ys[p] = dy[tok[p]]``, ``dx[t] = Σ_j d_xs[inv[t, j]]``),
    instead of the scatter-add ``jnp.take``'s autodiff would emit.

* **Pallas kernel** (`gather_gmm`, TPU): the expert-sort gather is folded
  into the grouped GEMM's lhs load — each row tile is DMA-gathered from
  the token activations in HBM directly into VMEM (no ``[A, h]`` gathered
  copy ever materializes in HBM), and int8 expert weights stream into
  VMEM *unconverted* (half the rhs bytes; dequantized in-register).
  Requires a per-group tile-padded row layout (built host-free in XLA int
  ops; padding rows carry combine weight 0, so they are exact no-ops in
  both directions). WITHDRAWN from selection: the TPU compiler refuses
  the kernel's one-row gather DMA (``GATHER_GMM_TPU_REFUSAL`` below), so
  ``FLAGS_moe_fused_kernel`` now defaults off and every backend runs the
  XLA rewrite. Turning the flag on selects the kernel again, and a
  failure to build it is an error, never a quiet other path.

Expert weights may be plain arrays or int8 dicts ``{"q": int8, "s": f32}``
from :func:`paddle_tpu.kernels.quant_matmul.quantize_grouped` — gate/up
scales ride the gu elementwise chain, down scales ride the combine-weight
chain (:mod:`quant_matmul`'s output-scaling idiom, grouped).

Path taken is visible as ``moe_gmm_fused_dispatch_total{path}`` with
path ∈ {pallas, xla}.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..framework.flags import define_flag, get_flag
from ..observability import numerics as _numerics
from ..observability.catalog import instrument as _instrument
from .quant_matmul import is_quantized_weight

define_flag("moe_fused_kernel", False,
            "use the Pallas gather-fused grouped-GEMM kernel for the "
            "fused MoE dispatch on TPU (off = the portable XLA rewrite "
            "everywhere). Off by default: the TPU compiler refuses the "
            "kernel (moe_fused.GATHER_GMM_TPU_REFUSAL)")

__all__ = ["fused_moe_ffn", "gather_gmm", "GATHER_GMM_TPU_REFUSAL"]

# What the TPU compiler says to gather_gmm (jax 0.9.0 / libtpu 0.0.34,
# tests/test_aot_chip_compile.py): the per-row gather DMA slices one row
# of the bf16 activations, whose HBM layout tiles rows in groups of 8
# (two to a sublane). Repairing it means another gather scheme.
GATHER_GMM_TPU_REFUSAL = (
    "Mosaic failed to compile TPU kernel: Slice shape along dimension 0 "
    "must be aligned to tiling (8), but is 1")

_M_FUSED = _instrument("moe_gmm_fused_dispatch_total")

# m tile of the gather-fused kernel: small keeps the per-group padding
# waste bounded (≤ E*(KTM-1) rows ≈ 6% at the bench shape)
_KTM = 128


# ---------------------------------------------------------------------------
# scatter-free gathers with gather-based VJPs
# ---------------------------------------------------------------------------

def _inverse_permutation(order):
    """inv with inv[order[p]] = p (an int scatter over [A] ids — the only
    scatter-shaped op left in the pipeline, 4 bytes/row)."""
    A = order.shape[0]
    return jnp.zeros((A,), jnp.int32).at[order].set(
        jnp.arange(A, dtype=jnp.int32))


@jax.custom_vjp
def _gather_rows(x, tok, inv2d):
    """xs[p] = x[tok[p]] — the dispatch gather, with a gather-based VJP.

    ``inv2d[t, j]`` is the row of ``xs`` holding token t's j-th
    assignment, so backward is ``dx[t] = Σ_j d_xs[inv2d[t, j]]`` — a
    k-way gathered sum instead of take's scatter-add transpose. Rows of
    ``xs`` not referenced by ``inv2d`` (per-group tile padding) must
    carry zero cotangents, which the combine-weight fold guarantees."""
    return jnp.take(x, tok, axis=0)


def _gather_rows_fwd(x, tok, inv2d):
    return jnp.take(x, tok, axis=0), (inv2d,)


def _gather_rows_bwd(res, d_xs):
    (inv2d,) = res
    T, k = inv2d.shape
    dx = jnp.sum(
        jnp.take(d_xs, inv2d.reshape(-1), axis=0)
        .reshape(T, k, d_xs.shape[1]).astype(jnp.float32), axis=1)
    return dx.astype(d_xs.dtype), None, None


_gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


@jax.custom_vjp
def _combine_rows(ys, inv2d, tok):
    """y[t] = Σ_j ys[inv2d[t, j]] in f32 — the combine, as a gather.

    The gate weights are already folded into ``ys``'s producer, so both
    directions are coefficient-free gathers: backward is
    ``d_ys[p] = dy[tok[p]]``. For padded layouts the extra rows receive
    the cotangent of token ``tok[p]`` even though they contributed
    nothing — exact anyway, because their folded combine weight is 0, so
    every downstream product vanishes."""
    T, k = inv2d.shape
    return jnp.sum(
        jnp.take(ys, inv2d.reshape(-1), axis=0)
        .reshape(T, k, ys.shape[1]).astype(jnp.float32), axis=1)


def _combine_rows_fwd(ys, inv2d, tok):
    return _combine_rows(ys, inv2d, tok), (jnp.zeros((), ys.dtype), tok)


def _combine_rows_bwd(res, dy):
    proto, tok = res
    return jnp.take(dy, tok, axis=0).astype(proto.dtype), None, None


_combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


# ---------------------------------------------------------------------------
# expert-weight unpacking (bf16 arrays or int8 {"q", "s"} leaves)
# ---------------------------------------------------------------------------

def _unpack(w):
    """-> (matrix, scales | None); int8 scales are constants
    (stop_gradient), so quantization never leaks into any grad."""
    if is_quantized_weight(w):
        return (jax.lax.stop_gradient(w["q"]),
                jax.lax.stop_gradient(w["s"]).astype(jnp.float32))
    return w, None


def _gate_up(e_gate, e_up, dt):
    """Concatenate gate|up into the single wide grouped GEMM rhs.
    Returns (Wcat [E, h, 2f] in dt or int8, scales [E, 2f] | None)."""
    qg, sg = _unpack(e_gate)
    qu, su = _unpack(e_up)
    if (sg is None) != (su is None):
        raise ValueError("e_gate/e_up must be both quantized or neither")
    cat = jnp.concatenate([qg, qu], axis=-1)
    if sg is None:
        return cat.astype(dt), None
    return cat, jnp.concatenate([sg, su], axis=-1)


def _grouped(xs, w, gs, full_rows):
    """grouped_matmul with inline int8 conversion (the convert fuses into
    the rhs read on the XLA path; the Pallas kernel reads int8 raw)."""
    from .moe_dispatch import grouped_matmul

    if w.dtype == jnp.int8:
        w = w.astype(xs.dtype)
    return grouped_matmul(xs, w, gs, full_rows=full_rows)


# ---------------------------------------------------------------------------
# Pallas gather-fused grouped GEMM (TPU)
# ---------------------------------------------------------------------------

def _kernel_tn(n: int, h: int = 0, rhs_itemsize: int = 2,
               x_itemsize: int = 2) -> Optional[int]:
    """Largest n tile that divides ``n`` AND keeps the kernel's VMEM
    residency inside the same ~15.5 MiB envelope gmm_autotune._fits is
    calibrated to: double-buffered rhs blocks (2*h*tn), the [tm, h] lhs
    gather scratch, and double-buffered [tm, tn] f32-accumulated output
    blocks. Selection by shape: a shape that would blow VMEM is screened
    out HERE (None = use the XLA rewrite), before the kernel is chosen."""
    for t in (512, 256, 128):
        if n % t:
            continue
        vmem = (2 * h * t * rhs_itemsize        # rhs double-buffered
                + _KTM * h * x_itemsize         # lhs gather scratch
                + 2 * _KTM * t * 4)             # out blocks (f32 acc)
        if vmem <= 15.5 * 2**20:
            return t
    return None


def gather_gmm(x, idx, rhs, gid, *, tm: int = _KTM,
               tn: Optional[int] = None, out_dtype=None,
               interpret: bool = False):
    """``out[i*tm + r] = x[idx[i*tm + r]] @ rhs[gid[i]]`` — a grouped
    matmul whose lhs rows are DMA-gathered from ``x`` (HBM) inside the
    kernel: the expert-sort gather folded into the GEMM lhs load, the
    FlashFuser move. Each m tile belongs to ONE group (``gid`` per tile,
    scalar-prefetched), which the caller guarantees via the per-group
    tile-padded layout. int8 ``rhs`` streams to VMEM unconverted and is
    widened in-register.

    The gather runs once per m tile (at the first n step) into a VMEM
    scratch reused across the n tiles; output stores are plain blocked
    writes — with the combine weight folded into the lhs by the caller,
    the store IS the weighted combine contribution, and no scatter
    follows."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    A_pad = idx.shape[0]
    T, h = x.shape
    E, h2, n = rhs.shape
    assert h2 == h and A_pad % tm == 0
    tn = tn or _kernel_tn(n, h, rhs.dtype.itemsize, x.dtype.itemsize)
    if tn is None or h % 128:
        raise ValueError(f"gather_gmm: unaligned/oversized shape "
                         f"h={h} n={n}")
    out_dtype = out_dtype or x.dtype
    grid = (A_pad // tm, n // tn)

    def kernel(idx_ref, gid_ref, x_hbm, rhs_ref, out_ref, xs_vmem, sem):
        i = pl.program_id(0)
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _gather():                 # once per m tile, reused over n
            def body(r, _):
                row = idx_ref[i * tm + r]
                cp = pltpu.make_async_copy(
                    x_hbm.at[row], xs_vmem.at[r], sem)
                cp.start()
                cp.wait()
                return 0
            jax.lax.fori_loop(0, tm, body, 0)

        lhs = xs_vmem[...]
        blk = rhs_ref[0]
        if blk.dtype != lhs.dtype:     # int8 weights: widen in-register
            blk = blk.astype(lhs.dtype)
        out_ref[...] = jax.lax.dot_general(
            lhs, blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(out_ref.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),           # x stays in HBM
            pl.BlockSpec((1, h, tn),
                         lambda i, j, idx_ref, gid_ref: (gid_ref[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, *_: (i, j)),
        scratch_shapes=[
            pltpu.VMEM((tm, h), x.dtype),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((A_pad, n), out_dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(idx, gid, x, rhs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _gather_gmm_op(x, tok_pad, inv2d, rhs, gs_pad, full_rows):
    """Differentiable wrapper: forward is the Pallas kernel; backward is
    the standard megablox dgrad/wgrad over the rematerialized gather
    (padding rows carry zero cotangents — see the combine-weight fold)."""
    gid = _tile_gids(gs_pad, tok_pad.shape[0], _KTM)
    return gather_gmm(x, tok_pad, rhs, gid, tm=_KTM)


def _tile_gids(gs_pad, A_pad, tm):
    """Group id of each m tile of the padded layout (every tile lies
    inside one group by construction; tail tiles clamp to the last)."""
    E = gs_pad.shape[0]
    starts = jnp.arange(A_pad // tm, dtype=jnp.int32) * tm
    gid = jnp.searchsorted(jnp.cumsum(gs_pad), starts, side="right")
    return jnp.minimum(gid, E - 1).astype(jnp.int32)


def _gather_gmm_fwd(x, tok_pad, inv2d, rhs, gs_pad, full_rows):
    out = _gather_gmm_op(x, tok_pad, inv2d, rhs, gs_pad, full_rows)
    return out, (x, tok_pad, inv2d, rhs, gs_pad)


def _gather_gmm_bwd(full_rows, res, g):
    from .gmm_autotune import get_tilings
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        gmm as _gmm, tgmm as _tgmm)

    x, tok_pad, inv2d, rhs, gs_pad = res
    T, h = x.shape
    E, _, n = rhs.shape
    m = tok_pad.shape[0]
    dt = x.dtype
    w = rhs.astype(dt) if rhs.dtype == jnp.int8 else rhs
    tri = get_tilings(m, h, n, E, dt, bool(full_rows), variant="fused")
    if tri is None:
        # unaligned for megablox: the ragged_dot transpose handles it
        xs = jnp.take(x, tok_pad, axis=0)
        _, vjp = jax.vjp(
            lambda a, b: jax.lax.ragged_dot(a, b, gs_pad), xs, w)
        d_xs, d_rhs = vjp(g)
    else:
        d_xs = _gmm(g, w, gs_pad, preferred_element_type=dt,
                    tiling=tri[1], transpose_rhs=True)
        xs = jnp.take(x, tok_pad, axis=0)
        d_rhs = _tgmm(xs.swapaxes(0, 1), g, gs_pad,
                      preferred_element_type=jnp.float32, tiling=tri[2],
                      num_actual_groups=E)
    Tk = inv2d.shape
    dx = jnp.sum(
        jnp.take(d_xs, inv2d.reshape(-1), axis=0)
        .reshape(Tk[0], Tk[1], h).astype(jnp.float32), axis=1).astype(dt)
    if rhs.dtype == jnp.int8:
        d_rhs = None                   # int8 experts are frozen
    else:
        d_rhs = d_rhs.astype(rhs.dtype)
    return dx, None, None, d_rhs, None


_gather_gmm_op.defvjp(_gather_gmm_fwd, _gather_gmm_bwd)


# ---------------------------------------------------------------------------
# the fused routed FFN
# ---------------------------------------------------------------------------

def _routing_meta(x, weights, idx, routing):
    from .moe_dispatch import sort_by_expert

    T, k = idx.shape
    if routing is None:
        order, tok, flat_e = sort_by_expert(idx)
        E = None
        gs = None
    else:
        order, tok, flat_e, gs = (routing.order, routing.tok,
                                  routing.flat_e, routing.gs)
    return order, tok, flat_e, gs


def _elementwise_core(gu, s_gu, ws, s_down, esorted, f, dt):
    """silu(g)·u with every per-row coefficient folded in: the combine
    weight, and (int8) the gate/up output scales + down input scales.
    One fused elementwise chain — the coefficients ride for free."""
    if s_gu is not None:
        gu = gu * jnp.take(s_gu, esorted, axis=0).astype(gu.dtype)
    z = jax.nn.silu(gu[..., :f]) * gu[..., f:]
    coef = ws
    zw = z * coef.astype(dt)[:, None]
    if s_down is not None:
        zw = zw * jnp.take(s_down, esorted, axis=0).astype(dt)
    return zw


def fused_moe_ffn(x, weights, idx, e_gate, e_up, e_down,
                  routing=None):
    """Capacity-less routed FFN, fused scatter-free form (single program).

    Semantically identical to :func:`moe_dispatch.dropless_moe_ffn`
    (same grouped GEMMs over the same expert-sorted rows); the data
    movement differs: combine weights fold into the pre-down-GEMM
    elementwise chain, the combine is a k-way gather, and both gathers'
    VJPs are gathers. On TPU (``FLAGS_moe_fused_kernel``) the dispatch
    gather additionally folds into the Pallas grouped-GEMM lhs load via
    the per-group tile-padded layout. Expert weights may be int8 dicts
    (:func:`quant_matmul.quantize_grouped`) — scales fold into the same
    chains, gradients never touch them."""
    T, h = x.shape
    k = idx.shape[1]
    A = T * k
    dt = x.dtype
    qg, _ = _unpack(e_gate)
    E = qg.shape[0]
    f = qg.shape[-1]

    order, tok, flat_e, gs = _routing_meta(x, weights, idx, routing)
    if gs is None:
        gs = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)
    esorted = flat_e[order]
    inv = _inverse_permutation(order)
    inv2d = inv.reshape(T, k)
    ws = weights.reshape(A)[order].astype(jnp.float32)

    Wcat, s_gu = _gate_up(e_gate, e_up, dt)
    Wd, s_down = _unpack(e_down)
    if s_down is None:
        Wd = Wd.astype(dt)

    use_kernel = (jax.default_backend() == "tpu"
                  and get_flag("moe_fused_kernel")
                  and h % 128 == 0
                  and _kernel_tn(2 * f, h, Wcat.dtype.itemsize,
                                 x.dtype.itemsize) is not None
                  and A >= _KTM)
    if use_kernel:
        _M_FUSED.labels(path="pallas").inc()
        y = _fused_padded(x, ws, tok, esorted, gs, inv2d, Wcat, s_gu,
                          Wd, s_down, E, f, dt)
    else:
        _M_FUSED.labels(path="xla").inc()
        xs = _gather_rows(x, tok, inv2d)
        gu = _grouped(xs, Wcat, gs, full_rows=True)
        zw = _elementwise_core(gu, s_gu, ws, s_down, esorted, f, dt)
        ys = _grouped(zw, Wd, gs, full_rows=True)
        y = _combine_rows(ys, inv2d, tok)
    # routed-output health probe (trace-time gated, zero ops off): with
    # int8 experts this is where a blown scale or a saturating expert
    # first becomes visible.
    # Lands in forward/serving programs and remat'd training bodies;
    # un-checkpointed grad drops in-scan probes (the models' ladder
    # covers training) — see numerics.record_stats.
    _numerics.record_stats("moe.routed_out", y)
    return y.astype(dt)


def _pad_layout(gs, tok, ws, esorted, inv2d, E: int, tm: int = _KTM):
    """Per-group tile-padded row layout for the gather-GMM kernel: each
    expert's segment is rounded up to a multiple of ``tm`` so every m
    tile lies inside ONE group. Padding rows point at token 0 with
    combine weight 0 — finite garbage that is never gathered forward,
    and every backward product through them carries the zero weight.
    Returns (tok_pad, ws_pad, es_pad, inv_pad2d, gs_pad); the padded row
    count is the static bound ``roundup(A + E*(tm-1), tm)``."""
    T, k = inv2d.shape
    A = T * k
    A_pad = -(-(A + E * (tm - 1)) // tm) * tm       # static upper bound

    tiles_per_g = -(-gs // tm)
    gs_pad = (tiles_per_g * tm).astype(jnp.int32)
    pad_off = jnp.cumsum(gs_pad) - gs_pad
    g_start = jnp.cumsum(gs) - gs
    p = jnp.arange(A, dtype=jnp.int32)
    pos_pad = (jnp.take(pad_off, esorted) + p
               - jnp.take(g_start, esorted)).astype(jnp.int32)

    tok_pad = jnp.zeros((A_pad,), jnp.int32).at[pos_pad].set(tok)
    ws_pad = jnp.zeros((A_pad,), jnp.float32).at[pos_pad].set(ws)
    es_pad = jnp.zeros((A_pad,), jnp.int32).at[pos_pad].set(esorted)
    inv_pad2d = jnp.take(pos_pad, inv2d.reshape(-1)).reshape(T, k)
    return tok_pad, ws_pad, es_pad, inv_pad2d, gs_pad


def _fused_padded(x, ws, tok, esorted, gs, inv2d, Wcat, s_gu, Wd, s_down,
                  E, f, dt):
    """The Pallas-kernel pipeline over the per-group tile-padded layout."""
    tok_pad, ws_pad, es_pad, inv_pad2d, gs_pad = _pad_layout(
        gs, tok, ws, esorted, inv2d, E)
    gu = _gather_gmm_op(x, tok_pad, inv_pad2d, Wcat, gs_pad, False)
    zw = _elementwise_core(gu, s_gu, ws_pad, s_down, es_pad, f, dt)
    ys = _grouped(zw, Wd, gs_pad, full_rows=False)
    return _combine_rows(ys, inv_pad2d, tok_pad)
