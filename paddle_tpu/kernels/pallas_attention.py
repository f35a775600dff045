"""FlashAttention-2 as a Pallas TPU kernel (forward + backward).

Replaces the reference's vendored FlashAttention-2 CUDA library
(reference: third_party/flashattn backing
paddle/phi/kernels/gpu/flash_attn_kernel.cu, python surface
python/paddle/nn/functional/flash_attention.py:358).

TPU-native design: online-softmax tiles sized for the MXU (128-multiple
blocks), f32 accumulators in VMEM scratch carried across the innermost
(kv) grid dimension, log-sum-exp saved as the residual so the backward
recomputes probabilities tile-by-tile (two kernels: dQ over kv tiles, dK/dV
over q tiles) — never materializing the [S, S] score matrix in HBM.

Layout contract: q, k, v are [batch, seq, heads, head_dim] (the framework's
public flash_attention layout); kernels run on [batch*heads, seq, head_dim].
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
STATS = 128  # lane width used to store per-row softmax stats


def _interpret() -> bool:
    # off-TPU (CPU tests) the kernels run in the Pallas interpreter
    return jax.default_backend() != "tpu"


def _pick_block(seq: int, want: int) -> int:
    b = min(want, seq)
    while seq % b:
        b //= 2
    return max(b, 128) if seq % max(b, 128) == 0 else b


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, causal, block_q, block_kv):
    i, j = pl.program_id(1), pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = True
    if causal:
        # tile fully above the diagonal contributes nothing
        run = (j * block_kv) <= (i * block_q + block_q - 1)

    @pl.when(run)
    def _():
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + i * block_q
            col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * block_kv
            s = jnp.where(row >= col, s, jnp.float32(NEG_INF))

        m_prev = m_ref[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == nj - 1)
    def _():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:, :1] + jnp.log(l)   # [block_q, 1]


def _fwd(q, k, v, causal, block_q, block_kv, scale, groups):
    """q: [B*Hq, S, D]; k/v: [B*Hkv, S, D] with Hq = Hkv*groups. Flattened
    b-major, q row b reads kv row b // groups (exact: (bb*Hq + h)//G =
    bb*Hkv + h//G — the repeat-interleave GQA convention of
    jnp.repeat(axis=2), so no repeated K/V is ever materialized)."""
    BH, S, D = q.shape
    bq = _pick_block(S, block_q)
    bkv = _pick_block(S, block_kv)
    grid = (BH, S // bq, S // bkv)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=bq, block_kv=bkv)
    kv_map = lambda b, i, j: (b // groups, j, 0)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, D), kv_map),
            pl.BlockSpec((1, bkv, D), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
            jax.ShapeDtypeStruct((BH, S, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, STATS), jnp.float32),
            pltpu.VMEM((bq, STATS), jnp.float32),
        ],
        interpret=_interpret(),
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, *, scale, causal, block_q, block_kv):
    i, j = pl.program_id(1), pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = True
    if causal:
        run = (j * block_kv) <= (i * block_q + block_q - 1)

    @pl.when(run)
    def _():
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + i * block_q
            col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * block_kv
            s = jnp.where(row >= col, s, jnp.float32(NEG_INF))
        p = jnp.exp(s - lse_ref[0])
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0]) * scale
        acc_ref[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc,
                *, scale, causal, block_q, block_kv):
    # grid: (B*Hkv, kv tiles, group q-heads, q tiles) — dk/dv accumulate
    # across BOTH the group's query heads (g) and the q tiles (i)
    j, g, i = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    ng, ni = pl.num_programs(2), pl.num_programs(3)

    @pl.when(jnp.logical_and(g == 0, i == 0))
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    run = True
    if causal:
        run = (j * block_kv) <= (i * block_q + block_q - 1)

    @pl.when(run)
    def _():
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + i * block_q
            col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * block_kv
            s = jnp.where(row >= col, s, jnp.float32(NEG_INF))
        p = jnp.exp(s - lse_ref[0])                              # [bq, bkv]
        do = do_ref[0]
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0]) * scale                     # [bq, bkv]
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(g == ng - 1, i == ni - 1))
    def _():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd(causal, block_q, block_kv, scale, groups, res, do):
    q, k, v, out, lse = res
    BH, S, D = q.shape
    BHkv = k.shape[0]
    bq = _pick_block(S, block_q)
    bkv = _pick_block(S, block_kv)
    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1, keepdims=True)                      # [BH, S, 1]

    kv_map = lambda b, i, j: (b // groups, j, 0)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_kv=bkv),
        grid=(BH, S // bq, S // bkv),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, D), kv_map),
            pl.BlockSpec((1, bkv, D), kv_map),
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)

    # dk/dv: grid dim0 walks KV rows; q-side refs select the group's q head
    # g via row b*groups + g (inverse of the forward's b // groups map)
    q_map = lambda b, j, g, i: (b * groups + g, i, 0)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_kv=bkv),
        grid=(BHkv, S // bkv, groups, S // bq),
        in_specs=[
            pl.BlockSpec((1, bq, D), q_map),
            pl.BlockSpec((1, bkv, D), lambda b, j, g, i: (b, j, 0)),
            pl.BlockSpec((1, bkv, D), lambda b, j, g, i: (b, j, 0)),
            pl.BlockSpec((1, bq, D), q_map),
            pl.BlockSpec((1, bq, 1), q_map),
            pl.BlockSpec((1, bq, 1), q_map),
        ],
        out_specs=[
            pl.BlockSpec((1, bkv, D), lambda b, j, g, i: (b, j, 0)),
            pl.BlockSpec((1, bkv, D), lambda b, j, g, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BHkv, S, D), k.dtype),
            jax.ShapeDtypeStruct((BHkv, S, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bkv, D), jnp.float32),
            pltpu.VMEM((bkv, D), jnp.float32),
        ],
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, block_q, block_kv, scale, groups):
    out, _ = _fwd(q, k, v, causal, block_q, block_kv, scale, groups)
    return out


def _flash_fwd(q, k, v, causal, block_q, block_kv, scale, groups):
    out, lse = _fwd(q, k, v, causal, block_q, block_kv, scale, groups)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, block_q, block_kv, scale, groups, res, do):
    return _bwd(causal, block_q, block_kv, scale, groups, res, do)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_fwd(q, k, v, causal: bool = False,
                        block_q: int = 1024, block_kv: int = 1024):
    """q: [batch, seq, heads, head_dim]; k/v may carry FEWER heads (GQA) —
    query head h attends kv head h // (Hq//Hkv) inside the kernel, so the
    repeated K/V (and their expanded dK/dV) never touch HBM.
    Differentiable (custom FA2 backward). Default 1024-blocks measured
    fastest on v5e (2.6B train step: 6.89k vs 6.52k tok/s at 512-blocks,
    bench.py runs); _pick_block shrinks them for shorter sequences."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    assert H % Hkv == 0, (H, Hkv)
    groups = H // Hkv
    scale = 1.0 / math.sqrt(D)

    def to_bh(x):
        h = x.shape[2]
        return jnp.swapaxes(x, 1, 2).reshape(B * h, S, D)

    out = _flash(to_bh(q), to_bh(k), to_bh(v), causal, block_q, block_kv,
                 scale, groups)
    return jnp.swapaxes(out.reshape(B, H, S, D), 1, 2)


# ---------------------------------------------------------------------------
# forward only, partial form: unequal key and value widths, a key length per
# group, and the log-sum-exp returned so that two calls combine
# ---------------------------------------------------------------------------

def _partial_kernel(len_ref, *rest, scale, causal, block_q, block_kv,
                    groups, v_cols, banded=False):
    if banded:
        lo_ref, *rest = rest
    q_ref, k_ref, *rest = rest
    if v_cols is None:
        v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    i, j = pl.program_id(1), pl.program_id(2)
    nj = pl.num_programs(2)
    kv_len = len_ref[pl.program_id(0) // groups]

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = j * block_kv < kv_len
    if causal:
        # tile fully above the diagonal contributes nothing
        run &= (j * block_kv) <= (i * block_q + block_q - 1)
    if banded:
        # row r sees no key before column r + lo: a tile whose last
        # column lies before the first row's bound is outside the band
        lo = lo_ref[pl.program_id(0) // groups]
        run &= (j * block_kv + block_kv - 1) >= (i * block_q + lo)

    @pl.when(run)
    def _():
        q = q_ref[0]
        k = k_ref[0]
        v = k[:, :v_cols] if v_cols is not None else v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * block_kv
        keep = col < kv_len
        if causal:
            row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) \
                + i * block_q
            keep &= row >= col
        if banded:
            row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) \
                + i * block_q
            keep &= col >= row + lo
        s = jnp.where(keep, s, jnp.float32(NEG_INF))
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # a row with no key yet keeps m = NEG_INF: exp(s - m) would be 1
        p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == nj - 1)
    def _():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        lse_ref[0] = jnp.where(l > 0, m_ref[:, :1] + jnp.log(
            jnp.maximum(l, 1e-30)), NEG_INF)


def flash_partial(q, k, v=None, *, scale: float, causal: bool = False,
                  kv_len=None, v_cols=None, block_q: int = 512,
                  block_kv: int = 512, name: str = "flash_partial",
                  band_lo=None):
    """Blockwise softmax attention in partial form, forward only.

    q: [G, S, Dk]; k: [Gk, T, Dk] with G a multiple of Gk (group g reads
    keys g // (G // Gk)); v: [Gk, T, Dv] with any Dv, or None with
    ``v_cols``: the values are then the first ``v_cols`` columns of the
    keys, sliced in VMEM (a latent row is key and value at once).
    ``kv_len`` [Gk] int32 masks keys at or past it, a runtime operand: key
    tiles past it are neither fetched again nor computed. ``causal``
    compares row and column indices as they are (S and T start together).
    ``band_lo`` [Gk] int32 (a window layer's; absent, the kernel is the
    one a full layer compiles) is a LOWER bound on the key a query row may
    see, a runtime operand beside the key length: row r sees column c only
    where ``c >= r + band_lo[g]`` (a window of W tokens over keys that
    start ``off`` positions before the queries: ``off - W + 1``), and key
    tiles wholly before a query tile's band are neither fetched nor
    computed.
    Returns ``(o [G, S, Dv] in q's dtype, lse [G, S] f32)``: normalised
    output and log-sum-exp, -1e30 where a row saw no key, so that
    ``combine_partials`` merges calls over disjoint key sets. Nothing of
    size S x T is materialised. Dk, Dv and v_cols are multiples of 128 on
    a TPU (Mosaic's lanes): pad with zero columns, which add 0.0."""
    G, S, Dk = q.shape
    Gk, T, _ = k.shape
    assert G % Gk == 0 and (v is None) != (v_cols is None), (G, Gk, v_cols)
    groups = G // Gk
    Dv = v_cols if v is None else v.shape[-1]
    bq, bkv = _pick_block(S, block_q), _pick_block(T, block_kv)
    if kv_len is None:
        kv_len = jnp.full((Gk,), T, jnp.int32)

    banded = band_lo is not None
    if banded:
        def kv_map(g, i, j, lens, lo):
            # before the band and past the length the nearest tile inside
            # is named again: no new copy
            last = jnp.maximum((lens[g // groups] + bkv - 1) // bkv - 1, 0)
            first = jnp.clip((i * bq + lo[g // groups]) // bkv, 0, last)
            return (g // groups, jnp.clip(j, first, last), 0)

        q_map = lambda g, i, j, lens, lo: (g, i, 0)
        scalars = [kv_len.astype(jnp.int32), band_lo.astype(jnp.int32)]
    else:
        def kv_map(g, i, j, lens):
            # past the length the same tile is named again: no new copy
            last = jnp.maximum((lens[g // groups] + bkv - 1) // bkv - 1, 0)
            return (g // groups, jnp.minimum(j, last), 0)

        q_map = lambda g, i, j, lens: (g, i, 0)
        scalars = [kv_len.astype(jnp.int32)]

    in_specs = [pl.BlockSpec((1, bq, Dk), q_map),
                pl.BlockSpec((1, bkv, Dk), kv_map)]
    operands = [q, k]
    if v is not None:
        in_specs.append(pl.BlockSpec((1, bkv, Dv), kv_map))
        operands.append(v)
    kernel = functools.partial(_partial_kernel, scale=scale, causal=causal,
                               block_q=bq, block_kv=bkv, groups=groups,
                               v_cols=v_cols)
    if banded:
        kernel = functools.partial(kernel, banded=True)
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=(G, S // bq, T // bkv),
            in_specs=in_specs,
            out_specs=[pl.BlockSpec((1, bq, Dv), q_map),
                       pl.BlockSpec((1, bq, 1), q_map)],
            scratch_shapes=[pltpu.VMEM((bq, Dv), jnp.float32),
                            pltpu.VMEM((bq, STATS), jnp.float32),
                            pltpu.VMEM((bq, STATS), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((G, S, Dv), q.dtype),
                   jax.ShapeDtypeStruct((G, S, 1), jnp.float32)],
        interpret=_interpret(), name=name,
    )(*scalars, *operands)
    return out, lse[..., 0]


def combine_partials(o1, lse1, o2, lse2):
    """One softmax over the union of two disjoint key sets, from each
    set's normalised output and log-sum-exp (``flash_partial``)."""
    m = jnp.maximum(lse1, lse2)
    w1, w2 = jnp.exp(lse1 - m), jnp.exp(lse2 - m)
    out = (o1.astype(jnp.float32) * w1[..., None]
           + o2.astype(jnp.float32) * w2[..., None]) \
        / jnp.maximum(w1 + w2, 1e-30)[..., None]
    return out.astype(o1.dtype)
