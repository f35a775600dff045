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
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
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
    round 4's chip runs); _pick_block shrinks them for shorter sequences."""
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

# the fast memory a Mosaic kernel may take on a v5e unless it raises the limit
_FLASH_VMEM = 16 << 20
# the widest key tile the rule answers, without a band and under one
_FLASH_KV_MAX, _FLASH_KV_MAX_BANDED = 1024, 512
# scores in a causal call's square tile (all the group's rows by its keys)
_FLASH_CAUSAL_TILE = 1 << 20
# the widest key tile of a latent history (``latent_history_tiles``)
_LATENT_KV_MAX = 512


def _flash_footprint(groups: int, bq: int, bkv: int, Dk: int, Dv: int,
                     item: int, cut: bool = False) -> int:
    """Bytes of the chip's fast memory a ``flash_partial`` step holds at a
    tile of ``groups * bq`` rows by ``bkv`` keys, reckoned against what
    Mosaic reports (``Scoped allocation with size ...`` in a
    described-topology compile; 14 tiles of the published shapes read in
    PR 43: the reckoning from 0.03 MiB under to a few MiB over, so a tile
    it admits at the limit's edge is held by the compiles in
    ``tests/test_aot_chip_compile.py``): the operand and output
    blocks twice each (the pipeline fetches the next while this one is
    worked on; a [rows, 1] block takes a lane tile a row), the float32
    accumulator and the two statistics, the tile's scores in float32, two
    temporaries of a statistic's size, and under a diagonal or a band
    (``cut``) the edge tiles' row and column indices and their mask, a
    POSITION's."""
    rows = groups * bq
    lanes = lambda d: -(-d // STATS) * STATS
    blocks = 2 * (item * (rows + bkv) * (lanes(Dk) + lanes(Dv))
                  + 4 * rows * STATS)
    scratch = 4 * rows * (lanes(Dv) + 2 * STATS)
    return (blocks + scratch + 4 * rows * bkv + 2 * 4 * rows * STATS
            + (4 * bq * bkv if cut else 0))


def _tile_sides(S: int, T: int, widest: int):
    """The sides a tile may take: ``block_q`` a power of two that divides
    S, tallest first; ``block_kv`` a multiple of 128 up to ``widest`` that
    divides T and lines up with S (divides it or is a multiple of it).
    Sides that no 128 divides take ``_pick_block``'s answer."""
    qs = [b for b in (1024, 512, 256, 128) if S % b == 0] \
        or [_pick_block(S, 512)]
    ks = [t for t in range(128, min(T, widest) + 1, 128)
          if T % t == 0 and (S % t == 0 or t % S == 0)] \
        or [_pick_block(T, 512)]
    return qs, ks


def flash_tiles(groups: int, S: int, T: int, Dk: int, Dv: int,
                item: int = 2, causal: bool = False, banded: bool = False
                ) -> Tuple[int, int]:
    """``flash_partial``'s tile ``(block_q, block_kv)`` from what the call
    can see of its operands, never from a model's name: ``block_q``
    positions of all ``groups`` heads (a power of two from 1024 down to
    128 that divides S) by ``block_kv`` keys (a multiple of 128 that
    divides T: the MXU asks for no power of two, and a divisor leaves no
    side padded), such that the step fits the kernel's fast memory
    (``_flash_footprint``). Read on the chip, the kernel alone at the
    published shapes (``PERF.md`` section 6, PR 43):

    - Keys every row may see but for a length and a band (a history):
      the TALLEST ``block_q`` that fits beside a key tile of 512, then
      the widest key tile that still fits beside it. Taller first: at
      equal scores a step the taller tile won every shape read (Trinity's
      8k history 1.75 ms at (256, 1024), 1.85 at (256, 512), 1.97 at (128,
      1024)): a key tile is fetched once a ``block_q``. The key tile is
      at most 1,024 wide, 512 under a band (a band cuts two edges of
      every query tile's keys, and what an edge tile wastes grows with
      its width: 4,608 banded keys 0.83 ms at 512, 0.98 at 768), and lines
      up with S (divides it or is a multiple of it): a history is whole
      pieces, so such a tile's edge falls on its length and no tile is
      masked (Mellum2's 8k: 1.17 ms at 512, 1.25 at 768). Wider halves
      the tiles past the length, which a table of 34,816 keys has 52 of
      68 of at 8k of history.
    - A causal call over its own keys (a chunk): a SQUARE tile, the
      largest of at most ``_FLASH_CAUSAL_TILE`` scores. The diagonal's
      tiles are half wasted whatever their size, ``1 + 1 / n`` of the
      triangle's work at n tiles a side, against a step's fixed cost n (n
      + 1) / 2 times (a group of 1 at 256 keys wide takes 1024: 0.76 ms
      against 0.93 at 512; groups of 4 to 8 read the same at every tile
      tried).

    Sides that no 128 divides (tests, short buckets) take ``_pick_block``'s
    answer, as before."""
    fits = lambda bq, bkv: _flash_footprint(
        groups, bq, bkv, Dk, Dv, item, causal or banded) <= _FLASH_VMEM
    qs, ks = _tile_sides(
        S, T, _FLASH_KV_MAX_BANDED if banded else _FLASH_KV_MAX)
    if causal and S == T:
        square = [b for b in qs if fits(b, b)
                  and groups * b * b <= _FLASH_CAUSAL_TILE]
        if square:
            return square[0], square[0]
    base = max(t for t in ks if t <= 512)
    bq = next((b for b in qs if fits(b, base)), qs[-1])
    return bq, max([t for t in ks if t >= base and fits(bq, t)] or [base])


def _tile_kind(q0, c0, bq: int, bkv: int, kv_len, lo, causal: bool):
    """What a tile of ``bq`` positions from ``q0`` by ``bkv`` keys from
    column ``c0`` is, from scalars alone, before the tile is touched:
    ``run``: some (row, column) of it is inside every mask; ``whole``:
    every one is (an interior tile). ``lo`` None: no band. The kernel
    calls it on traced scalars, ``flash_tile_counts`` on numpy arrays."""
    run = c0 < kv_len
    whole = c0 + bkv <= kv_len
    if causal:
        # a tile wholly above the diagonal contributes nothing; one whose
        # last column is the first row's own lies wholly under it
        run = run & (c0 <= q0 + bq - 1)
        whole = whole & (c0 + bkv - 1 <= q0)
    if lo is not None:
        # row r sees no key before column r + lo: a tile whose last
        # column lies before the first row's bound is outside the band,
        # one whose first column is the last row's bound wholly inside
        run = run & (c0 + bkv - 1 >= q0 + lo)
        whole = whole & (c0 >= q0 + bq - 1 + lo)
    return run, whole


def flash_tile_counts(S: int, T: int, kv_len: int, band_lo=None,
                      causal: bool = False, *, bq: int, bkv: int
                      ) -> Tuple[int, int, int]:
    """``(interior, edge, skipped)``: the grid steps of one KV head's
    ``flash_partial`` call by what the kernel does in them (``_tile_kind``,
    its own rule): the unmasked branch, the masked one, nothing. A step's
    tile holds every query head of the group, so the counts are a KV
    head's whatever the group's size."""
    q0 = np.arange(0, S, bq, dtype=np.int64)[:, None]
    c0 = np.arange(0, T, bkv, dtype=np.int64)[None, :]
    run, whole = _tile_kind(q0, c0, bq, bkv, int(kv_len),
                            None if band_lo is None else int(band_lo),
                            causal)
    run = np.broadcast_to(run, (q0.size, c0.size))
    interior = int((run & whole).sum())
    edge = int(run.sum()) - interior
    return interior, edge, run.size - interior - edge


def flash_call_tiles(groups: int, S: int, T: int, Dk: int, Dv: int,
                     kv_len=None, band_lo=None, causal: bool = False,
                     item: int = 2) -> Tuple[int, int, int]:
    """``flash_tile_counts`` of a call's one KV head at the tile
    ``flash_partial`` itself chooses for those operands."""
    bq, bkv = flash_tiles(groups, S, T, Dk, Dv, item, causal,
                          band_lo is not None)
    return flash_tile_counts(S, T, T if kv_len is None else kv_len, band_lo,
                             causal, bq=bq, bkv=bkv)


def _lanes(x, n: int):
    """x [rows, STATS], a row's value in every lane, at n lanes."""
    if n % STATS == 0:
        return x if n == STATS else pltpu.repeat(x, n // STATS, 1)
    return x[:, :n] if n < STATS else jnp.broadcast_to(
        x[:, :1], (x.shape[0], n))


def _init_softmax(acc_ref, m_ref, l_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _softmax_step(s, v, acc_ref, m_ref, l_ref, masked=None):
    """One key tile of the online softmax: scores s [rows, keys] f32
    (an edge tile's already ``masked(s, NEG_INF)``), values v [keys, Dv]."""
    # the statistics stay as they lie in their scratch, a row's value
    # in every one of its 128 lanes: a [rows, 1] column is spread over
    # the lanes twice a tile (the tile's max, the tile's sum) and
    # nowhere else
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - _lanes(m_new, s.shape[-1]))
    if masked is not None:
        # a row with no key yet keeps m = NEG_INF: exp(s - m) would be
        # 1. (An interior tile gives every one of its rows block_kv
        # keys, so m_new is finite there and a row that had seen none
        # takes alpha = 0: nothing to select.)
        p = masked(p, 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * _lanes(alpha, pv.shape[-1]) + pv
    m_ref[...] = m_new


def _write_partial(o_ref, lse_ref, acc_ref, m_ref, l_ref):
    l = l_ref[:, :1]
    o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(
        o_ref.dtype).reshape(o_ref.shape[1:])
    lse_ref[0] = jnp.where(l > 0, m_ref[:, :1] + jnp.log(
        jnp.maximum(l, 1e-30)), NEG_INF).reshape(lse_ref.shape[1:])


def _partial_kernel(len_ref, *rest, scale, causal, block_q, block_kv,
                    groups, banded=False):
    if banded:
        lo_ref, *rest = rest
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    g, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nj = pl.num_programs(2)
    kv_len = len_ref[g]
    # the tile: ``block_q`` positions of EVERY query head of KV head g
    # (rows h * block_q + r: head h of the group, position q0 + r) against
    # ``block_kv`` keys from column c0
    rows = groups * block_q
    q0, c0 = i * block_q, j * block_kv

    pl.when(j == 0)(functools.partial(_init_softmax, acc_ref, m_ref, l_ref))

    lo = lo_ref[g] if banded else None
    run, whole = _tile_kind(q0, c0, block_q, block_kv, kv_len, lo, causal)

    def tile(cut: bool):
        q = q_ref[0].reshape(rows, q_ref.shape[-1])
        k, v = k_ref[0], v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        masked = None
        if cut:
            # one mask a POSITION, shared by the group's heads
            shape = (block_q, block_kv)
            col = jax.lax.broadcasted_iota(jnp.int32, shape, 1) + c0
            keep = col < kv_len
            if causal or banded:
                row = jax.lax.broadcasted_iota(jnp.int32, shape, 0) + q0
            if causal:
                keep &= row >= col
            if banded:
                keep &= col >= row + lo
            masked = lambda x, fill: jnp.where(
                keep[None], x.reshape(groups, block_q, block_kv),
                jnp.float32(fill)).reshape(rows, block_kv)
            s = masked(s, NEG_INF)
        _softmax_step(s, v, acc_ref, m_ref, l_ref, masked)

    pl.when(run & whole)(functools.partial(tile, False))
    pl.when(run & jnp.logical_not(whole))(functools.partial(tile, True))

    pl.when(j == nj - 1)(functools.partial(
        _write_partial, o_ref, lse_ref, acc_ref, m_ref, l_ref))


def flash_partial(q, k, v, *, scale: float, causal: bool = False,
                  kv_len=None, block_q=None, block_kv=None,
                  name: str = "flash_partial", band_lo=None):
    """Blockwise softmax attention in partial form, forward only.

    q: [G, S, Dk]; k: [Gk, T, Dk] with G a multiple of Gk (group g reads
    keys g // (G // Gk)); v: [Gk, T, Dv] with any Dv.
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

    The tile. A grid step takes ``block_q`` query POSITIONS of all the
    ``G // Gk`` heads that read one KV head (``groups * block_q`` rows of
    one matmul) against ``block_kv`` keys: a K and a V tile are named, and
    a step's fixed cost and every tile past the length paid, once a group
    and not once a query head. From scalars alone a step knows its tile as
    *skipped* (no (row, column) inside the masks), *interior* (every one
    inside the length, under the diagonal and inside the band: no iota, no
    compare, no select is computed) or *edge* (the rest: one mask a
    position, shared by the group's heads): ``flash_tile_counts``. The
    sizes follow from the operands' shapes (``flash_tiles``); ``block_q``
    / ``block_kv`` override them, for tests.

    Returns ``(o [G, S, Dv] in q's dtype, lse [G, S] f32)``: normalised
    output and log-sum-exp, -1e30 where a row saw no key, so that
    ``combine_partials`` merges calls over disjoint key sets. Nothing of
    size S x T is materialised. Dk and Dv are multiples of 128 on a TPU
    (Mosaic's lanes): pad with zero columns, which add 0.0. (Keys and
    values that are ONE latent row a token, shared by every head, go
    through ``latent_history_partial``.)"""
    G, S, Dk = q.shape
    Gk, T, _ = k.shape
    assert G % Gk == 0, (G, Gk)
    groups = G // Gk
    Dv = v.shape[-1]
    bq, bkv = flash_tiles(groups, S, T, Dk, Dv, q.dtype.itemsize, causal,
                          band_lo is not None)
    if block_q is not None:
        bq = _pick_block(S, block_q)
    if block_kv is not None:
        bkv = _pick_block(T, block_kv)
    if kv_len is None:
        kv_len = jnp.full((Gk,), T, jnp.int32)

    banded = band_lo is not None
    if banded:
        def kv_map(g, i, j, lens, lo):
            # before the band and past the length the nearest tile inside
            # is named again: no new copy
            last = jnp.maximum((lens[g] + bkv - 1) // bkv - 1, 0)
            first = jnp.clip((i * bq + lo[g]) // bkv, 0, last)
            return (g, jnp.clip(j, first, last), 0)

        q_map = lambda g, i, j, lens, lo: (g, 0, i, 0)
        scalars = [kv_len.astype(jnp.int32), band_lo.astype(jnp.int32)]
    else:
        def kv_map(g, i, j, lens):
            # past the length the same tile is named again: no new copy
            last = jnp.maximum((lens[g] + bkv - 1) // bkv - 1, 0)
            return (g, jnp.minimum(j, last), 0)

        q_map = lambda g, i, j, lens: (g, 0, i, 0)
        scalars = [kv_len.astype(jnp.int32)]

    kernel = functools.partial(_partial_kernel, scale=scale, causal=causal,
                               block_q=bq, block_kv=bkv, groups=groups)
    if banded:
        kernel = functools.partial(kernel, banded=True)
    rows = groups * bq
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=(Gk, S // bq, T // bkv),
            # the heads of a group are neighbours in q: a view, no copy
            in_specs=[pl.BlockSpec((1, groups, bq, Dk), q_map),
                      pl.BlockSpec((1, bkv, Dk), kv_map),
                      pl.BlockSpec((1, bkv, Dv), kv_map)],
            out_specs=[pl.BlockSpec((1, groups, bq, Dv), q_map),
                       pl.BlockSpec((1, groups, bq, 1), q_map)],
            scratch_shapes=[pltpu.VMEM((rows, Dv), jnp.float32),
                            pltpu.VMEM((rows, STATS), jnp.float32),
                            pltpu.VMEM((rows, STATS), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((Gk, groups, S, Dv), q.dtype),
                   jax.ShapeDtypeStruct((Gk, groups, S, 1), jnp.float32)],
        interpret=_interpret(), name=name,
    )(*scalars, q.reshape(Gk, groups, S, Dk), k, v)
    return out.reshape(G, S, Dv), lse.reshape(G, S)


# ---------------------------------------------------------------------------
# the same partial over a LATENT history: one row a token, shared by every
# head, expanded to a head's keys and values a key tile at a time
# ---------------------------------------------------------------------------

def latent_history_tiles(S: int, T: int) -> Tuple[int, int]:
    """``latent_history_partial``'s tile ``(block_q, block_kv)``: a head's
    WHOLE piece where S allows (a key tile is expanded once a ``block_q``,
    2 x block_kv x rank x (dn + dv) FLOPs that ``block_q`` rows share), by
    the widest key tile up to ``_LATENT_KV_MAX`` (``_tile_sides``)."""
    qs, ks = _tile_sides(S, T, _LATENT_KV_MAX)
    return qs[0], max(ks)


def _latent_history_kernel(len_ref, q_ref, c_ref, uk_ref, uv_ref, o_ref,
                           lse_ref, acc_ref, m_ref, l_ref, *, scale,
                           block_q, block_kv, rope):
    j, nj = pl.program_id(2), pl.num_programs(2)
    kv_len, c0 = len_ref[0], j * block_kv
    dn, r = uk_ref.shape[1:]

    pl.when(j == 0)(functools.partial(_init_softmax, acc_ref, m_ref, l_ref))

    # every row of a piece sees every key of its history: a tile is cut by
    # the length alone
    run, whole = _tile_kind(0, c0, block_q, block_kv, kv_len, None, False)

    def tile(cut: bool):
        q, rows = q_ref[0], c_ref[0]
        lat, dt = rows[:, :r], rows.dtype
        nt = lambda a, b: jax.lax.dot_general(
            a, b, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        # this head's keys and values of the tile, from the latent rows:
        # f32 accumulation, rounded as the chunk's own are
        k_nope = nt(lat, uk_ref[0]).astype(dt)
        v = jnp.dot(lat, uv_ref[0],
                    preferred_element_type=jnp.float32).astype(dt)
        s = (nt(q[:, :dn], k_nope)
             + nt(q[:, dn:dn + rope], rows[:, r:r + rope])) * scale
        masked = None
        if cut:
            keep = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1) + c0 < kv_len
            masked = lambda x, fill: jnp.where(keep, x, jnp.float32(fill))
            s = masked(s, NEG_INF)
        _softmax_step(s, v, acc_ref, m_ref, l_ref, masked)

    pl.when(run & whole)(functools.partial(tile, False))
    pl.when(run & jnp.logical_not(whole))(functools.partial(tile, True))

    pl.when(j == nj - 1)(functools.partial(
        _write_partial, o_ref, lse_ref, acc_ref, m_ref, l_ref))


def latent_history_partial(q, hist, w_uk, w_uv, *, scale: float, kv_len,
                           block_q=None, block_kv=None,
                           name: str = "latent_history_partial"):
    """``flash_partial`` over a history kept as LATENT rows, in the
    expanded form: a head's keys and values of a key tile are made inside
    the kernel, in the chip's fast memory, and never lie in HBM.

    q: [H, S, Dq], a head's ``[nope dn ; rope ; zeros]`` as the chunk's own
    call takes it; hist: [1, T, W], a token's ``[latent r ; roped key ;
    zeros]`` as the pool keeps it; w_uk: [H, dn, r]; w_uv: [H, r, Dv];
    ``kv_len`` [1] int32, a runtime operand: key tiles past it are neither
    fetched again nor computed. A grid step takes ``block_q`` positions of
    ONE head against ``block_kv`` history rows: ``k_nope = lat . w_uk[h]^T``
    and ``v = lat . w_uv[h]`` (f32 accumulation, rounded to the rows'
    dtype), ``s = q_nope . k_nope^T + q_rope . k_rope^T`` and the online
    softmax of ``flash_partial``, its interior / edge / skipped tiles by the
    length (``flash_tile_counts``). Per (query, head, key) that is 2 x (dn
    + rope + Dv) FLOPs and the tile's expansion, 2 x r x (dn + Dv), shared
    by ``block_q`` queries (``latent_history_tiles``: a head's whole
    piece), where the absorbed form (queries carried into the latent's
    coordinates: the decode walk's) pays 2 x (r + rope + r) whatever S:
    the absorbed form wins only under r x (dn + Dv) / (2 r - dn - Dv)
    queries a head (~170 at the published widths), which no piece is.

    The rope columns are taken ``min(Dq - dn, W - r)`` wide: past the rope
    both sides hold zeros, so on a TPU (Dq 256, W 640) both slices are
    whole lane tiles. Returns ``(o [H, S, Dv], lse [H, S] f32)`` as
    ``flash_partial`` does."""
    H, S, Dq = q.shape
    _, T, W = hist.shape
    _, dn, r = w_uk.shape
    Dv = w_uv.shape[-1]
    bq, bkv = latent_history_tiles(S, T)
    if block_q is not None:
        bq = _pick_block(S, block_q)
    if block_kv is not None:
        bkv = _pick_block(T, block_kv)

    def row_map(h, i, j, lens):
        # past the length the same tile is named again: no new copy
        last = jnp.maximum((lens[0] + bkv - 1) // bkv - 1, 0)
        return (0, jnp.minimum(j, last), 0)

    q_map = lambda h, i, j, lens: (h, i, 0)
    w_map = lambda h, i, j, lens: (h, 0, 0)
    kernel = functools.partial(
        _latent_history_kernel, scale=scale, block_q=bq, block_kv=bkv,
        rope=min(Dq - dn, W - r))
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(H, S // bq, T // bkv),
            in_specs=[pl.BlockSpec((1, bq, Dq), q_map),
                      pl.BlockSpec((1, bkv, W), row_map),
                      pl.BlockSpec((1, dn, r), w_map),
                      pl.BlockSpec((1, r, Dv), w_map)],
            out_specs=[pl.BlockSpec((1, bq, Dv), q_map),
                       pl.BlockSpec((1, bq, 1), q_map)],
            scratch_shapes=[pltpu.VMEM((bq, Dv), jnp.float32),
                            pltpu.VMEM((bq, STATS), jnp.float32),
                            pltpu.VMEM((bq, STATS), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((H, S, Dv), q.dtype),
                   jax.ShapeDtypeStruct((H, S, 1), jnp.float32)],
        interpret=_interpret(), name=name,
    )(kv_len.astype(jnp.int32), q, hist, w_uk, w_uv)
    return o, lse[..., 0]


def combine_partials(o1, lse1, o2, lse2):
    """One softmax over the union of two disjoint key sets, from each
    set's normalised output and log-sum-exp (``flash_partial``)."""
    m = jnp.maximum(lse1, lse2)
    w1, w2 = jnp.exp(lse1 - m), jnp.exp(lse2 - m)
    out = (o1.astype(jnp.float32) * w1[..., None]
           + o2.astype(jnp.float32) * w2[..., None]) \
        / jnp.maximum(w1 + w2, 1e-30)[..., None]
    return out.astype(o1.dtype)
