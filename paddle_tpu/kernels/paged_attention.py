"""Paged KV-cache attention (block tables) — the serving decode path.

Parity: the reference's blocked decode kernel
(phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu, python surface
incubate/nn/functional/block_multihead_attention) whose cache is paged:
physical blocks of block_size tokens + per-sequence block tables. The
ragged kernel below is the "Ragged Paged Attention" direction (PAPERS.md
lead paper, arXiv 2604.15464) done natively.

TPU-native: the cache is one [num_blocks, block_size, H, D] pool per k/v;
a block_table [B, max_blocks] maps logical sequence positions to pool
blocks. Two decode strategies live here, with different compile/variant
stories:

- XLA gather path (:func:`paged_attention` / the engine's hoisted-dense
  program): each sequence's blocks are gathered into a dense buffer of a
  STATIC width and positions past the true length are softmax-masked.
  Exact, but the static width must come from somewhere — the serving
  engine picks a power-of-two prefix bucket host-side, so attention cost
  scales with ``max(lengths)`` rounded up to the bucket ceiling and the
  compile cache carries one variant per (bucket, sampling-flags) pair
  (bounded at ``log2(max_blocks)+1 × 8``, but a recompile family all the
  same). This is the off-TPU / interpret fallback.
- Ragged Pallas path (:func:`ragged_paged_decode` /
  :func:`ragged_decode_partial`): one program per slot walks the slot's
  block table at its TRUE length — blocks past ``ceil(len/bs)`` are
  never visited (the walk's trip count ends there: no DMA, no FLOPs),
  the tail inside the last block is masked, and the softmax runs online
  across the walk, so nothing is
  ever gathered to a static horizon. Lengths are a runtime operand, not
  a shape: ONE compiled variant serves any batch composition, and the
  per-step KV read scales with the actual tokens resident, not any
  bucket ceiling. int8 pools stream unconverted and dequantize
  in-register via their per-entry scales (the quant_matmul scale-folding
  math).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["PagedKVCache", "paged_cache_init", "paged_append",
           "RAGGED_INT8_KV_TPU_REFUSAL", "ragged_tpu_refusal",
           "paged_attention", "paged_append_token", "paged_append_blocks",
           "paged_decode_attention", "ragged_decode_partial",
           "ragged_paged_decode"]


def _interpret() -> bool:
    # off-TPU (CPU tests) the kernels run in the Pallas interpreter
    return jax.default_backend() != "tpu"


# What the TPU compiler says to the ragged walk over int8 pools (jax 0.9.0
# / libtpu 0.0.34, tests/test_aot_chip_compile.py): the [bs, Hkv] slice of
# the f32 scale pools [L, NB, bs, Hkv] is narrower than the 128-lane tile
# the pool is laid out in. Repairing it means a new scale-pool layout, so
# the serving engine takes the bucketed path for int8 pools on a TPU.
RAGGED_INT8_KV_TPU_REFUSAL = (
    "Mosaic failed to compile TPU kernel: Slice shape along dimension 3 "
    "must be aligned to tiling (128), but is 8")


def ragged_tpu_refusal(head_dim: int, kv_int8: bool):
    """The TPU compiler's message for a ragged walk it refuses, ``None``
    for one it compiles — the engine's selection by shape. Besides int8
    pools, the block DMA slices a pool whose minor dim is the head dim:
    anything but a multiple of the 128-lane tile is refused (found on the
    chip at head dims 8 and 64)."""
    if kv_int8:
        return RAGGED_INT8_KV_TPU_REFUSAL
    if head_dim % 128:
        return ("Mosaic failed to compile TPU kernel: Slice shape along "
                "dimension 4 must be aligned to tiling (128), but is "
                f"{head_dim}")
    return None


class PagedKVCache(NamedTuple):
    """Pool layout is TOKEN-MAJOR — [num_blocks, block_size, H, D]. Mosaic
    tiles only the trailing two dims of a memref, so keeping (H, D) there
    (both tile-aligned constants) leaves the token dim freely sliceable —
    which is what lets the Pallas append kernel DMA a single token row to
    an arbitrary (block, offset) without violating tiling. (A head-major
    layout would put block_size in the tiled pair and forbid exactly that
    slice.)"""
    k_pool: jax.Array          # [num_blocks, block_size, H, D]
    v_pool: jax.Array          # [num_blocks, block_size, H, D]
    block_table: jax.Array     # [B, max_blocks] int32 (pool indices)
    lengths: jax.Array         # [B] int32 current token counts


def paged_cache_init(batch: int, num_blocks: int, block_size: int,
                     num_heads: int, head_dim: int, max_blocks: int,
                     dtype=jnp.bfloat16) -> PagedKVCache:
    """Pre-partitioned allocation: sequence b owns blocks
    b*max_blocks..(b+1)*max_blocks-1 by default (callers doing real paging
    can overwrite block_table with any pool mapping)."""
    assert num_blocks >= batch * max_blocks
    table = (jnp.arange(batch * max_blocks, dtype=jnp.int32)
             .reshape(batch, max_blocks))
    return PagedKVCache(
        jnp.zeros((num_blocks, block_size, num_heads, head_dim), dtype),
        jnp.zeros((num_blocks, block_size, num_heads, head_dim), dtype),
        table, jnp.zeros((batch,), jnp.int32))


def paged_append(cache: PagedKVCache, k_new, v_new) -> PagedKVCache:
    """Append ONE token per sequence (XLA reference path — the Pallas
    fast path is :func:`paged_append_token`). k_new/v_new: [B, H, D]."""
    bs = cache.k_pool.shape[1]
    pos = cache.lengths                               # [B]
    blk_logical = pos // bs
    offset = pos % bs
    blk_physical = jnp.take_along_axis(
        cache.block_table, blk_logical[:, None], axis=1)[:, 0]
    k_pool = cache.k_pool.at[blk_physical, offset].set(
        k_new.astype(cache.k_pool.dtype))
    v_pool = cache.v_pool.at[blk_physical, offset].set(
        v_new.astype(cache.v_pool.dtype))
    return PagedKVCache(k_pool, v_pool, cache.block_table, pos + 1)


# ---------------------------------------------------------------------------
# Pallas TPU kernels — the serving hot path.
#
# XLA lowers the pool updates/reads below to generic scatter/gather because
# every slot indexes a DIFFERENT physical block (vector indices): measured
# ~0.5 ms PER LAYER each on a v5e — 2x the cost of the whole dense decode
# step at 510M. These kernels replace them with block-table-driven DMAs:
# appends are one grid step per row/block, and the decode attention streams
# exactly the blocks each slot's true length covers (the reference's paged
# serving kernel, block_multi_head_attention_kernel.cu, done the TPU way —
# also the "Ragged Paged Attention" direction in PAPERS.md).
# ---------------------------------------------------------------------------


def _as5d(pool):
    """View a [NB, BS, H, D] pool as [1, NB, BS, H, D] (bitcast — XLA
    aliases the reshape, so in-place semantics survive the wrapper)."""
    return pool if pool.ndim == 5 else pool[None]


def _append_token_kernel(layer_ref, blk_ref, off_ref, k_new_ref, v_new_ref,
                         k_in_ref, v_in_ref, k_out_ref, v_out_ref, sem):
    """Grid (N,): store slot n's new K/V rows at (layer, blk[n], off[n]).
    Integer indexing squeezes the layer/block/token dims on the
    destination and the slot dim on the source, so the DMA moves one
    tile-aligned [Hkv, D] row — only untiled dims are ever sliced."""
    n = pl.program_id(0)
    lyr = layer_ref[0]
    blk, off = blk_ref[n], off_ref[n]
    cp_k = pltpu.make_async_copy(
        k_new_ref.at[n], k_out_ref.at[lyr, blk, off], sem)
    cp_k.start()
    cp_k.wait()
    cp_v = pltpu.make_async_copy(
        v_new_ref.at[n], v_out_ref.at[lyr, blk, off], sem)
    cp_v.start()
    cp_v.wait()


def paged_append_token(k_pool, v_pool, k_new, v_new, blk_phys, offset,
                       layer=0):
    """Append ONE token per slot in place: k_pool[layer, blk_phys[n],
    offset[n]] = k_new[n]. k_pool/v_pool: [L, NB, BS, Hkv, D] or
    [NB, BS, Hkv, D] (aliased — the returned pools reuse the input
    buffers; a 4D pool comes back 4D); k_new/v_new: [N, Hkv, D];
    blk_phys/offset: [N] int32; ``layer`` selects the pool's layer plane
    (traced — the serving engine passes its static layer loop index).
    Slots meant to be inactive should point at the trash block."""
    was4d = k_pool.ndim == 4
    kp, vp = _as5d(k_pool), _as5d(v_pool)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(k_new.shape[0],),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),   # pools stay in HBM
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
    )
    ko, vo = pl.pallas_call(
        _append_token_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(kp.shape, kp.dtype),
                   jax.ShapeDtypeStruct(vp.shape, vp.dtype)],
        input_output_aliases={5: 0, 6: 1},
        interpret=_interpret(),
    )(jnp.asarray(layer, jnp.int32)[None], blk_phys, offset,
      k_new.astype(kp.dtype), v_new.astype(vp.dtype), kp, vp)
    return (ko[0], vo[0]) if was4d else (ko, vo)


def _append_blocks_kernel(layer_ref, blk_ids_ref, k_blk_ref, v_blk_ref,
                          k_in_ref, v_in_ref, k_out_ref, v_out_ref, sem):
    """Grid (nblk,): store prefill block b at pool block blk_ids[b]
    (HBM-to-HBM DMA of one whole [BS, Hkv, D] block each)."""
    b = pl.program_id(0)
    lyr = layer_ref[0]
    dst = blk_ids_ref[b]
    cp_k = pltpu.make_async_copy(
        k_blk_ref.at[b], k_out_ref.at[lyr, dst], sem)
    cp_k.start()
    cp_k.wait()
    cp_v = pltpu.make_async_copy(
        v_blk_ref.at[b], v_out_ref.at[lyr, dst], sem)
    cp_v.start()
    cp_v.wait()


def paged_append_blocks(k_pool, v_pool, k_blocks, v_blocks, blk_ids,
                        layer=0):
    """Scatter whole prefill blocks into the pool in place (the prefill-side
    analogue of paged_append_token). k_blocks/v_blocks: [nblk, BS, Hkv, D];
    blk_ids: [nblk] int32 destinations (duplicates allowed only for the
    trash block — pad blocks may all point at 0); pools/layer as in
    :func:`paged_append_token`."""
    was4d = k_pool.ndim == 4
    kp, vp = _as5d(k_pool), _as5d(v_pool)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(blk_ids.shape[0],),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
    )
    ko, vo = pl.pallas_call(
        _append_blocks_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(kp.shape, kp.dtype),
                   jax.ShapeDtypeStruct(vp.shape, vp.dtype)],
        input_output_aliases={4: 0, 5: 1},
        interpret=_interpret(),
    )(jnp.asarray(layer, jnp.int32)[None], blk_ids,
      k_blocks.astype(kp.dtype), v_blocks.astype(vp.dtype), kp, vp)
    return (ko[0], vo[0]) if was4d else (ko, vo)


def _decode_attn_kernel(layer_ref, table_ref, lens_ref, q_ref, k_pool_ref,
                        v_pool_ref, o_ref, kbuf, vbuf, sems, *, block_size,
                        n_kv, max_blocks):
    """Grid (N,): ONE program per slot. All the slot's valid pool blocks
    are DMA'd into VMEM in parallel (start everything, then wait), then
    attention runs single-shot per kv head over the contiguous buffer.
    Few large programs + bulk DMA keep the kernel bandwidth-bound instead
    of program-overhead-bound (a (slot, head, block) grid measured 2 us of
    overhead per tiny program — 20x the DMA time it hid)."""
    n = pl.program_id(0)
    lyr = layer_ref[0]
    ln = lens_ref[n]
    copies = []
    for b in range(max_blocks):
        valid = b * block_size < ln
        blk = table_ref[n, b]

        @pl.when(valid)
        def _(b=b, blk=blk):
            cp_k = pltpu.make_async_copy(
                k_pool_ref.at[lyr, blk],
                kbuf.at[pl.ds(b * block_size, block_size)],
                sems.at[0, b])
            cp_k.start()
            cp_v = pltpu.make_async_copy(
                v_pool_ref.at[lyr, blk],
                vbuf.at[pl.ds(b * block_size, block_size)],
                sems.at[1, b])
            cp_v.start()

        copies.append((valid, blk, b))
    for valid, blk, b in copies:
        @pl.when(valid)
        def _(b=b, blk=blk):
            pltpu.make_async_copy(
                k_pool_ref.at[lyr, blk],
                kbuf.at[pl.ds(b * block_size, block_size)],
                sems.at[0, b]).wait()
            pltpu.make_async_copy(
                v_pool_ref.at[lyr, blk],
                vbuf.at[pl.ds(b * block_size, block_size)],
                sems.at[1, b]).wait()

        # never-copied V blocks hold scratch garbage; the ~0 softmax
        # weights of masked columns still NaN-poison the p@V contraction
        # unless the values are finite, so zero them (VPU-only, no HBM
        # traffic). K needs no fill: masked score columns are rewritten
        # by the -1e30 where() regardless of what the dot produced.
        @pl.when(jnp.logical_not(valid))
        def _(b=b):
            vbuf[b * block_size:(b + 1) * block_size] = jnp.zeros(
                (block_size,) + vbuf.shape[1:], vbuf.dtype)

    S = max_blocks * block_size
    col = jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)
    for h in range(n_kv):                      # static unroll over kv heads
        q = q_ref[0, h]                        # [G, D]
        k = kbuf[:, h]                         # [S, D] (relayout from VMEM)
        v = vbuf[:, h]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)     # [G, S]
        s = s / math.sqrt(q.shape[-1])
        s = jnp.where(col < ln, s, jnp.float32(-1e30))
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)     # [G, D]
        o_ref[0, h] = (o / l).astype(o_ref.dtype)


def paged_decode_attention(q, cache: PagedKVCache, layer=0) -> jax.Array:
    """Pallas decode attention: q [N, Hq, D] -> [N, Hq, D], attending each
    slot's first ``cache.lengths[n]`` pool positions of pool plane
    ``layer`` (pools may be [L, NB, BS, Hkv, D] or 4D). Same contract as
    :func:`paged_attention` (which stays as the XLA reference path and the
    numerics oracle in tests); unlike it, nothing is gathered into a dense
    [N, mb*bs, ...] HBM copy — each slot's blocks stream straight into a
    VMEM buffer, and blocks past the true length are never read."""
    N, Hq, D = q.shape
    kp, vp = _as5d(cache.k_pool), _as5d(cache.v_pool)
    bs, Hkv = kp.shape[2], kp.shape[3]
    mb = cache.block_table.shape[1]
    assert Hq % Hkv == 0, (Hq, Hkv)
    G = Hq // Hkv
    # the two VMEM staging buffers hold the slot's whole context; past
    # ~12 MiB they can't coexist with the rest of the working set in the
    # ~16 MiB VMEM, so long-context pools take the XLA gather path instead
    # of failing with an opaque Mosaic allocation error at serving time
    scratch_bytes = 2 * mb * bs * Hkv * D * kp.dtype.itemsize
    if scratch_bytes > 12 * 1024 * 1024:
        return paged_attention(q, PagedKVCache(
            kp[layer], vp[layer], cache.block_table, cache.lengths))
    qg = q.reshape(N, Hkv, G, D)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(N,),
        in_specs=[
            pl.BlockSpec((1, Hkv, G, D), lambda n, l, t, ln: (n, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # pools stay in HBM
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, Hkv, G, D),
                               lambda n, l, t, ln: (n, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((mb * bs, Hkv, D), kp.dtype),
            pltpu.VMEM((mb * bs, Hkv, D), vp.dtype),
            pltpu.SemaphoreType.DMA((2, mb)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_attn_kernel, block_size=bs, n_kv=Hkv,
                          max_blocks=mb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, Hkv, G, D), q.dtype),
        interpret=_interpret(),
    )(jnp.asarray(layer, jnp.int32)[None], cache.block_table,
      cache.lengths, qg, kp, vp)
    return out.reshape(N, Hq, D)


# ---------------------------------------------------------------------------
# Ragged paged attention — the true-length block walk (arXiv 2604.15464).
#
# Grid: one program per slot; the program's kv-head groups are walked
# in-register inside the block loop rather than as a grid axis, because
# the pool layout keeps (Hkv, D) as the Mosaic-tiled pair — a per-head
# DMA would slice the tiled Hkv dim (illegal), and a per-(slot, head)
# grid re-DMAing whole [bs, Hkv, D] blocks would multiply the KV read
# bytes by Hkv on a bandwidth-bound path. Each real block is DMA'd
# exactly once (double-buffered: block b+1 streams while b computes) and
# every kv head consumes it while it is VMEM-resident.
# ---------------------------------------------------------------------------


def _ragged_decode_kernel(layer_ref, table_ref, lens_ref, q_ref,
                          k_pool_ref, v_pool_ref, *rest, block_size,
                          n_kv, max_blocks, kv_int8):
    """Grid (N,): walk slot n's block table up to ``ceil(lens[n]/bs)``
    REAL blocks with an online softmax. Blocks past the length are never
    visited — the fori_loop trip count ends the walk there (program size
    stays O(1) in the table width) and the ``pl.when`` prefetch guard
    stops the DMA stream at the last real block — so a slot's cost
    scales with its true length whatever the table width. The tail
    inside the last block is masked to -1e30 before the running max, so
    its exp is exactly 0.0 (bucketed-path exactness argument, applied
    per block). int8 pools: the [bs, Hkv, D] payload
    blocks and [bs, Hkv] per-entry scale blocks stream as stored; the
    payload widens in-register (int8 -> q dtype is exact) and the K
    scale multiplies the f32 scores / the V scale folds into the
    probabilities — attn_qk / attn_pv's scale-folding math, inlined.

    Emits the online-softmax PARTIAL state per (slot, kv head, q-in-
    group): unnormalized ``acc`` (f32 [N, Hkv, G, D]), running max ``m``
    and sum ``l`` (f32 [N, Hkv, G]) — the flash-decoding combine
    contract, so a caller can merge in-flight tokens (the engine's
    in-call ring) before normalizing. A slot with length 0 emits
    (acc=0, m=-1e30, l=0), the identity of the combine."""
    if kv_int8:
        (ks_pool_ref, vs_pool_ref, acc_ref, m_ref, l_ref,
         kbuf, vbuf, ksbuf, vsbuf, accs, ms, ls, sems) = rest
    else:
        (acc_ref, m_ref, l_ref, kbuf, vbuf, accs, ms, ls, sems) = rest
    n = pl.program_id(0)
    lyr = layer_ref[0]
    ln = lens_ref[n]
    sm_scale = 1.0 / math.sqrt(q_ref.shape[-1])
    ms[:] = jnp.full(ms.shape, -1e30, jnp.float32)
    ls[:] = jnp.zeros(ls.shape, jnp.float32)
    accs[:] = jnp.zeros(accs.shape, jnp.float32)

    def copies(b, slot):
        blk = table_ref[n, b]
        cps = [pltpu.make_async_copy(k_pool_ref.at[lyr, blk],
                                     kbuf.at[slot], sems.at[0, slot]),
               pltpu.make_async_copy(v_pool_ref.at[lyr, blk],
                                     vbuf.at[slot], sems.at[1, slot])]
        if kv_int8:
            cps += [pltpu.make_async_copy(ks_pool_ref.at[lyr, blk],
                                          ksbuf.at[slot], sems.at[2, slot]),
                    pltpu.make_async_copy(vs_pool_ref.at[lyr, blk],
                                          vsbuf.at[slot], sems.at[3, slot])]
        return cps

    # the walk's trip count IS the skip mechanism: blocks past the
    # length are never visited, so program size stays O(1) in the table
    # width (a python unroll over max_blocks would emit mb x Hkv copies
    # of the DMA+MXU body — a compile cliff at long max_model_len)
    nblk = jnp.minimum((ln + block_size - 1) // block_size, max_blocks)

    @pl.when(nblk > 0)
    def _():
        for cp in copies(0, 0):
            cp.start()

    def walk(b, _):
        sl = jax.lax.rem(b, 2)
        # prefetch block b+1 into the other slot while b computes (the
        # standard two-slot pipeline; pl.when ends the stream exactly at
        # the slot's last real block)
        @pl.when(b + 1 < nblk)
        def _():
            for cp in copies(b + 1, 1 - sl):
                cp.start()

        for cp in copies(b, sl):
            cp.wait()
        col = (jax.lax.broadcasted_iota(jnp.int32, (1, block_size), 1)
               + b * block_size)
        live = col < ln                                      # [1, bs]
        for h in range(n_kv):                    # static kv-head groups
            qh = q_ref[0, h]                                 # [G, D]
            kh = kbuf[sl][:, h]                              # [bs, D]
            if kv_int8:
                kh = kh.astype(qh.dtype)         # int8 widen: exact
            s = jax.lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            if kv_int8:
                s = s * ksbuf[sl][:, h][None, :]
            s = jnp.where(live, s, jnp.float32(-1e30))       # [G, bs]
            m_prev = ms[h]                                   # [G]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[:, None])
            ls[h] = ls[h] * alpha + jnp.sum(p, axis=-1)
            vh = vbuf[sl][:, h]
            if kv_int8:
                # V scale rides the probabilities (it varies along the
                # contracted axis) and int8 V widens in-register
                p = p * vsbuf[sl][:, h][None, :]
                vh = vh.astype(jnp.float32)
            else:
                p = p.astype(vh.dtype)
            pv = jax.lax.dot_general(
                p, vh, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)          # [G, D]
            accs[h] = accs[h] * alpha[:, None] + pv
            ms[h] = m_new
        return 0

    jax.lax.fori_loop(0, nblk, walk, 0)

    acc_ref[0] = accs[:]
    m_ref[0] = ms[:]
    l_ref[0] = ls[:]


def ragged_decode_partial(q, k_pool, v_pool, block_table, lengths, *,
                          layer=0, ks_pool=None, vs_pool=None, mesh=None):
    """Ragged block-walk decode attention over each slot's TRUE length —
    partial (flash-decoding) form. q: [N, Hq, D]; pools:
    [L, NB, BS, Hkv, D] or 4D (bf16/f32, or int8 with per-entry f32
    scale pools ks/vs [L, NB, BS, Hkv] or 3D); block_table: [N, MB]
    int32; lengths: [N] runtime operand — NOT a shape. Returns the
    online-softmax partials ``(acc [N, Hkv, G, D] f32, m [N, Hkv, G]
    f32, l [N, Hkv, G] f32)`` so callers can merge extra keys (the
    serving engine's in-call ring) before normalizing; use
    :func:`ragged_paged_decode` for the normalized one-shot form.

    One compiled variant serves ANY length mix: the table width MB is
    the only shape, and slots read exactly ``ceil(lengths[n]/BS)``
    blocks of it. VMEM use is two double-buffered blocks + the [Hkv, G,
    D] accumulators, independent of context length — no long-context
    staging-buffer cliff like :func:`paged_decode_attention`'s.

    With ``mesh`` (a Mesh carrying a 'tp' axis of size > 1) the call is
    wrapped in a shard_map over 'tp': KV heads shard naturally — every
    shard walks the SAME block tables and lengths (replicated scalars)
    against its Hkv/tp head slice of q and the pools (the engine's
    ``P(None,None,None,"tp",None)`` pool shardings). Per-kv-head online
    softmax is independent, so the sharded partials are bit-identical
    to the unsharded ones. Hkv must divide by the tp size."""
    if mesh is not None:
        tp = dict(mesh.shape).get("tp", 1)
        if tp > 1:
            from jax.sharding import PartitionSpec as P
            Hkv_g = _as5d(k_pool).shape[3]
            assert Hkv_g % tp == 0, (Hkv_g, tp)
            pool_s = P(None, None, None, "tp", None) \
                if k_pool.ndim == 5 else P(None, None, "tp", None)
            scale_s = None
            if ks_pool is not None:
                scale_s = P(None, None, None, "tp") \
                    if ks_pool.ndim == 4 else P(None, None, "tp")
            inner = functools.partial(ragged_decode_partial, layer=layer)
            if ks_pool is not None:
                inner = lambda q_, k_, v_, t_, l_, ks_, vs_: \
                    ragged_decode_partial(q_, k_, v_, t_, l_, layer=layer,
                                          ks_pool=ks_, vs_pool=vs_)
            fn = jax.shard_map(
                inner, mesh=mesh,
                in_specs=(P(None, "tp", None), pool_s, pool_s, P(), P())
                + ((scale_s, scale_s) if ks_pool is not None else ()),
                out_specs=(P(None, "tp", None, None), P(None, "tp", None),
                           P(None, "tp", None)),
                axis_names={"tp"}, check_vma=False)
            args = (q, k_pool, v_pool, block_table, lengths)
            if ks_pool is not None:
                args += (ks_pool, vs_pool)
            return fn(*args)
    N, Hq, D = q.shape
    kp, vp = _as5d(k_pool), _as5d(v_pool)
    bs, Hkv = kp.shape[2], kp.shape[3]
    mb = block_table.shape[1]
    assert Hq % Hkv == 0, (Hq, Hkv)
    G = Hq // Hkv
    kv_int8 = kp.dtype == jnp.int8
    if kv_int8 and (ks_pool is None or vs_pool is None):
        raise ValueError("int8 pools require ks_pool/vs_pool scales")
    qg = q.reshape(N, Hkv, G, D)

    in_specs = [
        pl.BlockSpec((1, Hkv, G, D), lambda n, l, t, ln: (n, 0, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),     # pools stay in HBM
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    inputs = [qg, kp, vp]
    scratch = [pltpu.VMEM((2, bs, Hkv, D), kp.dtype),
               pltpu.VMEM((2, bs, Hkv, D), vp.dtype)]
    if kv_int8:
        ksp = ks_pool if ks_pool.ndim == 4 else ks_pool[None]
        vsp = vs_pool if vs_pool.ndim == 4 else vs_pool[None]
        in_specs += [pl.BlockSpec(memory_space=pl.ANY),
                     pl.BlockSpec(memory_space=pl.ANY)]
        inputs += [ksp.astype(jnp.float32), vsp.astype(jnp.float32)]
        scratch += [pltpu.VMEM((2, bs, Hkv), jnp.float32),
                    pltpu.VMEM((2, bs, Hkv), jnp.float32)]
    scratch += [pltpu.VMEM((Hkv, G, D), jnp.float32),
                pltpu.VMEM((Hkv, G), jnp.float32),
                pltpu.VMEM((Hkv, G), jnp.float32),
                pltpu.SemaphoreType.DMA((4 if kv_int8 else 2, 2))]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(N,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, Hkv, G, D), lambda n, l, t, ln: (n, 0, 0, 0)),
            pl.BlockSpec((1, Hkv, G), lambda n, l, t, ln: (n, 0, 0)),
            pl.BlockSpec((1, Hkv, G), lambda n, l, t, ln: (n, 0, 0)),
        ],
        scratch_shapes=scratch,
    )
    acc, m, l = pl.pallas_call(
        functools.partial(_ragged_decode_kernel, block_size=bs, n_kv=Hkv,
                          max_blocks=mb, kv_int8=kv_int8),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((N, Hkv, G, D), jnp.float32),
                   jax.ShapeDtypeStruct((N, Hkv, G), jnp.float32),
                   jax.ShapeDtypeStruct((N, Hkv, G), jnp.float32)],
        interpret=_interpret(),
    )(jnp.asarray(layer, jnp.int32)[None], block_table.astype(jnp.int32),
      lengths.astype(jnp.int32), *inputs)
    return acc, m, l


def ragged_paged_decode(q, cache: PagedKVCache, layer=0, ks_pool=None,
                        vs_pool=None, mesh=None) -> jax.Array:
    """Normalized ragged decode attention: q [N, Hq, D] -> [N, Hq, D],
    attending each slot's first ``cache.lengths[n]`` pool positions via
    the true-length block walk (:func:`ragged_decode_partial`). Same
    contract as :func:`paged_attention` — which remains the XLA gather
    reference and the numerics oracle in tests — but lengths are a
    runtime operand: one compiled program serves any length mix, reads
    no block past any slot's length, and holds only two blocks in VMEM
    however long the context. Zero-length slots return 0. ``mesh``
    shards the walk over the 'tp' axis (see
    :func:`ragged_decode_partial`)."""
    N, Hq, D = q.shape
    acc, m, l = ragged_decode_partial(
        q, cache.k_pool, cache.v_pool, cache.block_table, cache.lengths,
        layer=layer, ks_pool=ks_pool, vs_pool=vs_pool, mesh=mesh)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = jnp.where((l > 0)[..., None], out, 0.0)
    return out.reshape(N, Hq, D).astype(q.dtype)


def paged_attention(q, cache: PagedKVCache) -> jax.Array:
    """Decode attention for one query token per sequence.
    q: [B, Hq, D] → [B, Hq, D]. Keys beyond each sequence's length are
    masked. GQA-native: Hq may be G * Hkv (pool heads); query heads are
    grouped against their kv head in the einsum, so the paged pool is never
    materialized repeated (decode is KV-bandwidth-bound — same design as
    models/llama._cached_attention)."""
    B, Hq, D = q.shape
    nb, bs, Hkv = cache.k_pool.shape[0], cache.k_pool.shape[1], \
        cache.k_pool.shape[2]
    mb = cache.block_table.shape[1]
    assert Hq % Hkv == 0, (Hq, Hkv)
    G = Hq // Hkv

    # gather each sequence's blocks: [B, mb, bs, Hkv, D] → [B, mb*bs, Hkv, D]
    k = cache.k_pool[cache.block_table].reshape(B, mb * bs, Hkv, D)
    v = cache.v_pool[cache.block_table].reshape(B, mb * bs, Hkv, D)

    qg = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k,
                   preferred_element_type=jnp.float32) / math.sqrt(D)
    valid = jnp.arange(mb * bs)[None, :] < cache.lengths[:, None]  # [B, K]
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgk,bkhd->bhgd", p, v)
    return out.reshape(B, Hq, D)
