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
  block table at its TRUE length, a chunk of several blocks per loop
  iteration — blocks past ``ceil(len/bs)`` are never fetched and chunks
  past them never visited (the walk's trip count ends there: no DMA, no
  FLOPs), the tail inside the last chunk is masked, all KV heads of a
  chunk go through one dot, and the softmax runs online across the
  chunks, so nothing is
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
           "ragged_paged_decode", "latent_decode_partial",
           "flat_decode_partial", "pack_queries", "unpack_outputs"]


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
    for one it compiles — the engine's selection by shape. ``head_dim`` is
    the minor dim of the pool's rows, which the block DMA slices: a
    multiple of the 128-lane tile runs on the chip, anything else is
    refused (found on the chip at rows of 8 and of 64). A model of head
    dim 64 walks on the chip all the same where its pools keep a token's
    KV heads side by side in ONE row (values then keys, 2 x 8 x 64 = 1024
    lanes, ``flat_decode_partial``; models/lfm2_moe.py does, and asks with
    its row's width); int8 pools are refused at any head dim."""
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
# Grid: one program per slot. The pool layout keeps (Hkv, D) as the
# Mosaic-tiled pair, so a per-head DMA would slice the tiled Hkv dim
# (illegal) and a per-(slot, head) grid re-DMAing whole [bs, Hkv, D]
# blocks would multiply the KV read bytes by Hkv on a bandwidth-bound
# path. Pulling one head's [bs, D] out of a VMEM-resident block is no
# better: bf16 packs two heads into each 32-bit sublane word, and the
# compiler's dump of that extraction is ~90 rotate/shuffle/select
# operations per (block, head) — it, not the copy, was the walk's time.
#
# So the walk never separates the heads. It moves a CHUNK of C blocks per
# loop iteration (C copies of K and of V in flight into the other half of
# a two-chunk buffer while this half computes), views the chunk flat as
# [C*bs*Hkv, D] rows — the bytes as they lie, no relayout — and runs ALL
# query heads against it in one dot. Row (token t, head h) of the chunk
# is column t*Hkv + h of the scores; the columns of another KV head than
# the query head's own are masked to -1e30 with the tail, before the
# running max, so their probabilities are exactly 0.0 in the PV dot. The
# MXU does Hkv times the useful FLOPs and is idle all the same: the bytes
# are what this kernel costs.
# ---------------------------------------------------------------------------

# flat rows (tokens x KV heads) one loop iteration aims at, and the VMEM
# the two-chunk K and V buffers may take (a sixth of the scoped default)
_WALK_ROWS = 1024
_WALK_VMEM_BYTES = 4 << 20


def _walk_chunk_blocks(block_size, n_kv, head_dim, itemsize, max_blocks):
    """Blocks the ragged walk moves per loop iteration, from what the
    kernel can see at trace time: enough tokens x KV heads to fill
    ``_WALK_ROWS`` rows (128 tokens at 8 KV heads, 256 on a tp=2 shard's
    4), halved while two chunks of K and V exceed the VMEM budget, never
    more than the table is wide."""
    c = max(1, _WALK_ROWS // (block_size * n_kv))
    while c > 1 and (4 * c * block_size * n_kv * head_dim * itemsize
                     > _WALK_VMEM_BYTES):
        c //= 2
    return min(c, max_blocks)


def _ragged_decode_kernel(layer_ref, table_ref, lens_ref, q_ref,
                          k_pool_ref, v_pool_ref, *rest, block_size,
                          n_kv, max_blocks, chunk, kv_int8):
    """Grid (N,): walk slot n's block table up to ``ceil(lens[n]/bs)``
    REAL blocks, ``chunk`` blocks per loop iteration, with one online-
    softmax update per chunk. Blocks past the length are never fetched
    and chunks past it never visited — the fori_loop trip count ends the
    walk there, and program size stays O(chunk), not O(table width) — so
    a slot's cost scales with its true length whatever the table width.
    Every position a dot touches is either a row copied from a real block
    or, in the last chunk's remainder, masked (K) and zeroed (V): the
    tail is masked to -1e30 before the running max, so its exp is exactly
    0.0 (bucketed-path exactness argument, applied per chunk), and 0.0
    times a zero V row adds nothing, where stale VMEM could be a NaN.
    int8 pools: the [bs, Hkv, D] payload blocks and [bs, Hkv] per-entry
    scale blocks stream as stored; the payload widens in-register (int8
    -> q dtype is exact) and the K scale multiplies the f32 scores / the
    V scale folds into the probabilities — attn_qk / attn_pv's
    scale-folding math, inlined.

    Emits the online-softmax PARTIAL state per query head: unnormalized
    ``acc`` (f32 [N, Hq, D]), running max ``m`` and sum ``l`` (f32
    [N, Hq, 1]) — the flash-decoding combine contract, so a caller can
    merge in-flight tokens (the engine's in-call ring) before
    normalizing. A slot with length 0 emits (acc=0, m=-1e30, l=0), the
    identity of the combine."""
    if kv_int8:
        (ks_pool_ref, vs_pool_ref, acc_ref, m_ref, l_ref,
         kbuf, vbuf, ksbuf, vsbuf, tokcol, sems) = rest
    else:
        (acc_ref, m_ref, l_ref, kbuf, vbuf, tokcol, sems) = rest
    n = pl.program_id(0)
    lyr = layer_ref[0]
    ln = lens_ref[n]
    Hq, D = q_ref.shape[1:]
    group = Hq // n_kv
    C, T = chunk, chunk * block_size
    R = T * n_kv                           # rows of a chunk viewed flat
    sm_scale = 1.0 / math.sqrt(D)
    # the flat view of a chunk. A dtype that packs (bf16: two KV heads of
    # a token in each 32-bit sublane word) is read as the words it lies
    # in and bitcast back in registers: read as bf16, each load comes out
    # in the buffer's (Hkv, 128) tiling and is shuffled into the dot's
    # (16, 128) — the same bytes — which the copies hide at 8 KV heads and
    # not at a tp shard's 4 (measured on the chip: 1.29 ms against 1.64
    # for sixteen layers of sixteen slots).
    pack = 4 // kbuf.dtype.itemsize
    if pack > 1 and n_kv % pack == 0:
        def flat(buf):
            words = buf.bitcast(jnp.uint32).reshape(2, R // pack, D)
            return lambda half: pltpu.bitcast(words[half], buf.dtype)
    else:
        def flat(buf):
            rows = buf.reshape(2, R, D)
            return lambda half: rows[half]
    kflat, vflat = flat(kbuf), flat(vbuf)

    # column c of a chunk's scores is (token c // Hkv, KV head c % Hkv)
    # and row r is a query head of KV head r // group: tokcol holds the
    # column's token where the two heads are one, and elsewhere a number
    # no length reaches. Scratch outlives the grid step: filled once.
    @pl.when(n == 0)
    def _():
        col = jax.lax.broadcasted_iota(jnp.int32, (Hq, R), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (Hq, R), 0)
        tokcol[...] = jnp.where(col % n_kv == row // group, col // n_kv,
                                jnp.int32(2 ** 30))

    nblk = jnp.minimum((ln + block_size - 1) // block_size, max_blocks)
    nchunk = (nblk + C - 1) // C

    def copies(c, j, half):
        blk = table_ref[n, c * C + j]
        rows = pl.ds(j * block_size, block_size)
        cps = [pltpu.make_async_copy(k_pool_ref.at[lyr, blk],
                                     kbuf.at[half, rows], sems.at[0, half]),
               pltpu.make_async_copy(v_pool_ref.at[lyr, blk],
                                     vbuf.at[half, rows], sems.at[1, half])]
        if kv_int8:
            cps += [pltpu.make_async_copy(ks_pool_ref.at[lyr, blk],
                                          ksbuf.at[half, rows],
                                          sems.at[2, half]),
                    pltpu.make_async_copy(vs_pool_ref.at[lyr, blk],
                                          vsbuf.at[half, rows],
                                          sems.at[3, half])]
        return cps

    def each_block(c, half, op):
        """``op`` ("start" or "wait") the copies of the blocks of chunk c
        that lie under the length: a loop and not an unroll, so the
        kernel is traced and compiled once per call site whatever C is."""
        def body(j, _):
            for cp in copies(c, j, half):
                getattr(cp, op)()
            return 0
        jax.lax.fori_loop(0, jnp.minimum(C, nblk - c * C), body, 0)

    @pl.when(nchunk > 0)
    def _():
        each_block(0, 0, "start")

    def walk(c, carry):
        m_prev, l_prev, acc = carry
        half = jax.lax.rem(c, 2)

        # chunk c+1 streams into the other half while c computes (the
        # two-slot pipeline, C copies deep; each_block's trip count ends
        # the stream at the slot's last real block)
        @pl.when(c + 1 < nchunk)
        def _():
            each_block(c + 1, 1 - half, "start")

        each_block(c, half, "wait")

        def zero(j, _):
            rows = pl.ds(j * block_size, block_size)
            vbuf[half, rows] = jnp.zeros(
                (block_size,) + vbuf.shape[2:], vbuf.dtype)
            if kv_int8:
                vsbuf[half, rows] = jnp.zeros(
                    (block_size, n_kv), jnp.float32)
            return 0
        jax.lax.fori_loop(jnp.minimum(C, nblk - c * C), C, zero, 0)

        q = q_ref[0]                                         # [Hq, D]
        k = kflat(half)                                      # [R, D]
        if kv_int8:
            k = k.astype(q.dtype)                # int8 widen: exact
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # [Hq, R]
        if kv_int8:
            s = s * ksbuf[half].reshape(1, R)
        s = jnp.where(tokcol[...] < ln - c * T, s, jnp.float32(-1e30))
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = vflat(half)
        if kv_int8:
            # V scale rides the probabilities (it varies along the
            # contracted axis) and int8 V widens in-register
            p = p * vsbuf[half].reshape(1, R)
            v = v.astype(jnp.float32)
        else:
            p = p.astype(v.dtype)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # [Hq, D]
        return m_new, l_new, acc * alpha + pv

    m, l, acc = jax.lax.fori_loop(
        0, nchunk, walk,
        (jnp.full((Hq, 1), -1e30, jnp.float32),
         jnp.zeros((Hq, 1), jnp.float32), jnp.zeros((Hq, D), jnp.float32)))
    acc_ref[0] = acc
    m_ref[0] = m
    l_ref[0] = l


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
    blocks of it, ``_walk_chunk_blocks`` of them per loop iteration.
    VMEM use is two chunks of K and of V (1 MiB in all at 8 KV heads of
    128 in bf16) + the chunk's [Hq, rows] scores and mask + the [Hq, D]
    accumulator, independent of context length — no long-context
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
    C = _walk_chunk_blocks(bs, Hkv, D, kp.dtype.itemsize, mb)
    T = C * bs

    in_specs = [
        pl.BlockSpec((1, Hq, D), lambda n, l, t, ln: (n, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),     # pools stay in HBM
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    inputs = [q, kp, vp]
    scratch = [pltpu.VMEM((2, T, Hkv, D), kp.dtype),
               pltpu.VMEM((2, T, Hkv, D), vp.dtype)]
    if kv_int8:
        ksp = ks_pool if ks_pool.ndim == 4 else ks_pool[None]
        vsp = vs_pool if vs_pool.ndim == 4 else vs_pool[None]
        in_specs += [pl.BlockSpec(memory_space=pl.ANY),
                     pl.BlockSpec(memory_space=pl.ANY)]
        inputs += [ksp.astype(jnp.float32), vsp.astype(jnp.float32)]
        scratch += [pltpu.VMEM((2, T, Hkv), jnp.float32),
                    pltpu.VMEM((2, T, Hkv), jnp.float32)]
    scratch += [pltpu.VMEM((Hq, T * Hkv), jnp.int32),
                pltpu.SemaphoreType.DMA((4 if kv_int8 else 2, 2))]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(N,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, Hq, D), lambda n, l, t, ln: (n, 0, 0)),
            pl.BlockSpec((1, Hq, 1), lambda n, l, t, ln: (n, 0, 0)),
            pl.BlockSpec((1, Hq, 1), lambda n, l, t, ln: (n, 0, 0)),
        ],
        scratch_shapes=scratch,
    )
    acc, m, l = pl.pallas_call(
        functools.partial(_ragged_decode_kernel, block_size=bs, n_kv=Hkv,
                          max_blocks=mb, chunk=C, kv_int8=kv_int8),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((N, Hq, D), jnp.float32),
                   jax.ShapeDtypeStruct((N, Hq, 1), jnp.float32),
                   jax.ShapeDtypeStruct((N, Hq, 1), jnp.float32)],
        interpret=_interpret(),
    )(jnp.asarray(layer, jnp.int32)[None], block_table.astype(jnp.int32),
      lengths.astype(jnp.int32), *inputs)
    return (acc.reshape(N, Hkv, G, D), m.reshape(N, Hkv, G),
            l.reshape(N, Hkv, G))


# ---------------------------------------------------------------------------
# The latent walk: the same true-length chunked walk over a pool of LATENT
# rows (multi-head latent attention in its absorbed form). A cached token is
# one row per layer, shared by every query head: its first ``v_cols``
# columns are the normed latent, which is key AND value, the next ones the
# roped key part, the rest padding up to a multiple of the 128 lanes (zero
# in the pool and in the query, so it adds exactly 0.0 to a score). The
# query arrives absorbed — q_nope . W_UK beside q_rope — so scores are ONE
# dot of all query heads against the chunk as it lies, and the values are a
# lane-aligned slice of the same buffer: one copy per block, no second
# pool, Hkv = 1 and no head mask. Per cached token-layer the heads do
# 2 * Hq * (row + v_cols) FLOPs against one row's bytes: at 128 heads on
# 512 + 64 columns that is the v5e's ridge, so the walk is bound by the MXU
# and by HBM at once (the GQA walk above is bytes only).
# ---------------------------------------------------------------------------
def _latent_decode_kernel(layer_ref, table_ref, lens_ref, *rest, block_size,
                          max_blocks, chunk, v_cols, sm_scale,
                          windowed=False):
    """Grid (N,): slot n's walk, ``chunk`` blocks per loop iteration, as
    ``_ragged_decode_kernel`` does it (two-chunk buffer, copies of chunk
    c+1 in flight while c computes, the trip count ends at the slot's last
    real block, the last chunk's remainder zeroed because a value row of
    stale VMEM could be a NaN). Emits the online-softmax partials (acc
    [Hq, v_cols], m, l) for the flash-decoding combine.

    ``windowed`` (static): a fourth scalar operand gives each slot a START
    beside its length, and the table is a RING of ``max_blocks`` columns:
    logical block b lies in column ``b % max_blocks``. The walk then
    begins at block ``start // bs`` (the blocks wholly before it are never
    fetched, whatever the context) and the head of its first block is
    masked as the tail of its last is. Softmax does not care in which
    order blocks arrive, so nothing is sorted."""
    if windowed:
        start_ref, *rest = rest
    q_ref, pool_ref, acc_ref, m_ref, l_ref, buf, sems = rest
    n = pl.program_id(0)
    lyr = layer_ref[0]
    ln = lens_ref[n]
    Hq = q_ref.shape[1]
    C, T = chunk, chunk * block_size
    if windowed:
        st = jnp.minimum(start_ref[n], ln)
        sb = st // block_size                  # the walk's first block
        # (a start at or past the length leaves nothing to walk: a block
        # of masked columns alone would count each as exp(0))
        nblk = jnp.where(st < ln, jnp.minimum(
            (ln + block_size - 1) // block_size - sb, max_blocks), 0)
    else:
        nblk = jnp.minimum((ln + block_size - 1) // block_size, max_blocks)
    nchunk = (nblk + C - 1) // C

    def each_block(c, half, op):
        def body(j, _):
            col = (jax.lax.rem(sb + c * C + j, max_blocks) if windowed
                   else c * C + j)
            cp = pltpu.make_async_copy(
                pool_ref.at[lyr, table_ref[n, col]],
                buf.at[half, pl.ds(j * block_size, block_size)],
                sems.at[half])
            getattr(cp, op)()
            return 0
        jax.lax.fori_loop(0, jnp.minimum(C, nblk - c * C), body, 0)

    @pl.when(nchunk > 0)
    def _():
        each_block(0, 0, "start")

    def walk(c, carry):
        m_prev, l_prev, acc = carry
        half = jax.lax.rem(c, 2)

        @pl.when(c + 1 < nchunk)
        def _():
            each_block(c + 1, 1 - half, "start")

        each_block(c, half, "wait")

        def zero(j, _):
            buf[half, pl.ds(j * block_size, block_size)] = jnp.zeros(
                (block_size, buf.shape[2]), buf.dtype)
            return 0
        jax.lax.fori_loop(jnp.minimum(C, nblk - c * C), C, zero, 0)

        q = q_ref[0]                                         # [Hq, W]
        k = buf[half]                                        # [T, W]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # [Hq, T]
        tok = jax.lax.broadcasted_iota(jnp.int32, (Hq, T), 1)
        if windowed:
            # the chunk's first token is position base: keep [start, len)
            base = sb * block_size + c * T
            s = jnp.where((tok < ln - base) & (tok >= st - base), s,
                          jnp.float32(-1e30))
        else:
            s = jnp.where(tok < ln - c * T, s, jnp.float32(-1e30))
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(k.dtype), k[:, :v_cols], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # [Hq, v_cols]
        return m_new, l_new, acc * alpha + pv

    m, l, acc = jax.lax.fori_loop(
        0, nchunk, walk,
        (jnp.full((Hq, 1), -1e30, jnp.float32),
         jnp.zeros((Hq, 1), jnp.float32),
         jnp.zeros((Hq, v_cols), jnp.float32)))
    acc_ref[0] = acc
    m_ref[0] = m
    l_ref[0] = l


def latent_decode_partial(q, pool, block_table, lengths, *, layer=0,
                          v_cols: int, sm_scale: float,
                          name: str = "mla_latent_walk", starts=None):
    """The latent walk, partial (flash-decoding) form. q: [N, Hq, W]
    absorbed queries; pool: [L, NB, BS, W] latent rows (W a multiple of
    128, ``v_cols`` too); block_table: [N, MB]; lengths: [N], a runtime
    operand. Returns ``(acc [N, Hq, v_cols] f32, m [N, Hq] f32, l [N, Hq]
    f32)``; a slot of length 0 gives the combine's identity. The chunk is
    ``_walk_chunk_blocks`` with one KV head: 1024 tokens a loop iteration
    at blocks of 16, 2.5 MiB of VMEM for the two-chunk buffer at W = 640.

    ``starts`` [N] (a window layer's; absent, the kernel is the one a
    full layer compiles): slot n attends to positions ``[starts[n],
    lengths[n])`` only, and ``block_table`` is then a RING of MB columns,
    logical block b in column ``b % MB``: a slot whose window is W tokens
    keeps ``ceil(W / BS) + 1`` blocks whatever its context and writes a new
    block over the one that fell behind the window, and the walk DMAs
    blocks ``[starts[n] // BS, ceil(lengths[n] / BS))`` and masks the head
    of the first as it masks the tail of the last."""
    N, Hq, W = q.shape
    bs, mb = pool.shape[2], block_table.shape[1]
    assert pool.shape[3] == W and W % 128 == 0 and v_cols % 128 == 0, (
        pool.shape, W, v_cols)
    C = _walk_chunk_blocks(bs, 1, W, pool.dtype.itemsize, mb)
    row = lambda n, l, t, ln: (n, 0, 0)
    kernel = functools.partial(_latent_decode_kernel, block_size=bs,
                               max_blocks=mb, chunk=C, v_cols=v_cols,
                               sm_scale=sm_scale)
    scalars = [jnp.asarray(layer, jnp.int32)[None],
               block_table.astype(jnp.int32), lengths.astype(jnp.int32)]
    if starts is not None:
        row = lambda n, l, t, ln, st: (n, 0, 0)
        kernel = functools.partial(kernel, windowed=True)
        scalars.append(starts.astype(jnp.int32))
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=(N,),
            in_specs=[pl.BlockSpec((1, Hq, W), row),
                      pl.BlockSpec(memory_space=pl.ANY)],  # pool stays in HBM
            out_specs=[pl.BlockSpec((1, Hq, v_cols), row),
                       pl.BlockSpec((1, Hq, 1), row),
                       pl.BlockSpec((1, Hq, 1), row)],
            scratch_shapes=[pltpu.VMEM((2, C * bs, W), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct((N, Hq, v_cols), jnp.float32),
                   jax.ShapeDtypeStruct((N, Hq, 1), jnp.float32),
                   jax.ShapeDtypeStruct((N, Hq, 1), jnp.float32)],
        interpret=_interpret(), name=name,
    )(*scalars, q, pool)
    return acc, m[..., 0], l[..., 0]


# ---------------------------------------------------------------------------
# The flat walk: grouped-query attention at a head dim that does not fill
# the 128 lanes (64). A pool whose rows are [Hkv, 64] cannot be sliced by
# the block DMA (``ragged_tpu_refusal``), and one whose rows pair the heads
# up ([Hkv / 2, 128]) is re-laid out whole, there and back, around the
# prefill's scatter of a piece's blocks (4 x 0.8 GB a piece at LFM2's cell,
# read in the compiled program). So a token's KV heads lie side by side in
# ONE row of a layer's ONE pool, values first: [V (Hkv x 64) | K (Hkv x
# 64)] = 1024 lanes, the bytes of the unpadded K and V rows, which scatters
# in place like a latent row does. That row IS a latent row — its first
# ``v_cols`` columns the values, all of it the key — once the query is zero
# over the value columns, so the walk is the latent walk above, unchanged:
# a query sits in the columns of its own KV head's key with zeros in the
# others (``pack_queries``), one dot of all query heads against a chunk as
# it lies gives exactly q . k of each head's own key, and the columns of
# its head are taken out of the weighted sum of whole value rows
# (``unpack_outputs``). The MXU contracts 1024 columns for 64 (an eighth of
# the v5e's ridge): the bytes are what this walk costs, and they are the
# mathematics'. Measured against a K and a V pool walked by a second copy
# of the kernel with two DMAs a block (PR 32, 91k live tokens at 64 slots):
# 0.427 ms a layer against 0.441.
# ---------------------------------------------------------------------------
def pack_queries(q, n_kv: int):
    """Queries [..., Hq, D] for keys whose ``n_kv`` heads lie side by side
    in a row: [..., Hq, n_kv * D], each query in the columns of its own KV
    head (head ``h // (Hq / n_kv)``), zeros in the others."""
    Hq, D = q.shape[-2:]
    own = jnp.arange(Hq) // (Hq // n_kv)                            # [Hq]
    sel = (own[:, None] == jnp.arange(n_kv)[None, :]).astype(q.dtype)
    return (q[..., None, :] * sel[:, :, None]).reshape(
        q.shape[:-1] + (n_kv * D,))


def unpack_outputs(o, n_kv: int):
    """Each query head's own KV head's columns out of its output over
    whole rows: [..., Hq, n_kv * D] -> [..., Hq, D]."""
    Hq, W = o.shape[-2:]
    own = jnp.arange(Hq) // (Hq // n_kv)
    o = o.reshape(o.shape[:-1] + (n_kv, W // n_kv))
    return jnp.take_along_axis(
        o, own.reshape((1,) * (o.ndim - 3) + (Hq, 1, 1)), axis=-2)[..., 0, :]


def flat_decode_partial(q, pool, block_table, lengths, *, n_kv: int,
                        layer=0, name: str = "flat_walk", starts=None):
    """The flat walk, partial (flash-decoding) form. q: [N, Hq, D]; pool:
    [L, NB, BS, 2 * n_kv * D], a token's values then its keys, heads side
    by side (``n_kv * D`` a multiple of 128); block_table: [N, MB];
    lengths: [N], a runtime operand. Returns ``(acc [N, Hkv, G, D] f32, m
    [N, Hkv, G] f32, l [N, Hkv, G] f32)`` as ``ragged_decode_partial``
    does; a slot of length 0 gives the combine's identity. ``starts``: a
    window layer's walk over a ring (``latent_decode_partial``)."""
    N, Hq, D = q.shape
    W = n_kv * D
    assert pool.shape[3] == 2 * W and Hq % n_kv == 0, (pool.shape, W)
    qk = pack_queries(q, n_kv)
    acc, m, l = latent_decode_partial(
        jnp.concatenate([jnp.zeros_like(qk), qk], -1), pool, block_table,
        lengths, layer=layer, v_cols=W, sm_scale=1.0 / math.sqrt(D),
        name=name, starts=starts)
    G = Hq // n_kv
    return (unpack_outputs(acc, n_kv).reshape(N, n_kv, G, D),
            m.reshape(N, n_kv, G), l.reshape(N, n_kv, G))


def ragged_paged_decode(q, cache: PagedKVCache, layer=0, ks_pool=None,
                        vs_pool=None, mesh=None) -> jax.Array:
    """Normalized ragged decode attention: q [N, Hq, D] -> [N, Hq, D],
    attending each slot's first ``cache.lengths[n]`` pool positions via
    the true-length block walk (:func:`ragged_decode_partial`). Same
    contract as :func:`paged_attention` — which remains the XLA gather
    reference and the numerics oracle in tests — but lengths are a
    runtime operand: one compiled program serves any length mix, reads
    no block past any slot's length, and holds only two chunks of blocks
    in VMEM however long the context. Zero-length slots return 0. ``mesh``
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
