"""Grouped-query attention over a cache whose row is all of a token's value
heads and then all of its key heads, side by side (``[V | K]``, one pool a
layer: ``docs/served_models.md`` says why): a prefill piece's blockwise
attention over [history ; piece] and a decode step's over [pool ; in-call
ring]. ``models/lfm2_moe.py`` and, through ``models/window_kv.py``,
``models/mellum.py`` and ``models/afmoe.py`` share it; what
differs between them and between Mellum's two kinds of layer (a band, a
start, the kernels' names in a trace) comes in as arguments that are absent
by default, so a model without them compiles the program it always had.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels.paged_attention import flat_decode_partial
from ..kernels.pallas_attention import (combine_partials, flash_call_tiles,
                                        flash_partial)

__all__ = ["prefill_attention", "prefill_attention_tiles",
           "decode_attention", "pack_rows"]


def pack_rows(k, v):
    """The cache rows of new tokens: k, v [..., Hkv, D] -> [..., 2 * Hkv *
    D], values then keys."""
    lead = k.shape[:-2]
    return jnp.concatenate([v.reshape(lead + (-1,)),
                            k.reshape(lead + (-1,))], -1)


def prefill_attention(q, k, v, *, chunk_name: str, chunk_band=None,
                      history: Optional[Tuple] = None):
    """Attention of a piece over [history ; piece], both parts blockwise,
    one softmax. q [B, S, H, D], k and v [B, S, Hkv, D] -> [B, S, H * D].

    ``chunk_band`` [B * Hkv]: a lower bound on the key a row of the piece
    may see inside the piece (``flash_partial(band_lo=)``). ``history``:
    ``(pool [1, NB, bs, 2 * Hkv * D], table [B, nbk], length [B], band_lo [B
    * Hkv] or None, name)``: the cached rows through their block table, of
    which ``length`` are real."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    scale = 1.0 / math.sqrt(D)
    heads = lambda x: jnp.swapaxes(x, 1, 2).reshape(
        -1, x.shape[1], D)                       # [B,T,h,D] -> [B*h,T,D]
    qf = heads(q)
    o, lse = flash_partial(qf, heads(k), heads(v), scale=scale,
                           causal=True, band_lo=chunk_band, name=chunk_name)
    if history is not None:
        pool, tbl, n_hist, band, name = history
        rows = pool[0][tbl].reshape(B, -1, 2, Hkv, D)        # a row is [V | K]
        n_hist = jnp.repeat(n_hist.astype(jnp.int32), Hkv)
        o_h, lse_h = flash_partial(
            qf, heads(rows[:, :, 1]), heads(rows[:, :, 0]), scale=scale,
            kv_len=n_hist, band_lo=band, name=name)
        o = combine_partials(o, lse, o_h, lse_h)
    return jnp.swapaxes(o.reshape(B, H, S, D), 1, 2).reshape(B, S, H * D)


def prefill_attention_tiles(S: int, H: int, Hkv: int, D: int, *,
                            chunk_band=None, history=None):
    """``prefill_attention``'s two calls counted, host side and from
    numbers alone: ``(the chunk's, the history's or None)``, each the
    ``(interior, edge, skipped)`` grid steps of ONE KV head
    (``kernels.pallas_attention.flash_tile_counts``). ``chunk_band``: the
    band's lower bound inside the piece; ``history``: ``(keys gathered,
    keys real, lower bound or None)``."""
    chunk = flash_call_tiles(H // Hkv, S, S, D, D, band_lo=chunk_band,
                             causal=True)
    if history is None:
        return chunk, None
    T, n_hist, band = history
    return chunk, flash_call_tiles(H // Hkv, S, T, D, D, kv_len=n_hist,
                                   band_lo=band)


def decode_attention(q, k, v, ring, a: int, t, ring_mask, dt, *,
                     walk: Optional[Tuple] = None,
                     dense: Optional[Tuple] = None):
    """One decode step's attention of N slots. q [N, H, D], k and v [N,
    Hkv, D] (the step's own token); ``ring`` [L, N, S, 2 * Hkv * D] the
    in-call rows of the layers of this kind, of which plane ``a`` takes the
    new row at ``t``; ``ring_mask`` broadcastable to [N, Hkv, G, S]. On the
    chip ``walk = (pool, table, lengths, starts or None, name)``: the flat
    walk's partials over the pool, combined with the ring (which always
    holds the step's own token: the sum is >= 1). Off it ``dense = (keys,
    values [N, P, Hkv, D], mask)``: the gathered prefix under one softmax.
    Returns (attention [N, H * D] in ``dt``, the ring with the new row)."""
    N, H, D = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    scale = 1.0 / math.sqrt(D)
    ring = jax.lax.dynamic_update_slice(
        ring, jnp.concatenate(
            [v.reshape(1, N, 1, Hkv * D), k.reshape(1, N, 1, Hkv * D)], -1),
        (a, 0, t, 0))
    qg = q.reshape(N, Hkv, G, D)
    rows = ring[a].reshape(N, -1, 2, Hkv, D)              # a row is [V | K]
    rka, rva = rows[:, :, 1], rows[:, :, 0]
    s_rng = jnp.einsum("nhgd,nshd->nhgs", qg, rka,
                       preferred_element_type=jnp.float32) * scale
    s_rng = jnp.where(ring_mask, s_rng, -1e30)
    if walk is not None:
        pool, table, lengths, starts, name = walk
        acc_p, m_p, l_p = flat_decode_partial(
            q, pool, table, lengths, n_kv=Hkv, name=name, starts=starts)
        m_tot = jnp.maximum(m_p, jnp.max(s_rng, axis=-1))
        corr = jnp.exp(m_p - m_tot)
        p_rng = jnp.exp(s_rng - m_tot[..., None])
        l_tot = l_p * corr + jnp.sum(p_rng, axis=-1)
        att = (acc_p * corr[..., None] + jnp.einsum(
            "nhgs,nshd->nhgd", p_rng, rva,
            preferred_element_type=jnp.float32)) / l_tot[..., None]
    else:
        kd, vd, pre_mask = dense
        P = kd.shape[1]
        s_pre = jnp.einsum("nhgd,nphd->nhgp", qg, kd,
                           preferred_element_type=jnp.float32) * scale
        s_pre = jnp.where(pre_mask, s_pre, -1e30)
        probs = jax.nn.softmax(
            jnp.concatenate([s_pre, s_rng], axis=-1), axis=-1)
        att = (jnp.einsum("nhgp,nphd->nhgd", probs[..., :P].astype(dt), vd)
               + jnp.einsum("nhgs,nshd->nhgd", probs[..., P:].astype(dt),
                            rva))
    return att.reshape(N, H * D).astype(dt), ring
