"""DeepSeek-V2 behind the serving engine's model interface: multi-head
latent attention over a paged LATENT cache, and sparse-expert layers run as
one chip's share of an expert-parallel deployment.

The equations are the published ones (``benchmark/reference/
deepseek_v2_f32.py`` states them in float32 and imports nothing from here):
pre-norm residual layers; MLA with a low-rank query (``q_lora_rank``) and a
joint key/value latent (``kv_lora_rank``) beside one roped key row shared
by all heads; YaRN-scaled rope on the rope part only; layer 0 a dense
SwiGLU, the rest a shared expert plus top-k of a softmax router under
group-limited routing, gates un-renormalised and scaled.

What the engine sees (the interface of ``models/llama_served.py``):

- **one cache entry per token per layer**: the normed latent (512) then
  the roped key (64), padded with zeros to 640 columns, because Mosaic
  wants a multiple of 128 lanes. One row, one copy per block; the pad is
  11% more bytes than the 576 the mathematics needs and is never
  multiplied by anything but zero. Each layer's rows are a pool of their
  own (``"c0"``, ``"c1"``, ... of shape [1, NB, bs, 640]): a decode step
  writes one row a slot at (block, offset), and over a pool with a
  leading layer axis of 7 XLA re-lays the WHOLE pool out and back around
  that scatter (two copies of 2.3 GB a step, read in the compiled
  program); over a layer's own pool it scatters in place.
- **decode in the absorbed form**: ``q_lat = q_nope . W_UK`` per head, the
  scores of all 128 heads against the shared rows in one dot
  (``kernels.paged_attention.latent_decode_partial``, or a dense gather
  off a TPU), ``o = (softmax . c_kv) . W_UV``. Keys and values are never
  expanded.
- **prefill in two parts** that one softmax joins
  (``pallas_attention.combine_partials``), both in the expanded form
  (q/k 192 wide padded to 256, v 128), blockwise: a chunk's own tokens
  against keys and values expanded before the call, causal
  (``flash_partial``); the tokens of earlier chunks against the gathered
  latent rows as they lie, a head's keys and values of a 512-key tile
  made INSIDE the kernel (``latent_history_partial``: ``W_UK`` and
  ``W_UV`` a head are 128 KiB each, and a piece's 1,024 positions of the
  head share the tile's expansion). Expanding a 16k history's keys and
  values in HBM for a padded wave would take tens of GB; in the chip's
  fast memory it costs 256 FLOPs a (query, head, key) beside the 640 of
  the scores and the weighted sum, where the absorbed form (decode's)
  pays 2,304. Both run one row at a time (``lax.map``; the engine's
  programs take one row each): the expanded operands of several rows at
  once would not fit.
- **the chip's share of an expert layer**: the router scores all
  ``n_routed_experts``, the routing rule runs over all groups, and this
  chip computes the pairs that fell on the experts it holds
  (``held_first .. held_first + held_experts - 1``) plus the whole shared
  expert; pairs routed elsewhere are other chips' work and nothing here
  stands in for them or for the exchange. Pad rows of a wave and idle
  slots are not routed.

Departures from the published modelling code, none of them in the logits:
the rope pairs channels (2i, 2i+1) of the stored layout there; here the
rope columns of ``W_UQ`` and ``W_DKV`` are permuted once at load
(``from_published``) so that the rotation is the half-split one, and
``W_UKV`` is stored as its two halves ``w_uk`` [H, 128, 512] and ``w_uv``
[H, 512, 128] so that neither form slices a weight in a step.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..kernels.moe_dispatch import group_limited_routing, held_expert_ffn
from ..kernels.paged_attention import latent_decode_partial
from ..kernels.pallas_attention import (combine_partials, flash_call_tiles,
                                        flash_partial, flash_tile_counts,
                                        latent_history_partial,
                                        latent_history_tiles)
from .llama import _rms_norm
from .llama_served import ServeOpts
from .rope import rope_half as _rope
from .rope import yarn_frequencies

__all__ = ["DeepseekV2Config", "DeepseekV2Served", "from_published",
           "yarn_inv_freq", "LATENT_PAD"]

LATENT_PAD = 128       # a pool row is padded to a multiple of the lanes


@dataclasses.dataclass(frozen=True)
class DeepseekV2Config:
    vocab_size: int = 102400
    hidden_size: int = 5120
    intermediate_size: int = 12288        # the leading dense layers' FFN
    moe_intermediate_size: int = 1536     # one routed expert's FFN
    num_layers: int = 60
    num_heads: int = 128
    q_lora_rank: Optional[int] = 1536     # None: ``w_q`` straight from h
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 160           # the router's width
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    n_group: int = 8
    topk_group: int = 3
    routed_scaling_factor: float = 16.0
    first_k_dense_replace: int = 1
    # the experts this chip holds: its share of an expert-parallel layer
    held_first: int = 0
    held_experts: int = 160
    rope_theta: float = 10000.0
    rope_factor: float = 40.0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 0.707
    rope_mscale_all_dim: float = 0.707
    rope_original_max: int = 4096
    rms_eps: float = 1e-6
    max_seq_len: int = 163840
    dtype: Any = jnp.bfloat16
    remat: bool = False                   # accepted, unused: serving only

    @property
    def latent_width(self) -> int:
        w = self.kv_lora_rank + self.qk_rope_head_dim
        return -(-w // LATENT_PAD) * LATENT_PAD

    @property
    def softmax_scale(self) -> float:
        """``(nope + rope)^-0.5 * m^2`` with YaRN's ``m = 0.1 *
        mscale_all_dim * ln(factor) + 1``."""
        m = 0.1 * self.rope_mscale_all_dim * math.log(self.rope_factor) + 1.0
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m

    def is_moe_layer(self, l: int) -> bool:
        return l >= self.first_k_dense_replace

    def served_model(self):
        return DeepseekV2Served(self)


def _yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(c: DeepseekV2Config):
    """YaRN's inverse frequencies over the rope dims (``rope.
    yarn_frequencies``) and the factor on cos/sin (1.0 where ``mscale ==
    mscale_all_dim``)."""
    inv = yarn_frequencies(c.qk_rope_head_dim, c.rope_theta, c.rope_factor,
                           c.rope_original_max, c.rope_beta_fast,
                           c.rope_beta_slow)
    mscale = (_yarn_mscale(c.rope_factor, c.rope_mscale)
              / _yarn_mscale(c.rope_factor, c.rope_mscale_all_dim))
    return inv, mscale


def from_published(layer: Dict, c: DeepseekV2Config) -> Dict:
    """One layer's leaves in the published layout (``w_uq`` [q_rank,
    H*(nope+rope)], ``w_dkv`` [h, kv_rank+rope], ``w_ukv`` [kv_rank,
    H*(nope+v)], gate/up of the held experts apart) as this program keeps
    them: rope columns de-interleaved, ``W_UKV`` split per head into
    ``w_uk``/``w_uv``, the held experts' gate and up side by side."""
    H, dn, dr, dv = (c.num_heads, c.qk_nope_head_dim, c.qk_rope_head_dim,
                     c.v_head_dim)
    r = c.kv_lora_rank
    perm = jnp.concatenate([jnp.arange(0, dr, 2), jnp.arange(1, dr, 2)])
    # the queries' matrix: ``w_uq`` behind the low-rank ``w_dq``, or (a
    # model with no query compression, ``q_lora_rank`` None) ``w_q``
    wq = "w_q" if c.q_lora_rank is None else "w_uq"
    out = {k: v for k, v in layer.items()
           if k not in (wq, "w_dkv", "w_ukv", "e_gate", "e_up")}
    w_uq = layer[wq].reshape(-1, H, dn + dr)
    out[wq] = jnp.concatenate(
        [w_uq[..., :dn], w_uq[..., dn:][..., perm]], -1).reshape(
            -1, H * (dn + dr))
    w_dkv = layer["w_dkv"]
    out["w_dkv"] = jnp.concatenate([w_dkv[:, :r], w_dkv[:, r:][:, perm]], -1)
    w_ukv = layer["w_ukv"].reshape(r, H, dn + dv)
    out["w_uk"] = jnp.transpose(w_ukv[..., :dn], (1, 2, 0))     # [H, dn, r]
    out["w_uv"] = jnp.transpose(w_ukv[..., dn:], (1, 0, 2))     # [H, r, dv]
    if "e_gate" in layer:
        out["e_gu"] = jnp.concatenate([layer["e_gate"], layer["e_up"]], -1)
    return out


def _swiglu(x, w_gate, w_up, w_down, dt):
    g = jax.nn.silu(x @ w_gate.astype(dt))
    return (g * (x @ w_up.astype(dt))) @ w_down.astype(dt)


class DeepseekV2Served:
    cache_kind = "latent"
    state_entries = ()       # nothing is kept per slot beside the cache
    unsupported = {
        "spec": "there is no draft of this family and spec_verify is "
                "llama's program",
        "prefix_cache": "untested over a latent pool; the trie would work "
                        "on block ids, the suffix prefill's history path "
                        "has no test against it yet",
        "kv_swap": "the swap tier's restore path is tested on K/V pools "
                   "only",
        "mesh": "no sharding recipe for latent attention or the expert "
                "share (the share IS the deployment's expert parallelism)",
        "kv_int8": "the latent walk reads bf16/f32 rows; an int8 latent "
                   "needs its own scale entry and kernel path",
        "disagg": "the relay's spill/restore is tested on K/V pools only",
        "decode_steps": "the latent ring combine is written for one token "
                        "a call",
    }

    def __init__(self, config: DeepseekV2Config):
        c = config
        if c.n_routed_experts % c.n_group:
            raise ValueError("n_group must divide n_routed_experts")
        if not (0 <= c.held_first
                and c.held_first + c.held_experts <= c.n_routed_experts):
            raise ValueError("the held experts lie outside the router's "
                             "width")
        self.config = c
        self.num_layers = c.num_layers
        self.vocab_size = c.vocab_size
        self.dtype = c.dtype
        self._has_experts = c.num_layers > c.first_k_dense_replace

    # -- the cache -----------------------------------------------------------
    def make_pools(self, nb: int, bs: int, kv_int8: bool = False,
                   prefix: str = "") -> Dict:
        c = self.config
        return {f"{prefix}c{l}": jnp.zeros((1, nb, bs, c.latent_width),
                                           c.dtype)
                for l in range(c.num_layers)}

    def ragged_refusal(self, kv_int8: bool):
        return None

    @staticmethod
    def history_blocks(hist_blocks: int, mb: int) -> int:
        """Full width or none: the history kernel takes each row's length
        as a runtime operand and skips the tiles past it, so a history
        costs one program shape whatever its length."""
        return mb if hist_blocks else 0

    # -- top of the model ----------------------------------------------------
    def embed(self, params, tokens):
        return params["embed"].astype(self.dtype)[tokens]

    def final_norm(self, params, x):
        return _rms_norm(x, params["final_norm"], self.config.rms_eps)

    def head(self, params, x):
        return (x @ params["lm_head"].astype(self.dtype)).astype(jnp.float32)

    def decode_head(self, params):
        return params["lm_head"].astype(self.dtype)

    def decode_logits(self, params, head_w, xf):
        return (xf @ head_w).astype(jnp.float32)

    # -- shared pieces -------------------------------------------------------
    def _project(self, p, hn, ang, mscale):
        """From the normed hidden state: the queries' nope and roped parts
        [..., H, dn] / [..., H, dr] and the token's padded latent row
        [..., W] = [normed latent ; roped key ; zeros]."""
        c, dt = self.config, self.dtype
        H, dn, dr = c.num_heads, c.qk_nope_head_dim, c.qk_rope_head_dim
        r = c.kv_lora_rank
        if c.q_lora_rank is None:
            q = hn @ p["w_q"].astype(dt)
        else:
            cq = _rms_norm(hn @ p["w_dq"].astype(dt), p["q_norm"], c.rms_eps)
            q = cq @ p["w_uq"].astype(dt)
        q = q.reshape(hn.shape[:-1] + (H, dn + dr))
        q_nope = q[..., :dn]
        q_rope = _rope(q[..., dn:], ang[..., None, :], mscale)
        ckv = hn @ p["w_dkv"].astype(dt)
        lat = _rms_norm(ckv[..., :r], p["kv_norm"], c.rms_eps)
        k_r = _rope(ckv[..., r:], ang, mscale)
        row = jnp.concatenate(
            [lat, k_r, jnp.zeros(hn.shape[:-1]
                                 + (c.latent_width - r - dr,), dt)], -1)
        return q_nope, q_rope, row

    def _absorb(self, p, q_nope, q_rope):
        """Queries in the latent's coordinates, padded like a pool row."""
        c, dt = self.config, self.dtype
        q_lat = jnp.einsum("...hd,hdc->...hc", q_nope, p["w_uk"].astype(dt))
        pad = c.latent_width - c.kv_lora_rank - c.qk_rope_head_dim
        return jnp.concatenate(
            [q_lat, q_rope, jnp.zeros(q_rope.shape[:-1] + (pad,), dt)], -1)

    def _ffn(self, p, l: int, x, valid):
        """x [T, h] -> (y, counts or None)."""
        c, dt = self.config, self.dtype
        if not c.is_moe_layer(l):
            return _swiglu(x, p["w_gate"], p["w_up"], p["w_down"], dt), None
        probs = jax.nn.softmax(jnp.dot(
            x, p["router"].astype(dt), preferred_element_type=jnp.float32),
            axis=-1)
        gates, idx = group_limited_routing(
            probs, c.n_group, c.topk_group, c.num_experts_per_tok,
            c.routed_scaling_factor)
        routed, counts = held_expert_ffn(x, gates, idx, valid, p["e_gu"],
                                         p["e_down"], c.held_first)
        return routed + _swiglu(x, p["s_gate"], p["s_up"], p["s_down"],
                                dt), counts

    # -- prefill -------------------------------------------------------------
    def prefill_begin(self, params, pools, tokens, true_len, hist_len,
                      ctx_tbl, prefix_nbk: int, opts: ServeOpts):
        B, S = tokens.shape
        inv, mscale = yarn_inv_freq(self.config)
        start = (jnp.zeros((B,), jnp.float32) if hist_len is None
                 else hist_len.astype(jnp.float32))
        pos = start[:, None] + jnp.arange(S, dtype=jnp.float32)[None, :]
        return {"ang": pos[:, :, None] * inv[None, None, :],
                "mscale": mscale, "prefix_nbk": prefix_nbk,
                "hist_len": hist_len, "ctx_tbl": ctx_tbl,
                # pad positions of a row and pad rows are not routed
                "valid": (jnp.arange(S)[None, :]
                          < true_len[:, None]).reshape(B * S)}

    def prefill_mix(self, params, l: int, x, aux, pools, opts: ServeOpts):
        """The token-mixing half of a piece's layer: x [B, S, h] -> (x +
        latent attention, the layer's new latent rows)."""
        c, dt = self.config, self.dtype
        p = params["layers"][l]
        B, S, h = x.shape
        H, dn, dr, dv = (c.num_heads, c.qk_nope_head_dim,
                         c.qk_rope_head_dim, c.v_head_dim)
        scale = c.softmax_scale
        hn = _rms_norm(x, p["attn_norm"], c.rms_eps)
        q_nope, q_rope, row = self._project(p, hn, aux["ang"], aux["mscale"])
        r = c.kv_lora_rank
        qk_pad = -(dn + dr) % 128
        pool = pools[f"{opts.prefix}c{l}"][0]

        gated = "w_g" in p     # a head-wise output gate (arXiv:2505.06708)

        def one_row(args):
            qn, qr, rw = args[:3]                  # [S,H,dn] [S,H,dr] [S,W]
            if gated:
                *args, gate = args
            lat, k_r = rw[:, :r], rw[:, r:r + dr]
            # the chunk itself, expanded: q/k padded with zero columns
            k_nope = jnp.einsum("sc,hdc->hsd", lat, p["w_uk"].astype(dt))
            v = jnp.einsum("sc,hcd->hsd", lat, p["w_uv"].astype(dt))
            zq = jnp.zeros((H, S, qk_pad), dt)
            qf = jnp.concatenate([jnp.swapaxes(qn, 0, 1),
                                  jnp.swapaxes(qr, 0, 1), zq], -1)
            kf = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_r[None], (H, S, dr)), zq], -1)
            o, lse = flash_partial(qf, kf, v, scale=scale, causal=True,
                                   name="mla_prefill_chunk")
            if aux["prefix_nbk"]:
                # the earlier chunks, expanded too, but a key tile and a
                # head at a time inside the kernel, from the latent rows
                # as they lie
                tbl, n_hist = args[3:5]
                o_h, lse_h = latent_history_partial(
                    qf, pool[tbl].reshape(1, -1, c.latent_width),
                    p["w_uk"].astype(dt), p["w_uv"].astype(dt), scale=scale,
                    kv_len=n_hist[None], name="mla_prefill_history")
                o = combine_partials(o, lse, o_h, lse_h)
            o = jnp.swapaxes(o, 0, 1)                           # [S, H, dv]
            if gated:
                o = o * gate[..., None]
            return o.reshape(S, H * dv) @ p["w_o"].astype(dt)

        rows = (q_nope, q_rope, row)
        if aux["prefix_nbk"]:
            rows += (aux["ctx_tbl"], aux["hist_len"].astype(jnp.int32))
        if gated:
            rows += (jax.nn.sigmoid(hn @ p["w_g"].astype(dt)),)
        return x + jax.lax.map(one_row, rows), {"c": row}

    def piece_flash_tiles(self, S: int, hist: int, pnbk: int, bs: int):
        """The grid steps of a piece's blockwise attention by kernel and
        kind, over the layers: both halves' keys are expanded a head (a
        group of one, a call's KV heads the query heads), the chunk's
        before the call and the history's a key tile at a time inside it,
        from latent rows ``pnbk`` blocks wide of which ``hist`` tokens are
        real."""
        c = self.config
        L, H = self.num_layers, c.num_heads
        dqk = c.qk_nope_head_dim + c.qk_rope_head_dim
        out = {"mla_prefill_chunk": flash_call_tiles(
            1, S, S, dqk + -dqk % 128, c.v_head_dim, causal=True)}
        if pnbk:
            bq, bkv = latent_history_tiles(S, pnbk * bs)
            out["mla_prefill_history"] = flash_tile_counts(
                S, pnbk * bs, hist, bq=bq, bkv=bkv)
        return {k: tuple(L * H * t for t in v) for k, v in out.items()}

    def ffn(self, params, l: int, rows, valid):
        """The row-wise half of a layer, whatever program the rows come
        from: rows [T, h] -> (rows + FFN(norm(rows)), counts or None). No
        row's result depends on another's."""
        p = params["layers"][l]
        y, counts = self._ffn(
            p, l, _rms_norm(rows, p["mlp_norm"], self.config.rms_eps), valid)
        return rows + y, counts

    def prefill_layer(self, params, l: int, x, aux, pools, opts: ServeOpts):
        """``prefill_mix`` and then ``ffn``'s steps over the piece's own
        rows, in the order that keeps the lone program's text."""
        p = params["layers"][l]
        B, S, h = x.shape
        x, ent = self.prefill_mix(params, l, x, aux, pools, opts)
        hn = _rms_norm(x, p["mlp_norm"], self.config.rms_eps)
        y, counts = self._ffn(p, l, hn.reshape(B * S, h), aux["valid"])
        if self._has_experts:
            ent["_stats"] = (counts if counts is not None
                             else jnp.zeros((5,), jnp.float32))
        return x + y.reshape(B, S, h), ent

    def pack_entries(self, new: Dict, opts: ServeOpts) -> Dict:
        """Rows stacked over the layers, as each layer's own pool."""
        return {f"{opts.prefix}c{l}": new["c"][l:l + 1]
                for l in range(self.num_layers)}

    # -- decode --------------------------------------------------------------
    def ring_init(self, N: int, S: int, opts: ServeOpts) -> Dict:
        c = self.config
        ring = {"c": jnp.zeros((c.num_layers, N, S, c.latent_width),
                               c.dtype)}
        if self._has_experts:
            ring["_stats"] = jnp.zeros((5,), jnp.float32)
        return ring

    def decode_begin(self, params, pools, block_table, lens0, active,
                     n_steps: int, opts: ServeOpts):
        c = self.config
        N, MB = block_table.shape
        inv, mscale = yarn_inv_freq(c)
        aux = {"inv": inv, "mscale": mscale, "block_table": block_table}
        if opts.ragged:
            aux["walk_lens"] = jnp.where(active, lens0.astype(jnp.int32), 0)
        else:
            # off a TPU: one dense gather of every slot's frozen prefix
            aux["cd"] = [pools[f"{opts.prefix}c{l}"][0][block_table].reshape(
                N, -1, c.latent_width) for l in range(c.num_layers)]
            aux["pre_mask"] = (jnp.arange(aux["cd"][0].shape[1])[None, :]
                               < lens0[:, None])[:, None, :]      # [N,1,P]
        return aux

    def decode_step_begin(self, aux, lens, t, S: int):
        return {"ang": lens.astype(jnp.float32)[:, None] * aux["inv"][None],
                "ring_mask": (jnp.arange(S) <= t)[None, None, :]}

    def decode_mix(self, params, l: int, x, aux, step, ring, t, pools, act,
                   opts: ServeOpts):
        """The token-mixing half of a decode step's layer: x [N, 1, h] ->
        (x + absorbed latent attention [N, h], the ring with this step's
        latent row)."""
        c, dt = self.config, self.dtype
        p = params["layers"][l]
        N = x.shape[0]
        r, scale = c.kv_lora_rank, c.softmax_scale
        hn = _rms_norm(x[:, 0], p["attn_norm"], c.rms_eps)
        q_nope, q_rope, row = self._project(p, hn, step["ang"],
                                            aux["mscale"])
        rc = jax.lax.dynamic_update_slice(ring["c"], row[None, :, None],
                                          (l, 0, t, 0))
        q_abs = self._absorb(p, q_nope, q_rope)               # [N, H, W]
        s_rng = jnp.einsum("nhw,nsw->nhs", q_abs, rc[l],
                           preferred_element_type=jnp.float32) * scale
        s_rng = jnp.where(step["ring_mask"], s_rng, -1e30)
        v_rng = rc[l][..., :r]
        if opts.ragged:
            # the flash-decoding combine of the walk's partials over the
            # pool with the in-call ring (which always holds the step's
            # own token: l_tot >= 1)
            acc_p, m_p, l_p = latent_decode_partial(
                q_abs, pools[f"{opts.prefix}c{l}"], aux["block_table"],
                aux["walk_lens"], layer=0, v_cols=r, sm_scale=scale)
            m_tot = jnp.maximum(m_p, jnp.max(s_rng, axis=-1))
            corr = jnp.exp(m_p - m_tot)
            p_rng = jnp.exp(s_rng - m_tot[..., None])
            l_tot = l_p * corr + jnp.sum(p_rng, axis=-1)
            acc = acc_p * corr[..., None] + jnp.einsum(
                "nhs,nsc->nhc", p_rng, v_rng,
                preferred_element_type=jnp.float32)
            o_lat = (acc / l_tot[..., None]).astype(dt)
        else:
            cd = aux["cd"][l]                                  # [N, P, W]
            P = cd.shape[1]
            s_pre = jnp.einsum("nhw,npw->nhp", q_abs, cd,
                               preferred_element_type=jnp.float32) * scale
            s_pre = jnp.where(aux["pre_mask"], s_pre, -1e30)
            probs = jax.nn.softmax(
                jnp.concatenate([s_pre, s_rng], axis=-1), axis=-1)
            o_lat = (jnp.einsum("nhp,npc->nhc", probs[..., :P].astype(dt),
                                cd[..., :r])
                     + jnp.einsum("nhs,nsc->nhc", probs[..., P:].astype(dt),
                                  v_rng)).astype(dt)
        o = jnp.einsum("nhc,hcd->nhd", o_lat, p["w_uv"].astype(dt))
        if "w_g" in p:
            o = o * jax.nn.sigmoid(hn @ p["w_g"].astype(dt))[..., None]
        return (x[:, 0] + o.reshape(N, -1) @ p["w_o"].astype(dt),
                dict(ring, c=rc))

    def decode_layer(self, params, l: int, x, aux, step, ring, t, pools,
                     act, opts: ServeOpts):
        """``decode_mix`` and then ``ffn``'s steps over the slots' rows."""
        p = params["layers"][l]
        xa, ring = self.decode_mix(params, l, x, aux, step, ring, t, pools,
                                   act, opts)
        hn = _rms_norm(xa, p["mlp_norm"], self.config.rms_eps)
        y, counts = self._ffn(p, l, hn, act)
        if counts is not None:
            ring["_stats"] = ring["_stats"] + counts
        return (xa + y)[:, None], ring
