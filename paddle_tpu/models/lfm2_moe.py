"""LFM2-8B-A1B (``lfm2_moe``) behind the serving engine's model interface:
gated short convolutions whose whole memory is a per-slot STATE beside the
paged cache, grouped-query attention at head dim 64 in one layer of four,
and sparse-expert layers under a sigmoid router with a selection bias, all
experts held.

The equations are the published ones (``benchmark/reference/lfm2_moe_f32.py``
states them in float32 and imports nothing from here): pre-norm residual
layers ``x <- x + Op_l(rms(x))``, ``x <- x + FFN_l(rms(x))`` with ``Op_l``
by ``layer_types``; a final norm and a head tied to the embedding.

What the engine sees (the interface of ``models/llama_served.py``):

- **two kinds of cache entry, and a layer writes one of them.** An
  attention layer leaves one per-token row in blocks (``kv0``, ``kv1``,
  ... for the ATTENTION layers only, each [1, NB, bs, 2 * Hkv * 64]: a
  token's eight value heads and then its eight key heads side by side in
  one row of 1024 lanes — the bytes of the unpadded [Hkv, 64] rows, 2,048
  B a token-layer — and a pool of its own a layer, because over a pool
  with a leading layer axis XLA re-lays the whole pool out and back around
  the decode step's scatter, as DeepSeek-V2's latent pools found). A
  convolution layer leaves
  nothing per token: its memory is the last two inputs of the convolution,
  ``(u_{t-2}, u_{t-1})``, a per-SLOT entry a convolution layer (``s0``,
  ``s1``, ... of shape [1, slots + 1, 2, h]: ``state_entries``,
  ``make_state``; the last row is a trash row; an entry of its own a
  layer, so that a decode step rewrites one layer's 0.5 MB and not the
  stack's 6 MB twelve times). The
  engine zeroes it where a row starts its context, hands a continuing
  piece what its predecessor left, carries it through the decode scan
  beside the ring and writes it back once a call
  (``docs/served_models.md``, "Per-slot state beside the cache").
- **head dim 64 on the chip's kernels**: the block DMA of the walk cannot
  slice rows of 64, and rows of two heads ([Hkv / 2, 128]) are re-laid
  out whole around the prefill's scatter, so a pool row holds ALL of a
  token's heads, values then keys (1024 lanes). Decode: ``kernels.
  paged_attention.flat_decode_partial`` (``lfm2_ragged_walk`` in a trace),
  which is the latent walk as DeepSeek-V2 runs it — a query sits in the
  columns of its own head's key with zeros in the others, one dot of all
  query heads against a chunk as it lies, its head's columns taken out of
  the weighted sum of the value columns; the MXU contracts 1024 columns
  for 64 and is idle all the same, the bytes moved are the mathematics'.
  Prefill: ``flash_partial``
  at heads of 64 as they are (a block's minor dim may be the array's own)
  over the piece's own tokens, causal (``lfm2_prefill_chunk``), and over
  the gathered rows of earlier pieces with the history's length a runtime
  operand (``lfm2_prefill_history``), joined by one softmax
  (``combine_partials``).
- **the expert layer**: sigmoids of the router's logits in float32, a
  per-expert bias that enters the SELECTION and no weight, top-4 of 32
  renormalised (``kernels.moe_dispatch.sigmoid_bias_routing``), the pairs
  through ``held_expert_ffn`` with all 32 held (``first=0``). Pad rows of
  a piece and idle slots are not routed.
- the convolution is three shifted multiply-adds under
  ``jax.named_scope("lfm2.short_conv")``; what it costs is ``w_in`` and
  ``w_out``.

Departures from the published modelling code: none in the layout (rope is
half-split there too); gate and up of the experts are stored side by side
(``e_gu``, ``from_published``), which permutes nothing.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..kernels.moe_dispatch import held_expert_ffn, sigmoid_bias_routing
from ..kernels.paged_attention import ragged_tpu_refusal
from .flat_kv_attention import (decode_attention, pack_rows,
                                prefill_attention, prefill_attention_tiles)
from .deepseek_v2 import _rope, _swiglu
from .llama import _rms_norm
from .llama_served import ServeOpts

__all__ = ["Lfm2MoeConfig", "Lfm2MoeServed", "from_published",
           "PUBLISHED_LAYER_TYPES"]

LANES = 128
PUBLISHED_LAYER_TYPES = (
    "conv", "conv", "full_attention", "conv", "conv", "conv",
    "full_attention", "conv", "conv", "conv", "full_attention", "conv",
    "conv", "conv", "full_attention", "conv", "conv", "conv",
    "full_attention", "conv", "conv", "full_attention", "conv", "conv")


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168         # the leading dense layers' FFN
    moe_intermediate_size: int = 1792     # one expert's FFN
    layer_types: Tuple[str, ...] = PUBLISHED_LAYER_TYPES
    num_heads: int = 32
    num_kv_heads: int = 8
    num_dense_layers: int = 2
    num_experts: int = 32
    num_experts_per_tok: int = 4
    conv_L_cache: int = 3
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 128000
    dtype: Any = jnp.bfloat16
    remat: bool = False                   # accepted, unused: serving only

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    def is_moe_layer(self, l: int) -> bool:
        return l >= self.num_dense_layers

    def served_model(self):
        return Lfm2MoeServed(self)


def from_published(layer: Dict, c: Lfm2MoeConfig) -> Dict:
    """One layer's leaves in the published layout (gate and up of the
    experts apart) as this program keeps them: the experts' gate and up
    side by side. Nothing is permuted."""
    out = {k: v for k, v in layer.items() if k not in ("e_gate", "e_up")}
    if "e_gate" in layer:
        out["e_gu"] = jnp.concatenate([layer["e_gate"], layer["e_up"]], -1)
    return out


class Lfm2MoeServed:
    cache_kind = "kv"
    unsupported = {
        "spec": "there is no draft of this family and spec_verify is "
                "llama's program",
        "prefix_cache": "a cached block's reuse needs the convolutions' "
                        "state at the block's boundary, which is kept per "
                        "slot and not per block: no snapshot exists to "
                        "start a suffix from",
        "kv_swap": "the swap tier moves blocks; a swapped-out request's "
                   "per-slot state would have to be snapshotted with them",
        "mesh": "no sharding recipe for the per-slot state, the packed "
                "pools or the held experts",
        "kv_int8": "the packed walk reads bf16/f32 rows; int8 pools are "
                   "refused on the chip at any head dim",
        "disagg": "the relay hands over blocks; the per-slot state would "
                  "have to travel with them",
    }

    def __init__(self, config: Lfm2MoeConfig):
        c = config
        if c.conv_L_cache != 3:
            raise ValueError("the short convolution is written for "
                             "conv_L_cache 3: a state of two inputs")
        if c.hidden_size % c.num_heads or c.num_heads % c.num_kv_heads \
                or (c.num_kv_heads * c.head_dim) % LANES:
            raise ValueError(
                f"{c.num_kv_heads} KV heads of {c.head_dim} do not fill "
                f"rows of a multiple of {LANES} lanes")
        bad = set(c.layer_types) - {"conv", "full_attention"}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        self.config = c
        self.num_layers = c.num_layers
        self.vocab_size = c.vocab_size
        self.dtype = c.dtype
        # a layer's index among the layers of its own kind: which plane of
        # the pools, or of the state, it writes
        self._attn = [l for l, t in enumerate(c.layer_types)
                      if t == "full_attention"]
        self._conv = [l for l, t in enumerate(c.layer_types) if t == "conv"]
        # the per-slot entries (``make_state``): what each convolution
        # layer remembers
        self.state_entries = tuple(f"s{i}" for i in range(len(self._conv)))
        self._has_experts = c.num_layers > c.num_dense_layers

    # -- the cache and the state ---------------------------------------------
    def make_pools(self, nb: int, bs: int, kv_int8: bool = False,
                   prefix: str = "") -> Dict:
        c = self.config
        shape = (1, nb, bs, 2 * c.num_kv_heads * c.head_dim)     # [V | K]
        return {f"{prefix}kv{a}": jnp.zeros(shape, c.dtype)
                for a in range(len(self._attn))}

    def make_state(self, slots: int) -> Dict:
        """The per-slot entries, zeroed: ``s<i>`` [1, slots + 1, 2, h]
        holds ``(u_{t-2}, u_{t-1})`` of the i-th convolution layer for
        every slot; the last row takes the writes of rows with no slot."""
        c = self.config
        return {n: jnp.zeros((1, slots + 1, 2, c.hidden_size), c.dtype)
                for n in self.state_entries}

    def ragged_refusal(self, kv_int8: bool):
        c = self.config                              # rows of 1024 lanes
        return ragged_tpu_refusal(2 * c.num_kv_heads * c.head_dim, kv_int8)

    @staticmethod
    def history_blocks(hist_blocks: int, mb: int) -> int:
        """Full width or none: the history kernel takes the row's length
        as a runtime operand and skips the tiles past it, so a history
        costs one program shape whatever its length."""
        return mb if hist_blocks else 0

    # -- top of the model ----------------------------------------------------
    def embed(self, params, tokens):
        return params["embed"].astype(self.dtype)[tokens]

    def final_norm(self, params, x):
        return _rms_norm(x, params["final_norm"], self.config.norm_eps)

    def head(self, params, x):
        """Tied: logits = x . Embed^T, the embedding contracted on its
        minor dim where it lies (no transposed copy of 268 MB)."""
        return jax.lax.dot_general(
            x, params["embed"].astype(self.dtype),
            (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    def decode_head(self, params):
        return None

    def decode_logits(self, params, head_w, xf):
        return self.head(params, xf)

    # -- shared pieces -------------------------------------------------------
    def _freq(self):
        d = self.config.head_dim
        return self.config.rope_theta ** (
            -jnp.arange(0, d, 2, dtype=jnp.float32) / d)

    def _qkv(self, hn, p, ang):
        """Normed, roped queries [..., H, D] and keys [..., Hkv, D], and
        values. The barrier holds the three products [..., out] in the
        compiled program (``LlamaServed._qkv``, PR 29)."""
        c, dt = self.config, self.dtype
        q, k, v = jax.lax.optimization_barrier(
            tuple(hn @ p[w].astype(dt) for w in ("wq", "wk", "wv")))
        D = c.head_dim
        q = q.reshape(hn.shape[:-1] + (c.num_heads, D))
        k = k.reshape(hn.shape[:-1] + (c.num_kv_heads, D))
        v = v.reshape(hn.shape[:-1] + (c.num_kv_heads, D))
        ang = ang[..., None, :]                   # over the head axis
        q = _rope(_rms_norm(q, p["q_norm"], c.norm_eps), ang, 1.0)
        k = _rope(_rms_norm(k, p["k_norm"], c.norm_eps), ang, 1.0)
        return q, k, v

    def _conv_in(self, p, hn):
        """``[B ; C ; x~] = hn . W_in`` and ``u = B (.) x~``."""
        h = self.config.hidden_size
        bcx = jax.lax.optimization_barrier(hn @ p["w_in"].astype(self.dtype))
        return bcx[..., :h] * bcx[..., 2 * h:], bcx[..., h:2 * h]

    def _ffn(self, p, l: int, x, valid):
        """x [T, h] -> (y, counts or None)."""
        c, dt = self.config, self.dtype
        if not c.is_moe_layer(l):
            return _swiglu(x, p["w_gate"], p["w_up"], p["w_down"], dt), None
        scores = jax.nn.sigmoid(jnp.dot(
            x, p["router"].astype(dt), preferred_element_type=jnp.float32))
        bias = (p["expert_bias"].astype(jnp.float32) if c.use_expert_bias
                else jnp.zeros((c.num_experts,), jnp.float32))
        gates, idx = sigmoid_bias_routing(
            scores, bias, c.num_experts_per_tok, c.routed_scaling_factor,
            c.norm_topk_prob)
        return held_expert_ffn(x, gates, idx, valid, p["e_gu"], p["e_down"],
                               0)

    # -- prefill -------------------------------------------------------------
    def prefill_begin(self, params, pools, tokens, true_len, hist_len,
                      ctx_tbl, prefix_nbk: int, opts: ServeOpts):
        B, S = tokens.shape
        start = (jnp.zeros((B,), jnp.float32) if hist_len is None
                 else hist_len.astype(jnp.float32))
        pos = start[:, None] + jnp.arange(S, dtype=jnp.float32)[None, :]
        return {"ang": pos[:, :, None] * self._freq()[None, None, :],
                "prefix_nbk": prefix_nbk, "hist_len": hist_len,
                "ctx_tbl": ctx_tbl, "true_len": true_len,
                # pad positions of a row and pad rows are not routed
                "valid": (jnp.arange(S)[None, :]
                          < true_len[:, None]).reshape(B * S)}

    def _prefill_attention(self, p, a: int, hn, aux, pools, opts):
        """Causal attention of a piece over [history ; piece]: both parts
        blockwise at heads of 64 as they are, one softmax."""
        q, k, v = self._qkv(hn, p, aux["ang"])
        o = prefill_attention(
            q, k, v, chunk_name="lfm2_prefill_chunk",
            history=(pools[f"{opts.prefix}kv{a}"], aux["ctx_tbl"],
                     aux["hist_len"], None, "lfm2_prefill_history")
            if aux["prefix_nbk"] else None)
        return o @ p["wo"].astype(self.dtype), {"kv": pack_rows(k, v)}

    def piece_flash_tiles(self, S: int, hist: int, pnbk: int, bs: int):
        """The grid steps of a piece's blockwise attention by kind, over
        the attention layers and their KV heads: a bucket of ``S`` after
        ``hist`` cached tokens gathered ``pnbk`` blocks wide."""
        c = self.config
        n = len(self._attn) * c.num_kv_heads
        chunk, history = prefill_attention_tiles(
            S, c.num_heads, c.num_kv_heads, c.head_dim,
            history=(pnbk * bs, hist, None) if pnbk else None)
        out = {"lfm2_prefill_chunk": tuple(n * t for t in chunk)}
        if history:
            out["lfm2_prefill_history"] = tuple(n * t for t in history)
        return out

    def _prefill_conv(self, p, ci: int, hn, aux):
        """The gated short convolution of a piece from the state its
        predecessor left; the new state is the one after the piece's last
        REAL token, whatever padding follows it."""
        B, S, _ = hn.shape
        dt = self.dtype
        with jax.named_scope("lfm2.short_conv"):
            u, gate = self._conv_in(p, hn)
            ue = jnp.concatenate([aux["state"][f"s{ci}"][0].astype(dt), u], 1)
            w = p["conv_w"].astype(dt)                        # [h, 3]
            conv = (w[:, 0] * ue[:, :S] + w[:, 1] * ue[:, 1:S + 1]
                    + w[:, 2] * ue[:, 2:])
            # u_ext rows (n, n + 1) are u_{n-2}, u_{n-1} of the piece
            s_new = jax.vmap(lambda r, n: jax.lax.dynamic_slice_in_dim(
                r, n, 2, 0))(ue, aux["true_len"].astype(jnp.int32))
        return (gate * conv) @ p["w_out"].astype(dt), {f"s{ci}": s_new}

    def prefill_mix(self, params, l: int, x, aux, pools, opts: ServeOpts):
        """The token-mixing half of a piece's layer: x [B, S, h] -> (x +
        convolution or attention, the layer's new entries)."""
        c = self.config
        p = params["layers"][l]
        hn = _rms_norm(x, p["op_norm"], c.norm_eps)
        if c.layer_types[l] == "conv":
            y, ent = self._prefill_conv(p, self._conv.index(l), hn, aux)
        else:
            y, ent = self._prefill_attention(p, self._attn.index(l), hn,
                                             aux, pools, opts)
        return x + y, ent

    def ffn(self, params, l: int, rows, valid):
        """The row-wise half of a layer, whatever program the rows come
        from: rows [T, h] -> (rows + FFN(norm(rows)), counts or None). No
        row's result depends on another's."""
        p = params["layers"][l]
        y, counts = self._ffn(
            p, l, _rms_norm(rows, p["ffn_norm"], self.config.norm_eps), valid)
        return rows + y, counts

    def prefill_layer(self, params, l: int, x, aux, pools, opts: ServeOpts):
        """``prefill_mix`` and then ``ffn``'s steps over the piece's own
        rows, in the order that keeps the lone program's text."""
        p = params["layers"][l]
        B, S, h = x.shape
        x, ent = self.prefill_mix(params, l, x, aux, pools, opts)
        hn = _rms_norm(x, p["ffn_norm"], self.config.norm_eps)
        y, counts = self._ffn(p, l, hn.reshape(B * S, h), aux["valid"])
        if self._has_experts:
            ent["_stats"] = (counts if counts is not None
                             else jnp.zeros((5,), jnp.float32))
        return x + y.reshape(B, S, h), ent

    def pack_entries(self, new: Dict, opts: ServeOpts) -> Dict:
        """Rows stacked over the attention layers [La, ..., 2 * Hkv * D],
        as each layer's own pool."""
        return {f"{opts.prefix}kv{a}": new["kv"][a:a + 1]
                for a in range(len(self._attn))}

    # -- decode --------------------------------------------------------------
    def ring_init(self, N: int, S: int, opts: ServeOpts) -> Dict:
        c = self.config
        ring = {"kv": jnp.zeros((max(len(self._attn), 1), N, S,
                                  2 * c.num_kv_heads * c.head_dim), c.dtype)}
        if self._has_experts:
            ring["_stats"] = jnp.zeros((5,), jnp.float32)
        return ring

    def decode_begin(self, params, pools, block_table, lens0, active,
                     n_steps: int, opts: ServeOpts):
        c = self.config
        N, MB = block_table.shape
        aux = {"freq": self._freq(), "block_table": block_table}
        if opts.ragged:
            aux["walk_lens"] = jnp.where(active, lens0.astype(jnp.int32), 0)
        else:
            # off a TPU: one dense gather of every slot's frozen prefix
            dense = [pools[f"{opts.prefix}kv{a}"][0][block_table].reshape(
                N, -1, 2, c.num_kv_heads, c.head_dim)
                for a in range(len(self._attn))]
            aux["kd"] = [r[:, :, 1] for r in dense]
            aux["vd"] = [r[:, :, 0] for r in dense]
            aux["pre_mask"] = (jnp.arange(MB * pools[
                f"{opts.prefix}kv0"].shape[2])[None, :]
                < lens0[:, None])[:, None, None, :]
        return aux

    def decode_step_begin(self, aux, lens, t, S: int):
        return {"ang": lens.astype(jnp.float32)[:, None]
                * aux["freq"][None, :],
                "ring_mask": (jnp.arange(S) <= t)[None, None, None, :]}

    def _decode_attention(self, p, a: int, hn, aux, step, ring, t, pools,
                          opts):
        dt = self.dtype
        q, kk, vv = self._qkv(hn, p, step["ang"])
        att, rkv = decode_attention(
            q, kk, vv, ring["kv"], a, t, step["ring_mask"], dt,
            walk=(pools[f"{opts.prefix}kv{a}"], aux["block_table"],
                  aux["walk_lens"], None, "lfm2_ragged_walk")
            if opts.ragged else None,
            dense=None if opts.ragged else
            (aux["kd"][a], aux["vd"][a], aux["pre_mask"]))
        return att @ p["wo"].astype(dt), dict(ring, kv=rkv)

    def _decode_conv(self, p, ci: int, hn, ring, act):
        """One token a slot: the state moves only where the slot is active
        and not done."""
        dt = self.dtype
        with jax.named_scope("lfm2.short_conv"):
            u, gate = self._conv_in(p, hn)
            s = ring[f"s{ci}"][0]                             # [N, 2, h]
            w = p["conv_w"].astype(dt)
            conv = (w[:, 0] * s[:, 0].astype(dt) + w[:, 1] * s[:, 1].astype(dt)
                    + w[:, 2] * u)
            s_new = jnp.where(act[:, None, None],
                              jnp.stack([s[:, 1], u.astype(s.dtype)], 1), s)
        y = (gate * conv) @ p["w_out"].astype(dt)
        return y, {**ring, f"s{ci}": s_new[None]}

    def decode_mix(self, params, l: int, x, aux, step, ring, t, pools, act,
                   opts: ServeOpts):
        """The token-mixing half of a decode step's layer: x [N, 1, h] ->
        (x + convolution or attention [N, h], the ring with this step's
        entry or state)."""
        c = self.config
        p = params["layers"][l]
        hn = _rms_norm(x[:, 0], p["op_norm"], c.norm_eps)
        if c.layer_types[l] == "conv":
            y, ring = self._decode_conv(p, self._conv.index(l), hn, ring,
                                        act)
        else:
            y, ring = self._decode_attention(p, self._attn.index(l), hn, aux,
                                             step, ring, t, pools, opts)
        return x[:, 0] + y, ring

    def decode_layer(self, params, l: int, x, aux, step, ring, t, pools,
                     act, opts: ServeOpts):
        """``decode_mix`` and then ``ffn``'s steps over the slots' rows."""
        p = params["layers"][l]
        xa, ring = self.decode_mix(params, l, x, aux, step, ring, t, pools,
                                   act, opts)
        hn = _rms_norm(xa, p["ffn_norm"], self.config.norm_eps)
        y, counts = self._ffn(p, l, hn, act)
        if counts is not None:
            ring = dict(ring, _stats=ring["_stats"] + counts)
        return (xa + y)[:, None], ring
