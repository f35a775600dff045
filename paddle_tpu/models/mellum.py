"""Mellum2-12B-A2.5B (``mellum``) behind the serving engine's model
interface: grouped-query attention whose layers come in two kinds, three
``sliding_attention`` layers (a token sees itself and the ``W - 1`` before
it) to one ``full_attention`` layer, a sparse-expert FFN in every layer
under a softmax router (top-8 of 64, renormalised, all experts held), an
untied head.

The equations are the published configuration's
(``benchmark/reference/mellum_f32.py`` states them in float32 and imports
nothing from here): pre-norm residual layers ``x <- x + Attn_l(rms(x))``,
``x <- x + MoE_l(rms(x))``; a per-head RMS norm of q and k before rope;
plain rope on the window layers and YaRN on the full ones (its attention
factor on cos and sin, ``models/rope.py``); a final norm and the head.

What the engine sees (the interface of ``models/llama_served.py``; the
cache half of it, which Trinity shares, is ``models/window_kv.py``
``TwoKindCache``: this file keeps the projections, both kinds' rope, the
router and the kernels' names):

- **two kinds of per-token cache entry in one manager.** A layer's pool
  row holds all of a token's heads, its 4 value heads and then its 4 key
  heads side by side: 2 x 4 x 128 = 1024 lanes, the bytes of the unpadded
  K and V rows (2,048 B a token-layer), one pool a layer, the layout
  LFM2's attention layers found (``docs/served_models.md``: over rows of
  ``[4, 128]``, the dense family's layout at 4 KV heads, the prefill's
  scatter re-lays a whole pool out and back, 2 x 0.4 GB a full layer a
  piece, read in a described-topology compile). A full layer's pool
  (``kvf<a>`` [1, NB, bs, 1024]) is indexed by the engine's block table: a
  slot holds its whole context there. A window layer's (``kvw<a>`` [1,
  NB_window, bs, 1024]) is an entry of the WINDOW kind
  (``window_entries``, ``window``): the engine keeps a second ledger for
  those (``serving/window_ledger.py``) whose table is a ring of ``ceil(W /
  bs) + 1`` blocks a slot, written again in place as the context moves
  on, so a window layer caches a slot's last ``W`` tokens whatever the
  context.
- **decode**: ``kernels.paged_attention.flat_decode_partial`` (the latent
  walk, as LFM2 runs it) for both kinds: a full layer walks the slot's
  table from block 0 to its length (``mellum_walk_full`` in a trace), a
  window layer walks the ring from the window's edge, ``[len - W + 1,
  len)``, with a start beside the length (``mellum_walk_window``): at most
  ``ceil(W / bs) + 1`` blocks whatever the context. Off a TPU both are
  gathered dense and masked by position.
- **prefill**: ``flash_partial`` over the piece's own tokens, causal
  (``mellum_prefill_chunk``; banded as well where a bucket is longer than
  the window), and over the gathered history: all of it for a full layer
  (``mellum_history_full``), the ring's blocks that hold the last ``W -
  1`` tokens for a window layer, under a lower bound on the key a query
  row may see (``mellum_history_window``); one softmax joins the two
  (``combine_partials``).
- **the expert layer**: softmax of the router's logits in float32, top-8
  renormalised (``kernels.moe_dispatch.routing_from_logits``), the pairs
  through ``held_expert_ffn`` with all 64 held (``first=0``). Pad rows of
  a piece and idle slots are not routed.

Departures from the published layout: gate and up of the experts are
stored side by side (``e_gu``, ``from_published``), which permutes nothing.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..kernels.moe_dispatch import held_expert_ffn, routing_from_logits
from .llama import _rms_norm
from .llama_served import ServeOpts
from .rope import rope_half, yarn_frequencies
from .window_kv import TwoKindCache

__all__ = ["MellumConfig", "MellumServed", "from_published",
           "PUBLISHED_LAYER_TYPES"]

PUBLISHED_LAYER_TYPES = ("sliding_attention", "sliding_attention",
                         "sliding_attention", "full_attention") * 7


@dataclasses.dataclass(frozen=True)
class MellumConfig:
    vocab_size: int = 98304
    hidden_size: int = 2304
    moe_intermediate_size: int = 896
    layer_types: Tuple[str, ...] = PUBLISHED_LAYER_TYPES
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    num_experts: int = 64
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    sliding_window: int = 1024
    rope_theta: float = 500000.0
    # YaRN, on the full-attention layers only
    rope_factor: float = 16.0
    rope_original_max: int = 8192
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_attention_factor: float = 1.2772588722239782
    rms_eps: float = 1e-6
    max_seq_len: int = 131072
    dtype: Any = jnp.bfloat16
    remat: bool = False                   # accepted, unused: serving only

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    def served_model(self):
        return MellumServed(self)


def from_published(layer: Dict, c: MellumConfig) -> Dict:
    """One layer's leaves in the published layout (gate and up of the
    experts apart) as this program keeps them: side by side."""
    out = {k: v for k, v in layer.items() if k not in ("e_gate", "e_up")}
    out["e_gu"] = jnp.concatenate([layer["e_gate"], layer["e_up"]], -1)
    return out


class MellumServed(TwoKindCache):
    trace_name = "mellum"
    state_entries = ()       # nothing is kept per slot beside the cache
    unsupported = {
        "spec": "there is no draft of this family and spec_verify is "
                "llama's program",
        "prefix_cache": "a cached block of a window layer is gone once "
                        "the window has passed it: a block is written "
                        "again in place, so no suffix can start from it",
        "kv_swap": "the swap tier moves blocks by the full kind's ids; a "
                   "window layer's ring would have to travel with them",
        "mesh": "no sharding recipe for the two kinds' pools or the held "
                "experts",
        "kv_int8": "the walk with a start reads bf16/f32 rows; int8 pools "
                   "are refused on the chip at any head dim",
        "disagg": "the relay hands over blocks by the full kind's ids; a "
                  "window layer's ring would have to travel with them",
    }

    def __init__(self, config: MellumConfig):
        c = config
        if not c.norm_topk_prob:
            raise ValueError("the router renormalises its top-k "
                             "(routing_from_logits): norm_topk_prob false "
                             "is not written")
        self.config = c
        self.num_layers = c.num_layers
        self.vocab_size = c.vocab_size
        self.dtype = c.dtype
        self._init_kinds()

    # -- top of the model ----------------------------------------------------
    def embed(self, params, tokens):
        return params["embed"].astype(self.dtype)[tokens]

    def final_norm(self, params, x):
        return _rms_norm(x, params["final_norm"], self.config.rms_eps)

    def head(self, params, x):
        """Untied: logits = x . W_head^T, the head [vocab, h] contracted
        on its minor dim where it lies."""
        return jax.lax.dot_general(
            x, params["head"].astype(self.dtype),
            (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    def decode_head(self, params):
        return None

    def decode_logits(self, params, head_w, xf):
        return self.head(params, xf)

    # -- shared pieces -------------------------------------------------------
    def _freqs(self):
        """(inverse frequencies, factor on cos and sin) of each kind."""
        c = self.config
        d = c.head_dim
        plain = c.rope_theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        yarn = yarn_frequencies(d, c.rope_theta, c.rope_factor,
                                c.rope_original_max, c.rope_beta_fast,
                                c.rope_beta_slow)
        return {"w": (plain, 1.0), "f": (yarn, c.rope_attention_factor)}

    def _qkv(self, hn, p, ang):
        """Normed, roped queries [..., H, D] and keys [..., Hkv, D], and
        values; ``ang`` the kind's (angles, factor on cos and sin). The
        barrier holds the three products [..., out] in the compiled
        program (``LlamaServed._qkv``, PR 29)."""
        ang, mscale = ang
        c, dt = self.config, self.dtype
        q, k, v = jax.lax.optimization_barrier(
            tuple(hn @ p[w].astype(dt) for w in ("wq", "wk", "wv")))
        D = c.head_dim
        q = q.reshape(hn.shape[:-1] + (c.num_heads, D))
        k = k.reshape(hn.shape[:-1] + (c.num_kv_heads, D))
        v = v.reshape(hn.shape[:-1] + (c.num_kv_heads, D))
        ang = ang[..., None, :]                   # over the head axis
        q = rope_half(_rms_norm(q, p["q_norm"], c.rms_eps), ang, mscale)
        k = rope_half(_rms_norm(k, p["k_norm"], c.rms_eps), ang, mscale)
        return q, k, v

    def _attn_out(self, p, o, hn):
        return o @ p["wo"].astype(self.dtype)

    def _ffn(self, p, x, valid):
        """x [T, h] -> (y, counts): every layer is sparse."""
        c, dt = self.config, self.dtype
        logits = jnp.dot(x, p["router"].astype(dt),
                         preferred_element_type=jnp.float32)
        r = routing_from_logits(logits, c.num_experts_per_tok)
        return held_expert_ffn(x, r.weights, r.idx.astype(jnp.int32), valid,
                               p["e_gu"], p["e_down"], 0)

    # -- prefill -------------------------------------------------------------
    def prefill_mix(self, params, l: int, x, aux, pools, opts: ServeOpts):
        """The token-mixing half of a piece's layer: x [B, S, h] -> (x +
        attention of its kind, the layer's new entries)."""
        p = params["layers"][l]
        kind, a = self._kind(l)
        hn = _rms_norm(x, p["attn_norm"], self.config.rms_eps)
        y, ent = self._prefill_attention(p, kind, a, hn, aux, pools, opts)
        return x + y, ent

    def ffn(self, params, l: int, rows, valid):
        """The row-wise half of a layer, whatever program the rows come
        from: rows [T, h] -> (rows + experts(norm(rows)), counts). No
        row's result depends on another's."""
        p = params["layers"][l]
        y, counts = self._ffn(
            p, _rms_norm(rows, p["ffn_norm"], self.config.rms_eps), valid)
        return rows + y, counts

    def prefill_layer(self, params, l: int, x, aux, pools, opts: ServeOpts):
        """``prefill_mix`` and then ``ffn``'s steps over the piece's own
        rows, in the order that keeps the lone program's text."""
        p = params["layers"][l]
        B, S, h = x.shape
        x, ent = self.prefill_mix(params, l, x, aux, pools, opts)
        hn = _rms_norm(x, p["ffn_norm"], self.config.rms_eps)
        y, ent["_stats"] = self._ffn(p, hn.reshape(B * S, h), aux["valid"])
        return x + y.reshape(B, S, h), ent

    # -- decode --------------------------------------------------------------
    def decode_mix(self, params, l: int, x, aux, step, ring, t, pools, act,
                   opts: ServeOpts):
        """The token-mixing half of a decode step's layer: x [N, 1, h] ->
        (x + attention of its kind [N, h], the ring with this step's
        entry)."""
        p = params["layers"][l]
        kind, a = self._kind(l)
        hn = _rms_norm(x[:, 0], p["attn_norm"], self.config.rms_eps)
        y, ring = self._decode_attention(p, kind, a, hn, aux, step, ring, t,
                                         pools, opts)
        return x[:, 0] + y, ring

    def decode_layer(self, params, l: int, x, aux, step, ring, t, pools,
                     act, opts: ServeOpts):
        """``decode_mix`` and then ``ffn``'s steps over the slots' rows."""
        p = params["layers"][l]
        xa, ring = self.decode_mix(params, l, x, aux, step, ring, t, pools,
                                   act, opts)
        hn = _rms_norm(xa, p["ffn_norm"], self.config.rms_eps)
        y, counts = self._ffn(p, hn, act)
        ring = dict(ring, _stats=ring["_stats"] + counts)
        return (xa + y)[:, None], ring
