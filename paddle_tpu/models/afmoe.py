"""Trinity-Large-Preview (``afmoe``) behind the serving engine's model
interface: grouped-query attention whose output is GATED at full width
before its projection, window layers beside full ones with rope on the
window layers ONLY, four norms a layer, leading dense layers and then
expert layers that hold a SHARE of the sigmoid-routed experts beside one
shared expert, an untied head.

The equations (``benchmark/reference/afmoe_f32.py`` states them in float32
and imports nothing from here; what no key of the published configuration
settles is listed under ``assumed`` in the benchmark's configuration file)::

    x0 = E[t] * sqrt(h)                                     (mup_enabled)
    a  = rms(x; attn_norm)
    q  = rms_head(a Wq; q_norm), k = rms_head(a Wk; k_norm), v = a Wv
    sliding layer: q, k <- rope(q, k; position)       full layer: no rope
    o  = softmax(q k^T / sqrt(D) + mask) v    mask: j <= i, and on a sliding
                                              layer also i - j < W
    x <- x + rms((o * sigmoid(a Wg)) Wo; attn_post_norm)
    b  = rms(x; ffn_norm)
    dense layer:  m = SwiGLU(b)
    expert layer: s = sigmoid(b Wr) in float32 over all experts
                  S = top-k of (s + bias), ties to the lower index
                  w_e = route_scale * s_e / (sum_{S} s + 1e-20)
                  m = Shared(b) + sum_{e in S, e held} w_e Expert_e(b)
    x <- x + rms(m; ffn_post_norm)
    logits = rms(x; final_norm) Head^T

What the engine sees (the interface of ``models/llama_served.py``):

- **two kinds of per-token cache entry in one manager**, as Mellum2's:
  ``models/window_kv.py`` holds what both models share (the ``[V | K]`` row,
  here 2 x 8 x 128 = 2,048 lanes; a pool a layer; the window kind's ring
  and history; both kinds' walks and flash calls). This model's part is
  ``_qkv`` (a full layer's q and k are NOT turned: that kind has no entry
  in ``_freqs`` and no multiply is spent on an angle of zero), ``_attn_out``
  (the gate, a fifth projection as wide as ``Wq``, then ``Wo``) and the
  kernels' names in a trace (``afmoe_walk_full``, ``afmoe_walk_window``,
  ``afmoe_prefill_chunk``, ``afmoe_history_full``,
  ``afmoe_history_window``).
- **the chip's share of an expert layer**: ``held_first`` / ``held_experts``
  say which routed experts this engine holds. The router scores all
  ``num_experts`` and keeps its top-k
  (``kernels.moe_dispatch.sigmoid_bias_routing``: the bias enters the
  selection and no weight), ``held_expert_ffn`` computes the pairs that
  fell on held experts, the shared expert is computed whole; pairs routed
  elsewhere are other chips' work and nothing stands in for them. Pad rows
  of a piece and idle slots are not routed.
- ``prefill_mix`` / ``decode_mix`` / ``ffn``: a layer's two halves, so that
  a step's last piece carries the decode rows (``docs/served_models.md``).

Departures from the published layout: gate and up of the held experts are
stored side by side (``e_gu``, ``from_published``), which permutes nothing.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..kernels.moe_dispatch import held_expert_ffn, sigmoid_bias_routing
from .deepseek_v2 import _swiglu
from .llama import _rms_norm
from .llama_served import ServeOpts
from .rope import rope_half
from .window_kv import TwoKindCache

__all__ = ["AfmoeConfig", "AfmoeServed", "from_published",
           "PUBLISHED_LAYER_TYPES"]

PUBLISHED_LAYER_TYPES = ("sliding_attention", "sliding_attention",
                         "sliding_attention", "full_attention") * 15


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 200192
    hidden_size: int = 3072
    intermediate_size: int = 12288        # the leading dense layers' FFN
    moe_intermediate_size: int = 3072     # one routed expert's, the shared's
    layer_types: Tuple[str, ...] = PUBLISHED_LAYER_TYPES
    num_dense_layers: int = 6             # of the layers run, the first
    num_heads: int = 48
    num_kv_heads: int = 8
    head_dim: int = 128
    num_experts: int = 256                # the router's width
    num_experts_per_tok: int = 4
    num_shared_experts: int = 1
    route_norm: bool = True
    route_scale: float = 2.448
    # the experts this chip holds: its share of an expert-parallel layer
    held_first: int = 0
    held_experts: int = 256
    sliding_window: int = 4096
    rope_theta: float = 10000.0
    mup_enabled: bool = True              # the embedding times sqrt(h)
    rms_eps: float = 1e-5
    max_seq_len: int = 262144
    dtype: Any = jnp.bfloat16
    remat: bool = False                   # accepted, unused: serving only

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    def is_moe_layer(self, l: int) -> bool:
        return l >= self.num_dense_layers

    def served_model(self):
        return AfmoeServed(self)


def from_published(layer: Dict, c: AfmoeConfig) -> Dict:
    """One layer's leaves in the published layout (gate and up of the
    held experts apart) as this program keeps them: side by side."""
    if "e_gate" not in layer:
        return dict(layer)
    out = {k: v for k, v in layer.items() if k not in ("e_gate", "e_up")}
    out["e_gu"] = jnp.concatenate([layer["e_gate"], layer["e_up"]], -1)
    return out


class AfmoeServed(TwoKindCache):
    trace_name = "afmoe"
    state_entries = ()       # nothing is kept per slot beside the cache
    unsupported = {
        "spec": "there is no draft of this family and spec_verify is "
                "llama's program",
        "prefix_cache": "a cached block of a window layer is gone once "
                        "the window has passed it: a block is written "
                        "again in place, so no suffix can start from it",
        "kv_swap": "the swap tier moves blocks by the full kind's ids; a "
                   "window layer's ring would have to travel with them",
        "mesh": "no sharding recipe for the two kinds' pools or the "
                "expert share: the deployment's exchange is not written",
        "kv_int8": "the walk with a start reads bf16/f32 rows; int8 pools "
                   "are refused on the chip at any head dim",
        "disagg": "the relay hands over blocks by the full kind's ids; a "
                  "window layer's ring would have to travel with them",
    }

    def __init__(self, config: AfmoeConfig):
        c = config
        if c.num_shared_experts != 1:
            raise ValueError(f"{c.num_shared_experts} shared experts: the "
                             "layer adds ONE shared expert of the routed "
                             "experts' width")
        if not 0 <= c.held_first <= c.held_first + c.held_experts \
                <= c.num_experts or c.held_experts < 1:
            raise ValueError(
                f"held experts [{c.held_first}, {c.held_first}+"
                f"{c.held_experts}) lie outside the router's "
                f"{c.num_experts}")
        if not 0 <= c.num_dense_layers < c.num_layers:
            raise ValueError(f"{c.num_dense_layers} dense layers of "
                             f"{c.num_layers}: an expert layer is expected "
                             "(the counts ride its spans)")
        self.config = c
        self.num_layers = c.num_layers
        self.vocab_size = c.vocab_size
        self.dtype = c.dtype
        self._init_kinds()

    # -- top of the model ----------------------------------------------------
    def embed(self, params, tokens):
        c = self.config
        x = params["embed"].astype(self.dtype)[tokens]
        if c.mup_enabled:
            x = x * jnp.asarray(math.sqrt(c.hidden_size), self.dtype)
        return x

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

    # -- this model's part of the attention ----------------------------------
    def _freqs(self):
        """The window kind's (inverse frequencies, factor on cos and sin).
        The full kind carries no position: it has no entry."""
        c = self.config
        d = c.head_dim
        plain = c.rope_theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        return {"w": (plain, 1.0)}

    def _qkv(self, hn, p, ang):
        """Normed queries [..., H, D] and keys [..., Hkv, D], turned where
        the layer's kind has angles (``ang`` None: a full layer), and
        values. The barrier holds the three products [..., out] in the
        compiled program (``LlamaServed._qkv``, PR 29)."""
        c, dt = self.config, self.dtype
        q, k, v = jax.lax.optimization_barrier(
            tuple(hn @ p[w].astype(dt) for w in ("wq", "wk", "wv")))
        D = c.head_dim
        q = _rms_norm(q.reshape(hn.shape[:-1] + (c.num_heads, D)),
                      p["q_norm"], c.rms_eps)
        k = _rms_norm(k.reshape(hn.shape[:-1] + (c.num_kv_heads, D)),
                      p["k_norm"], c.rms_eps)
        v = v.reshape(hn.shape[:-1] + (c.num_kv_heads, D))
        if ang is not None:
            angles, mscale = ang
            angles = angles[..., None, :]         # over the head axis
            q, k = rope_half(q, angles, mscale), rope_half(k, angles, mscale)
        return q, k, v

    def _attn_out(self, p, o, hn):
        """The heads' outputs [..., H * D] times the sigmoid of the gate's
        projection of the layer's normed input, elementwise over all the
        columns, then the output projection."""
        dt = self.dtype
        gate = jax.nn.sigmoid(hn @ p["wg"].astype(dt))
        return (o * gate) @ p["wo"].astype(dt)

    def _ffn(self, p, l: int, x, valid):
        """x [T, h] -> (y, counts or None)."""
        c, dt = self.config, self.dtype
        if not c.is_moe_layer(l):
            return _swiglu(x, p["w_gate"], p["w_up"], p["w_down"], dt), None
        scores = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), p["router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        gates, idx = sigmoid_bias_routing(
            scores, p["expert_bias"].astype(jnp.float32),
            c.num_experts_per_tok, c.route_scale, c.route_norm, eps=1e-20)
        routed, counts = held_expert_ffn(x, gates, idx, valid, p["e_gu"],
                                         p["e_down"], c.held_first)
        return routed + _swiglu(x, p["s_gate"], p["s_up"], p["s_down"],
                                dt), counts

    # -- a layer's two halves ------------------------------------------------
    def prefill_mix(self, params, l: int, x, aux, pools, opts: ServeOpts):
        """The token-mixing half of a piece's layer: x [B, S, h] -> (x +
        the normed attention of its kind, the layer's new entries)."""
        p = params["layers"][l]
        eps = self.config.rms_eps
        kind, a = self._kind(l)
        hn = _rms_norm(x, p["attn_norm"], eps)
        y, ent = self._prefill_attention(p, kind, a, hn, aux, pools, opts)
        return x + _rms_norm(y, p["attn_post_norm"], eps), ent

    def decode_mix(self, params, l: int, x, aux, step, ring, t, pools, act,
                   opts: ServeOpts):
        """The same half of one decode step: x [N, 1, h] -> (x [N, h], the
        ring with this step's entry)."""
        p = params["layers"][l]
        eps = self.config.rms_eps
        kind, a = self._kind(l)
        hn = _rms_norm(x[:, 0], p["attn_norm"], eps)
        y, ring = self._decode_attention(p, kind, a, hn, aux, step, ring, t,
                                         pools, opts)
        return x[:, 0] + _rms_norm(y, p["attn_post_norm"], eps), ring

    def ffn(self, params, l: int, rows, valid):
        """The row-wise half of a layer, whatever program the rows come
        from: rows [T, h] -> (rows + norm(FFN(norm(rows))), counts or
        None). No row's result depends on another's."""
        p = params["layers"][l]
        eps = self.config.rms_eps
        y, counts = self._ffn(p, l, _rms_norm(rows, p["ffn_norm"], eps),
                              valid)
        return rows + _rms_norm(y, p["ffn_post_norm"], eps), counts

    def prefill_layer(self, params, l: int, x, aux, pools, opts: ServeOpts):
        """``prefill_mix`` and then ``ffn`` over the piece's own rows."""
        B, S, h = x.shape
        x, ent = self.prefill_mix(params, l, x, aux, pools, opts)
        rows, counts = self.ffn(params, l, x.reshape(B * S, h), aux["valid"])
        ent["_stats"] = (counts if counts is not None
                         else jnp.zeros((5,), jnp.float32))
        return rows.reshape(B, S, h), ent

    def decode_layer(self, params, l: int, x, aux, step, ring, t, pools,
                     act, opts: ServeOpts):
        """``decode_mix`` and then ``ffn`` over the slots' rows."""
        xa, ring = self.decode_mix(params, l, x, aux, step, ring, t, pools,
                                   act, opts)
        rows, counts = self.ffn(params, l, xa, act)
        if counts is not None:
            ring = dict(ring, _stats=ring["_stats"] + counts)
        return rows[:, None], ring
