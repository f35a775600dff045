"""Ling-3.0-flash (``bailing_hybrid``) behind the serving engine's model
interface: KDA linear-attention layers whose whole memory is a per-slot
MATRIX state, one multi-head latent attention layer in six on the paged
latent cache, and sparse-expert layers run as one chip's share of an
expert-parallel deployment under a sigmoid router with group-limited
selection.

The equations are the published ones (``benchmark/reference/
ling_hybrid_f32.py`` states them in float32 and imports nothing from here):
pre-norm residual layers ``x <- x + Mix_l(rms(x))``, ``x <- x +
FFN_l(rms(x))``; layer ``l`` of the PUBLISHED stack is an MLA layer where
``(l + 1) % layer_group_size == 0`` and a KDA layer otherwise, a dense
SwiGLU where ``l < first_k_dense_replace`` and a sparse layer otherwise. A
configuration cut in depth runs the published layers ``first_layer ..
first_layer + num_layers - 1``, so that the kinds keep their published
period.

What the engine sees (the interface of ``models/llama_served.py``):

- **one latent pool entry and two per-slot entries a KDA layer.** The MLA
  layer is ``models/deepseek_v2.py``'s, run by an inner served object at
  this model's widths (no query compression, plain rope, a head-wise
  output gate; its kernels keep their names in a trace, ``mla_latent_walk``
  / ``mla_prefill_*``): one row ``[latent 512 ; roped key 64 ; zeros]`` a
  token in ``c0``. A KDA layer leaves nothing per token. Its
  memory is ``s<i>`` [1, slots + 1, H, 128, 128] float32, the matrix a
  head (2 MB a slot a layer), and ``u<i>`` [1, slots + 1, 3, 3 H d], the
  last three inputs of the short convolutions over q, k and v (72 KB).
- **the matrix state is advanced in place** (``state_in_place``): a decode
  step reads and writes 4 MB a slot a layer, and 64 slots' 136 MB a layer
  cannot ride the decode carry and be written back whole as LFM2's 96 KB
  do. The engine hands ``decode_mix`` the pools' entry itself in the ring
  and takes back what ``kernels.kda.kda_step`` returns (``ling_kda_step``
  in a trace: the entry aliased in and out, a slot that is not ``act``
  untouched). A piece reads its slot's matrices (zero where it starts a
  context), runs ``kda_chunk`` (``ling_kda_chunk``) from them to the state
  after its last real token, and the engine writes that row once. The
  convolution inputs ride the carry as LFM2's state does.
- **the expert layer**: sigmoids of the router's float32 logits over all
  ``num_experts``, a bias that enters the SELECTION only, a group's score
  the sum of its top two, ``topk_group`` of ``n_group`` groups kept, top-k
  among them, the chosen scores renormalised over ALL the chosen and
  scaled (``kernels.moe_dispatch.sigmoid_bias_routing``); this chip
  computes the pairs that fell on the experts it holds (``held_first ..
  held_first + held_experts - 1``: several whole groups) plus the whole
  shared expert (``held_expert_ffn``). Nothing stands in for the other
  chips or the exchange.
- what it costs: q, k, v are one product ``w_qkv``, the two head-wise
  gates one product ``w_bg``; the log-decay's projection ``w_f`` is full
  rank (``no_kda_lora``).

Departures from the published layout, none in the logits
(``from_published``): q/k/v and beta/gate side by side; the rope columns
of the MLA layer's ``w_q`` and ``w_dkv`` de-interleaved as DeepSeek-V2's
are; gate and up of the experts side by side.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..kernels import kda
from ..kernels.moe_dispatch import held_expert_ffn, sigmoid_bias_routing
from . import deepseek_v2
from .deepseek_v2 import _swiglu
from .llama import _rms_norm
from .llama_served import ServeOpts

__all__ = ["LingHybridConfig", "LingHybridServed", "from_published"]


@dataclasses.dataclass(frozen=True)
class LingHybridConfig:
    vocab_size: int = 157184
    hidden_size: int = 2560
    intermediate_size: int = 6144         # the leading dense layers' FFN
    moe_intermediate_size: int = 768      # one routed expert's FFN
    shared_intermediate_size: int = 768   # the shared expert's
    num_layers: int = 42
    first_layer: int = 0                  # published index of layer 0 here
    layer_group_size: int = 6
    first_k_dense_replace: int = 2
    num_heads: int = 32
    head_dim: int = 128
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    group_norm_size: int = 1              # groups of the KDA output norm
    num_experts: int = 512                # the router's width
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    # the experts this chip holds: its share of an expert-parallel layer
    held_first: int = 0
    held_experts: int = 512
    rope_theta: float = 6000000.0
    rms_eps: float = 1e-6
    max_seq_len: int = 262144
    dtype: Any = jnp.bfloat16
    remat: bool = False                   # accepted, unused: serving only

    def is_mla_layer(self, l: int) -> bool:
        return (self.first_layer + l + 1) % self.layer_group_size == 0

    def is_moe_layer(self, l: int) -> bool:
        return self.first_layer + l >= self.first_k_dense_replace

    def served_model(self):
        return LingHybridServed(self)


def from_published(layer: Dict, c: LingHybridConfig) -> Dict:
    """One layer's leaves in the published layout (``w_q``/``w_k``/``w_v``,
    ``conv_q``/``conv_k``/``conv_v``, ``w_beta``/``w_g`` apart on a KDA
    layer; DeepSeek-V2's on an MLA layer, ``w_q`` uncompressed; gate and up
    of the held experts apart) as this program keeps them."""
    if "w_dkv" in layer:
        return deepseek_v2.from_published(layer, _mla_config(c))
    cat = lambda *names: jnp.concatenate([layer[n] for n in names], -1)
    out = {k: v for k, v in layer.items()
           if k not in ("w_q", "w_k", "w_v", "conv_q", "conv_k", "conv_v",
                        "w_beta", "w_g", "e_gate", "e_up")}
    out["w_qkv"] = cat("w_q", "w_k", "w_v")
    out["conv_w"] = jnp.concatenate(
        [layer[n] for n in ("conv_q", "conv_k", "conv_v")], 0)
    out["w_bg"] = cat("w_beta", "w_g")
    if "e_gate" in layer:
        out["e_gu"] = cat("e_gate", "e_up")
    return out


def _mla_config(c: LingHybridConfig) -> deepseek_v2.DeepseekV2Config:
    """The MLA layer as a one-layer ``DeepseekV2Config``: no query
    compression, no rope scaling (a factor of 1 leaves YaRN's tables and
    the softmax scale plain), no expert layer of its own."""
    return deepseek_v2.DeepseekV2Config(
        vocab_size=c.vocab_size, hidden_size=c.hidden_size, num_layers=1,
        num_heads=c.num_heads, q_lora_rank=None,
        kv_lora_rank=c.kv_lora_rank, qk_nope_head_dim=c.qk_nope_head_dim,
        qk_rope_head_dim=c.qk_rope_head_dim, v_head_dim=c.v_head_dim,
        first_k_dense_replace=1, rope_theta=c.rope_theta, rope_factor=1.0,
        rms_eps=c.rms_eps, max_seq_len=c.max_seq_len, dtype=c.dtype)


class LingHybridServed:
    cache_kind = "latent"
    unsupported = {
        "spec": "there is no draft of this family, its own next-token "
                "module is not loaded, and spec_verify is llama's program",
        "prefix_cache": "a cached block's reuse needs the KDA layers' "
                        "matrix state at the block's boundary, which is "
                        "kept per slot and not per block: no snapshot "
                        "exists to start a suffix from",
        "kv_swap": "the swap tier moves blocks; a swapped-out request's "
                   "13 MB of per-slot state would have to be snapshotted "
                   "with them",
        "mesh": "no sharding recipe for the per-slot state, the latent "
                "pool or the expert share (the share IS the deployment's "
                "expert parallelism)",
        "kv_int8": "the latent walk reads bf16/f32 rows; an int8 latent "
                   "needs its own scale entry and kernel path",
        "disagg": "the relay hands over blocks; the per-slot state would "
                  "have to travel with them",
    }

    def __init__(self, config: LingHybridConfig):
        c = config
        if c.num_experts % c.n_group or not (
                0 <= c.held_first
                and c.held_first + c.held_experts <= c.num_experts):
            raise ValueError("n_group must divide num_experts, and the "
                             "held experts lie inside the router's width")
        if c.short_conv_kernel_size != 4:
            raise ValueError("the short convolution is written for four "
                             "taps: a state of three inputs")
        if c.kda_lower_bound < kda.LOWER_BOUND:
            raise ValueError(
                f"kda_lower_bound {c.kda_lower_bound} is below the "
                f"{kda.LOWER_BOUND} that the chunk kernel's factored "
                "sub-blocks keep inside float32")
        if (c.num_heads * c.head_dim) % c.group_norm_size:
            raise ValueError("group_norm_size must divide the heads' "
                             "channels")
        self.config = c
        self.num_layers = c.num_layers
        self.vocab_size = c.vocab_size
        self.dtype = c.dtype
        self._mla_layers = [l for l in range(c.num_layers)
                            if c.is_mla_layer(l)]
        if len(self._mla_layers) != 1:
            raise ValueError("one MLA layer a chip's stage, which writes "
                             "the one latent entry: a second would take a "
                             "second entry, none leaves no paged cache")
        self._kda = [l for l in range(c.num_layers) if not c.is_mla_layer(l)]
        self._mla = deepseek_v2.DeepseekV2Served(_mla_config(c))
        # the per-slot entries (``make_state``): the matrix a head, then
        # the convolutions' last three inputs, of each KDA layer
        self.state_in_place = tuple(f"s{i}" for i in range(len(self._kda)))
        self.state_entries = self.state_in_place + tuple(
            f"u{i}" for i in range(len(self._kda)))
        # layers whose state a scan advances over a piece's tokens
        self.scan_layers = len(self._kda)
        self._has_experts = any(c.is_moe_layer(l)
                                for l in range(c.num_layers))

    # -- the cache and the state ---------------------------------------------
    def make_pools(self, nb: int, bs: int, kv_int8: bool = False,
                   prefix: str = "") -> Dict:
        return self._mla.make_pools(nb, bs, kv_int8, prefix)

    def make_state(self, slots: int) -> Dict:
        """The per-slot entries, zeroed; the last row of each takes the
        writes of rows with no slot."""
        c = self.config
        H, d = c.num_heads, c.head_dim
        out = {n: jnp.zeros((1, slots + 1, H, d, d), jnp.float32)
               for n in self.state_in_place}
        out.update({f"u{i}": jnp.zeros((1, slots + 1, 3, 3 * H * d), c.dtype)
                    for i in range(len(self._kda))})
        return out

    def ragged_refusal(self, kv_int8: bool):
        return None

    history_blocks = staticmethod(deepseek_v2.DeepseekV2Served.history_blocks)

    def piece_flash_tiles(self, S: int, hist: int, pnbk: int, bs: int):
        """The one MLA layer's: the KDA layers run no blockwise softmax."""
        return self._mla.piece_flash_tiles(S, hist, pnbk, bs)

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
    def _kda_inputs(self, p, hn, conv):
        """From the normed hidden state and the convolved, activated q/k/v
        product [..., 3 H d]: q (unit length x d^-1/2), k (unit length),
        v [..., H, d] float32, the log-decays g [..., H, d] in
        (kda_lower_bound, 0), beta and the output gate [..., H]."""
        c, dt = self.config, self.dtype
        H, d = c.num_heads, c.head_dim
        q, k, v = (t.reshape(t.shape[:-1] + (H, d)).astype(jnp.float32)
                   for t in jnp.split(conv, 3, axis=-1))
        unit = lambda t: t * jax.lax.rsqrt(
            jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)
        f = (hn @ p["w_f"].astype(dt)).astype(jnp.float32) \
            + p["dt_bias"].astype(jnp.float32)
        g = c.kda_lower_bound * jax.nn.sigmoid(
            jnp.exp(p["a_log"].astype(jnp.float32))[:, None]
            * f.reshape(f.shape[:-1] + (H, d)))
        bg = jax.nn.sigmoid((hn @ p["w_bg"].astype(dt)).astype(jnp.float32))
        return unit(q) * d ** -0.5, unit(k), v, g, bg[..., :H], bg[..., H:]

    def _kda_out(self, p, o, gate):
        """o [..., H, d] float32: the group norm over the concatenated
        channels, the head-wise gate, ``w_o``."""
        c = self.config
        H, d = c.num_heads, c.head_dim
        lead = o.shape[:-2]
        og = o.reshape(lead + (c.group_norm_size, -1))
        og = og * jax.lax.rsqrt(
            jnp.mean(og * og, axis=-1, keepdims=True) + c.rms_eps)
        o = og.reshape(lead + (H, d)) * p["o_norm"].astype(
            jnp.float32).reshape(H, d) * gate[..., None]
        return o.reshape(lead + (H * d,)).astype(self.dtype) \
            @ p["w_o"].astype(self.dtype)

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
            c.num_experts_per_tok, c.routed_scaling_factor, c.norm_topk_prob,
            c.n_group, c.topk_group)
        routed, counts = held_expert_ffn(x, gates, idx, valid, p["e_gu"],
                                         p["e_down"], c.held_first)
        return routed + _swiglu(x, p["s_gate"], p["s_up"], p["s_down"],
                                dt), counts

    # -- prefill -------------------------------------------------------------
    def prefill_begin(self, params, pools, tokens, true_len, hist_len,
                      ctx_tbl, prefix_nbk: int, opts: ServeOpts):
        aux = self._mla.prefill_begin(params, pools, tokens, true_len,
                                      hist_len, ctx_tbl, prefix_nbk, opts)
        aux["true_len"] = true_len
        return aux

    def _prefill_kda(self, p, i: int, hn, aux):
        """The KDA layer of a piece from the state its predecessor left;
        both new entries are those after the piece's last REAL token,
        whatever padding follows it."""
        B, S, _ = hn.shape
        dt = self.dtype
        n = aux["true_len"].astype(jnp.int32)
        with jax.named_scope("ling.kda_conv"):
            x = hn @ p["w_qkv"].astype(dt)
            xe = jnp.concatenate([aux["state"][f"u{i}"][0].astype(dt), x], 1)
            w = p["conv_w"].astype(dt)                         # [3 H d, 4]
            conv = jax.nn.silu(sum(w[:, j] * xe[:, j:j + S]
                                   for j in range(4)))
            # xe rows (n, n + 1, n + 2) are x_{n-3} .. x_{n-1} of the piece
            u_new = jax.vmap(lambda r, m: jax.lax.dynamic_slice_in_dim(
                r, m, 3, 0))(xe, n)
        q, k, v, g, beta, gate = self._kda_inputs(p, hn, conv)
        o, s_new = jax.lax.map(
            lambda a: kda.kda_chunk(*a, name="ling_kda_chunk"),
            (q, k, v, g, beta, aux["state"][f"s{i}"][0], n))
        return self._kda_out(p, o, gate), {f"s{i}": s_new, f"u{i}": u_new}

    def prefill_mix(self, params, l: int, x, aux, pools, opts: ServeOpts):
        """The token-mixing half of a piece's layer: x [B, S, h] -> (x +
        KDA or latent attention, the layer's new entries)."""
        p = params["layers"][l]
        if self.config.is_mla_layer(l):
            return self._mla.prefill_mix({"layers": [p]}, 0, x, aux, pools,
                                         opts)
        hn = _rms_norm(x, p["attn_norm"], self.config.rms_eps)
        y, ent = self._prefill_kda(p, self._kda.index(l), hn, aux)
        return x + y, ent

    def ffn(self, params, l: int, rows, valid):
        """The row-wise half of a layer, whatever program the rows come
        from: rows [T, h] -> (rows + FFN(norm(rows)), counts or None). No
        row's result depends on another's."""
        p = params["layers"][l]
        y, counts = self._ffn(
            p, l, _rms_norm(rows, p["mlp_norm"], self.config.rms_eps), valid)
        return rows + y, counts

    def prefill_layer(self, params, l: int, x, aux, pools, opts: ServeOpts):
        """``prefill_mix`` and then ``ffn`` over the piece's own rows."""
        B, S, h = x.shape
        x, ent = self.prefill_mix(params, l, x, aux, pools, opts)
        rows, counts = self.ffn(params, l, x.reshape(B * S, h), aux["valid"])
        if self._has_experts:
            ent["_stats"] = (counts if counts is not None
                             else jnp.zeros((5,), jnp.float32))
        return rows.reshape(B, S, h), ent

    def pack_entries(self, new: Dict, opts: ServeOpts) -> Dict:
        return self._mla.pack_entries(new, opts)

    # -- decode --------------------------------------------------------------
    def ring_init(self, N: int, S: int, opts: ServeOpts) -> Dict:
        ring = self._mla.ring_init(N, S, opts)
        if self._has_experts:
            ring["_stats"] = jnp.zeros((5,), jnp.float32)
        return ring

    def decode_begin(self, params, pools, block_table, lens0, active,
                     n_steps: int, opts: ServeOpts):
        return self._mla.decode_begin(params, pools, block_table, lens0,
                                      active, n_steps, opts)

    def decode_step_begin(self, aux, lens, t, S: int):
        return self._mla.decode_step_begin(aux, lens, t, S)

    def _decode_kda(self, p, i: int, hn, ring, act):
        """One token a slot: both entries move only where the slot is
        active and not done; the matrix entry is the pools' own, advanced
        where it lies."""
        dt = self.dtype
        with jax.named_scope("ling.kda_conv"):
            x = hn @ p["w_qkv"].astype(dt)
            u = ring[f"u{i}"][0]                              # [N, 3, 3 H d]
            w = p["conv_w"].astype(dt)
            conv = jax.nn.silu(w[:, 0] * u[:, 0].astype(dt)
                               + w[:, 1] * u[:, 1].astype(dt)
                               + w[:, 2] * u[:, 2].astype(dt) + w[:, 3] * x)
            u_new = jnp.where(
                act[:, None, None],
                jnp.stack([u[:, 1], u[:, 2], x.astype(u.dtype)], 1), u)
        q, k, v, g, beta, gate = self._kda_inputs(p, hn, conv)
        o, s_new = kda.kda_step(q, k, v, g, beta, ring[f"s{i}"], act,
                                name="ling_kda_step")
        return self._kda_out(p, o, gate), {
            **ring, f"s{i}": s_new, f"u{i}": u_new[None]}

    def decode_mix(self, params, l: int, x, aux, step, ring, t, pools, act,
                   opts: ServeOpts):
        """The token-mixing half of a decode step's layer: x [N, 1, h] ->
        (x + KDA or absorbed latent attention [N, h], the ring with this
        step's latent row or advanced state)."""
        p = params["layers"][l]
        if self.config.is_mla_layer(l):
            return self._mla.decode_mix({"layers": [p]}, 0, x, aux, step,
                                        ring, t, pools, act, opts)
        hn = _rms_norm(x[:, 0], p["attn_norm"], self.config.rms_eps)
        y, ring = self._decode_kda(p, self._kda.index(l), hn, ring, act)
        return x[:, 0] + y, ring

    def decode_layer(self, params, l: int, x, aux, step, ring, t, pools,
                     act, opts: ServeOpts):
        """``decode_mix`` and then ``ffn`` over the slots' rows."""
        xa, ring = self.decode_mix(params, l, x, aux, step, ring, t, pools,
                                   act, opts)
        rows, counts = self.ffn(params, l, xa, act)
        if counts is not None:
            ring = dict(ring, _stats=ring["_stats"] + counts)
        return rows[:, None], ring
