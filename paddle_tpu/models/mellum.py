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

What the engine sees (the interface of ``models/llama_served.py``):

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
from ..kernels.paged_attention import ragged_tpu_refusal
from .flat_kv_attention import decode_attention, pack_rows, prefill_attention
from .llama import _rms_norm
from .llama_served import ServeOpts
from .rope import rope_half, yarn_frequencies

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


def _history_pad(tokens: int) -> int:
    """A gathered history's width: a multiple of the flash kernel's key
    tile (512, or 128 for a short one), so that no tile is narrower than
    the MXU; the rows past the history are masked by its length."""
    m = 512 if tokens > 512 else 128 if tokens > 128 else 1
    return -(-tokens // m) * m


def ring_positions(lens0, width: int, bs: int):
    """For a ring table gathered dense ([N, width * bs] rows, column c of
    the ring first): the position each row holds for a slot whose context
    is ``lens0`` tokens, -1 where the column was never written. Column c
    holds the newest logical block ``b <= (lens0 - 1) // bs`` with ``b %
    width == c`` (``serving/window_ledger.py``)."""
    newest = (lens0.astype(jnp.int32) - 1) // bs                   # [N]
    c = jnp.arange(width, dtype=jnp.int32)[None, :]
    b = newest[:, None] - jnp.mod(newest[:, None] - c, width)      # [N, w]
    pos = b[:, :, None] * bs + jnp.arange(bs, dtype=jnp.int32)[None, None, :]
    return jnp.where((b >= 0)[:, :, None], pos, -1).reshape(
        lens0.shape[0], width * bs)


class MellumServed:
    cache_kind = "kv"
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
        bad = set(c.layer_types) - {"sliding_attention", "full_attention"}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        if c.num_heads % c.num_kv_heads or c.head_dim % 2:
            raise ValueError(f"{c.num_heads} heads on {c.num_kv_heads} KV "
                             f"heads of {c.head_dim}")
        if not c.norm_topk_prob:
            raise ValueError("the router renormalises its top-k "
                             "(routing_from_logits): norm_topk_prob false "
                             "is not written")
        self.config = c
        self.num_layers = c.num_layers
        self.vocab_size = c.vocab_size
        self.dtype = c.dtype
        self.window = int(c.sliding_window)
        # a layer's index among the layers of its own kind: which plane of
        # its kind's pools it writes
        self._full = [l for l, t in enumerate(c.layer_types)
                      if t == "full_attention"]
        self._win = [l for l, t in enumerate(c.layer_types)
                     if t == "sliding_attention"]
        if not self._full or not self._win:
            raise ValueError("both kinds of layer are expected: the dense "
                             "family serves a model of one kind")
        # the pool entries of the WINDOW kind: a ring a slot in the engine
        self.window_entries = tuple(f"kvw{a}" for a in range(len(self._win)))

    # -- the cache -----------------------------------------------------------
    def make_pools(self, nb: int, bs: int, kv_int8: bool = False,
                   prefix: str = "", nb_window: int = 0) -> Dict:
        c = self.config
        row = (bs, 2 * c.num_kv_heads * c.head_dim)              # [V | K]
        return {f"{prefix}kv{kind}{a}": jnp.zeros((1, n) + row, c.dtype)
                for kind, ls, n in (("f", self._full, nb),
                                    ("w", self._win, nb_window))
                for a in range(len(ls))}

    def ragged_refusal(self, kv_int8: bool):
        c = self.config                              # rows of 1024 lanes
        return ragged_tpu_refusal(2 * c.num_kv_heads * c.head_dim, kv_int8)

    @staticmethod
    def history_blocks(hist_blocks: int, mb: int) -> int:
        """Full width or none: the history kernels take a row's length as
        a runtime operand and skip the tiles past it."""
        return mb if hist_blocks else 0

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

    def _kind(self, l: int) -> Tuple[str, int]:
        """("f" | "w", the layer's plane in its kind's pools)."""
        if self.config.layer_types[l] == "full_attention":
            return "f", self._full.index(l)
        return "w", self._win.index(l)

    def _qkv(self, hn, p, ang, mscale):
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
        q = rope_half(_rms_norm(q, p["q_norm"], c.rms_eps), ang, mscale)
        k = rope_half(_rms_norm(k, p["k_norm"], c.rms_eps), ang, mscale)
        return q, k, v

    def _ffn(self, p, x, valid):
        """x [T, h] -> (y, counts): every layer is sparse."""
        c, dt = self.config, self.dtype
        logits = jnp.dot(x, p["router"].astype(dt),
                         preferred_element_type=jnp.float32)
        r = routing_from_logits(logits, c.num_experts_per_tok)
        return held_expert_ffn(x, r.weights, r.idx.astype(jnp.int32), valid,
                               p["e_gu"], p["e_down"], 0)

    # -- prefill -------------------------------------------------------------
    def prefill_begin(self, params, pools, tokens, true_len, hist_len,
                      ctx_tbl, prefix_nbk: int, opts: ServeOpts, win=None):
        B, S = tokens.shape
        start = (jnp.zeros((B,), jnp.float32) if hist_len is None
                 else hist_len.astype(jnp.float32))
        pos = start[:, None] + jnp.arange(S, dtype=jnp.float32)[None, :]
        aux = {"ang": {k: (pos[:, :, None] * f[None, None, :], m)
                       for k, (f, m) in self._freqs().items()},
               "prefix_nbk": prefix_nbk, "hist_len": hist_len,
               "ctx_tbl": ctx_tbl,
               # pad positions of a row and pad rows are not routed
               "valid": (jnp.arange(S)[None, :]
                         < true_len[:, None]).reshape(B * S)}
        if prefix_nbk:
            # the window layers' history: the ring's blocks that hold the
            # last W - 1 tokens before the piece, in order, padded with
            # the trash block to a width the flash kernel tiles well
            tbl = win["ctx_tbl"]
            bs = pools[f"{opts.prefix}kvw0"].shape[2]
            width = _history_pad(tbl.shape[1] * bs) // bs
            aux["win_tbl"] = jnp.pad(tbl, ((0, 0), (0, width - tbl.shape[1])))
            aux["win_len"] = hist_len.astype(jnp.int32) - win["ctx_start"]
        return aux

    def _prefill_attention(self, p, kind: str, a: int, hn, aux, pools, opts):
        """Attention of a piece over [history ; piece]: both parts
        blockwise, one softmax. A window layer's history is the last W - 1
        tokens under the band ``i - j < W``; a full layer's is all of it."""
        B, S, _ = hn.shape
        Hkv, W = self.config.num_kv_heads, self.window
        q, k, v = self._qkv(hn, p, *aux["ang"][kind])
        # inside a piece the band cuts nothing unless the bucket is longer
        # than the window (a static fact of the program)
        band = (jnp.full((B * Hkv,), 1 - W, jnp.int32)
                if kind == "w" and S > W else None)
        history = None
        pool = pools[f"{opts.prefix}kv{kind}{a}"]
        if aux["prefix_nbk"] and kind == "f":
            history = (pool, aux["ctx_tbl"], aux["hist_len"], None,
                       "mellum_history_full")
        elif aux["prefix_nbk"]:
            # gathered key j is position ctx_start + j, query row i
            # position hist_len + i: i - j < W in the rows' own indices
            history = (pool, aux["win_tbl"], aux["win_len"],
                       jnp.repeat(aux["win_len"] - W + 1, Hkv),
                       "mellum_history_window")
        o = prefill_attention(q, k, v, chunk_name="mellum_prefill_chunk",
                              chunk_band=band, history=history)
        return (o @ p["wo"].astype(self.dtype),
                {f"kv{kind}": pack_rows(k, v)})

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

    def pack_entries(self, new: Dict, opts: ServeOpts) -> Dict:
        """Rows stacked over a kind's layers [L_kind, ..., 2 * Hkv * D],
        as each layer's own pool."""
        return {f"{opts.prefix}{n}{a}": rows[a:a + 1]
                for n, rows in new.items() for a in range(rows.shape[0])}

    # -- decode --------------------------------------------------------------
    def ring_init(self, N: int, S: int, opts: ServeOpts) -> Dict:
        c = self.config
        row = (N, S, 2 * c.num_kv_heads * c.head_dim)
        ring = {f"kv{kind}": jnp.zeros((len(ls),) + row, c.dtype)
                for kind, ls in (("f", self._full), ("w", self._win))}
        ring["_stats"] = jnp.zeros((5,), jnp.float32)
        return ring

    def decode_begin(self, params, pools, block_table, lens0, active,
                     n_steps: int, opts: ServeOpts, win_table=None):
        c = self.config
        N, MB = block_table.shape
        Hkv, D = c.num_kv_heads, c.head_dim
        aux = {"freqs": self._freqs(), "block_table": block_table,
               "win_table": win_table, "lens0": lens0.astype(jnp.int32)}
        if opts.ragged:
            # slots outside the decode set walk zero blocks
            aux["walk_lens"] = jnp.where(active, lens0.astype(jnp.int32), 0)
            return aux
        # off a TPU: one dense gather of every slot's frozen prefix, the
        # full kind's through its table, the window kind's ring as it lies
        # with the position each of its rows holds
        px = opts.prefix
        bs = pools[px + "kvf0"].shape[2]
        for kind, ls, tbl in (("f", self._full, block_table),
                              ("w", self._win, win_table)):
            dense = [pools[f"{px}kv{kind}{a}"][0][tbl].reshape(
                N, -1, 2, Hkv, D) for a in range(len(ls))]
            aux[f"kd{kind}"] = [r[:, :, 1] for r in dense]
            aux[f"vd{kind}"] = [r[:, :, 0] for r in dense]
        aux["pos_f"] = jnp.broadcast_to(
            jnp.arange(MB * bs, dtype=jnp.int32)[None, :], (N, MB * bs))
        aux["pos_w"] = ring_positions(lens0, win_table.shape[1], bs)
        return aux

    def decode_step_begin(self, aux, lens, t, S: int):
        W = self.window
        lens = lens.astype(jnp.int32)
        lens0 = aux["lens0"]
        # what a window layer may see of a query at position ``lens``:
        # [lens - W + 1, lens], of which the pool holds [.., lens0)
        start = jnp.maximum(lens - W + 1, 0)
        ring_pos = lens0[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
        in_call = (jnp.arange(S) <= t)[None, :]
        step = {"ang": {k: (lens.astype(jnp.float32)[:, None] * f[None, :], m)
                        for k, (f, m) in aux["freqs"].items()},
                "start": start,
                "ring_mask": {
                    "f": in_call[:, None, None, :],
                    "w": (in_call & (ring_pos >= start[:, None])
                          )[:, None, None, :]}}
        if "pos_w" in aux:
            held = lambda pos: (pos >= 0) & (pos < lens0[:, None])
            step["pre_mask"] = {
                "f": held(aux["pos_f"])[:, None, None, :],
                "w": (held(aux["pos_w"]) & (aux["pos_w"] >= start[:, None])
                      )[:, None, None, :]}
        return step

    def _decode_attention(self, p, kind: str, a: int, hn, aux, step, ring,
                          t, pools, opts):
        dt = self.dtype
        name = f"kv{kind}"
        q, kk, vv = self._qkv(hn, p, *step["ang"][kind])
        walk = dense = None
        if opts.ragged and kind == "f":
            walk = (pools[f"{opts.prefix}{name}{a}"], aux["block_table"],
                    aux["walk_lens"], None, "mellum_walk_full")
        elif opts.ragged:
            walk = (pools[f"{opts.prefix}{name}{a}"], aux["win_table"],
                    aux["walk_lens"], step["start"], "mellum_walk_window")
        else:
            dense = (aux[f"kd{kind}"][a], aux[f"vd{kind}"][a],
                     step["pre_mask"][kind])
        att, rkv = decode_attention(
            q, kk, vv, ring[name], a, t, step["ring_mask"][kind], dt,
            walk=walk, dense=dense)
        return att @ p["wo"].astype(dt), {**ring, name: rkv}

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
