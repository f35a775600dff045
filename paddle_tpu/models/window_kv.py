"""Window layers beside full ones over the ``[V | K]`` cache row: what a
served model with BOTH kinds of layer needs of the engine's two block
ledgers, written once. ``models/mellum.py`` (Mellum2) and
``models/afmoe.py`` (Trinity) inherit it; ``models/flat_kv_attention.py``
is the attention both kinds call.

A layer's pool row holds all of a token's value heads and then all of its
key heads (``2 x Hkv x D`` lanes, one pool a layer: ``docs/served_models.md``
says why). A FULL layer's pool (``kvf<a>`` [1, NB, bs, row]) is indexed by
the engine's block table: a slot holds its whole context there. A WINDOW
layer's (``kvw<a>`` [1, NB_window, bs, row]) is an entry of the window kind
(``window_entries``, ``window``): the engine's second ledger
(``serving/window_ledger.py``) gives a slot a ring of ``ceil(W / bs) + 1``
blocks, written again in place as the context moves on.

What is shared: the pools and their names, which plane of its kind's pools
a layer writes, the window history's table and length, the ring's
positions off a TPU, the masks of a decode step, and the two attention
calls with the operands of each kind (a band and a start for a window
layer, none for a full one). What a model keeps: its projections and what
it does to q and k (``_qkv(hn, p, ang)``: ``ang`` is ``(angles, factor)``
for a kind that turns them, ``None`` for a kind that carries no position),
its tables (``_freqs()``: one entry a kind that turns), what it does to the
heads' outputs (``_attn_out(p, o, hn)``: the output projection, a gate
before it), and its kernels' names in a trace (``trace_name``).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax.numpy as jnp

from ..kernels.paged_attention import ragged_tpu_refusal
from .flat_kv_attention import (decode_attention, pack_rows,
                                prefill_attention, prefill_attention_tiles)
from .llama_served import ServeOpts

__all__ = ["TwoKindCache", "ring_positions", "history_pad"]


def history_pad(tokens: int) -> int:
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


class TwoKindCache:
    """The cache half of a served model whose ``config.layer_types`` name
    ``sliding_attention`` and ``full_attention`` layers. The model sets
    ``config`` (``layer_types``, ``num_heads``, ``num_kv_heads``,
    ``head_dim``, ``sliding_window``, ``dtype``) and ``trace_name``, calls
    ``_init_kinds`` and gives ``_freqs``, ``_qkv`` and ``_attn_out``."""

    cache_kind = "kv"
    trace_name = "flat"

    def _init_kinds(self) -> None:
        c = self.config
        layer_types = c.layer_types
        bad = set(layer_types) - {"sliding_attention", "full_attention"}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        if c.num_heads % c.num_kv_heads or c.head_dim % 2:
            raise ValueError(f"{c.num_heads} heads on {c.num_kv_heads} KV "
                             f"heads of {c.head_dim}")
        self.window = int(c.sliding_window)
        # a layer's index among the layers of its own kind: which plane of
        # its kind's pools it writes
        self._full = [l for l, t in enumerate(layer_types)
                      if t == "full_attention"]
        self._win = [l for l, t in enumerate(layer_types)
                     if t == "sliding_attention"]
        if not self._full or not self._win:
            raise ValueError("both kinds of layer are expected: the dense "
                             "family serves a model of one kind")
        # the pool entries of the WINDOW kind: a ring a slot in the engine
        self.window_entries = tuple(f"kvw{a}" for a in range(len(self._win)))

    # -- the cache -----------------------------------------------------------
    def _row(self) -> int:
        c = self.config
        return 2 * c.num_kv_heads * c.head_dim                   # [V | K]

    def make_pools(self, nb: int, bs: int, kv_int8: bool = False,
                   prefix: str = "", nb_window: int = 0) -> Dict:
        row = (bs, self._row())
        return {f"{prefix}kv{kind}{a}": jnp.zeros((1, n) + row,
                                                  self.config.dtype)
                for kind, ls, n in (("f", self._full, nb),
                                    ("w", self._win, nb_window))
                for a in range(len(ls))}

    def ragged_refusal(self, kv_int8: bool):
        return ragged_tpu_refusal(self._row(), kv_int8)

    @staticmethod
    def history_blocks(hist_blocks: int, mb: int) -> int:
        """Full width or none: the history kernels take a row's length as
        a runtime operand and skip the tiles past it."""
        return mb if hist_blocks else 0

    def _kind(self, l: int) -> Tuple[str, int]:
        """("f" | "w", the layer's plane in its kind's pools)."""
        if self.config.layer_types[l] == "full_attention":
            return "f", self._full.index(l)
        return "w", self._win.index(l)

    def pack_entries(self, new: Dict, opts: ServeOpts) -> Dict:
        """Rows stacked over a kind's layers [L_kind, ..., 2 * Hkv * D],
        as each layer's own pool."""
        return {f"{opts.prefix}{n}{a}": rows[a:a + 1]
                for n, rows in new.items() for a in range(rows.shape[0])}

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
            width = history_pad(tbl.shape[1] * bs) // bs
            aux["win_tbl"] = jnp.pad(tbl, ((0, 0), (0, width - tbl.shape[1])))
            aux["win_len"] = hist_len.astype(jnp.int32) - win["ctx_start"]
        return aux

    def _prefill_attention(self, p, kind: str, a: int, hn, aux, pools, opts):
        """Attention of a piece over [history ; piece]: both parts
        blockwise, one softmax. A window layer's history is the last W - 1
        tokens under the band ``i - j < W``; a full layer's is all of it.
        Returns (the model's ``_attn_out`` of the heads' outputs, the
        layer's new rows)."""
        B, S, _ = hn.shape
        Hkv, W = self.config.num_kv_heads, self.window
        q, k, v = self._qkv(hn, p, aux["ang"].get(kind))
        # inside a piece the band cuts nothing unless the bucket is longer
        # than the window (a static fact of the program)
        band = (jnp.full((B * Hkv,), 1 - W, jnp.int32)
                if kind == "w" and S > W else None)
        history = None
        pool = pools[f"{opts.prefix}kv{kind}{a}"]
        if aux["prefix_nbk"] and kind == "f":
            history = (pool, aux["ctx_tbl"], aux["hist_len"], None,
                       f"{self.trace_name}_history_full")
        elif aux["prefix_nbk"]:
            # gathered key j is position ctx_start + j, query row i
            # position hist_len + i: i - j < W in the rows' own indices
            history = (pool, aux["win_tbl"], aux["win_len"],
                       jnp.repeat(aux["win_len"] - W + 1, Hkv),
                       f"{self.trace_name}_history_window")
        o = prefill_attention(
            q, k, v, chunk_name=f"{self.trace_name}_prefill_chunk",
            chunk_band=band, history=history)
        return self._attn_out(p, o, hn), {f"kv{kind}": pack_rows(k, v)}

    def piece_flash_tiles(self, S: int, hist: int, pnbk: int, bs: int):
        """The grid steps of a piece's blockwise attention by kernel and
        kind, over both kinds' layers and their KV heads: a bucket of
        ``S`` after ``hist`` cached tokens, of which a full layer gathers
        ``pnbk`` blocks and a window layer its ring (``prefill_begin``,
        ``serving/window_ledger.py``'s width and ``history``)."""
        c, W = self.config, self.window
        dims = (S, c.num_heads, c.num_kv_heads, c.head_dim)
        out = {}
        for kind, layers in (("full", self._full), ("window", self._win)):
            history = None
            if pnbk and kind == "full":
                history = (pnbk * bs, hist, None)
            elif pnbk:
                n_win = hist - max(0, hist - W + 1) // bs * bs
                history = (history_pad((-(-W // bs) + 1) * bs), n_win,
                           n_win - W + 1)
            counts = prefill_attention_tiles(
                *dims, chunk_band=1 - W if kind == "window" and S > W
                else None, history=history)
            n = len(layers) * c.num_kv_heads
            for name, got in zip(("prefill_chunk", f"history_{kind}"),
                                 counts):
                if got:
                    name = f"{self.trace_name}_{name}"
                    out[name] = tuple(a + n * t for a, t in zip(
                        out.get(name, (0, 0, 0)), got))
        return out

    # -- decode --------------------------------------------------------------
    def ring_init(self, N: int, S: int, opts: ServeOpts) -> Dict:
        row = (N, S, self._row())
        ring = {f"kv{kind}": jnp.zeros((len(ls),) + row, self.config.dtype)
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
        """One decode step's attention of its kind: (the model's
        ``_attn_out`` of the heads' outputs [N, H * D], the ring with this
        step's row)."""
        name = f"kv{kind}"
        q, kk, vv = self._qkv(hn, p, step["ang"].get(kind))
        walk = dense = None
        if opts.ragged and kind == "f":
            walk = (pools[f"{opts.prefix}{name}{a}"], aux["block_table"],
                    aux["walk_lens"], None, f"{self.trace_name}_walk_full")
        elif opts.ragged:
            walk = (pools[f"{opts.prefix}{name}{a}"], aux["win_table"],
                    aux["walk_lens"], step["start"],
                    f"{self.trace_name}_walk_window")
        else:
            dense = (aux[f"kd{kind}"][a], aux[f"vd{kind}"][a],
                     step["pre_mask"][kind])
        att, rkv = decode_attention(
            q, kk, vv, ring[name], a, t, step["ring_mask"][kind], self.dtype,
            walk=walk, dense=dense)
        return self._attn_out(p, att, hn), {**ring, name: rkv}
