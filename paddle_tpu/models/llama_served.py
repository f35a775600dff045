"""The llama family behind the serving engine's model interface.

``serving/engine.py`` writes no model's layer out. Its programs
(``_paged_prefill``, ``_paged_decode``) own what every served model shares
— the operands' shapes (a prefill program takes ONE row: a prompt, a
cache-hit suffix or a chunk, in its own bucket), the loop over the layers,
the in-call ring, the write-back into the paged pools, sampling — and call
a model object for everything that is the model's: which entries a token
leaves in the cache per layer, a layer's prefill step, a layer's decode
step, the embedding, the final norm and the head. A config object names
its served model (``config.served_model()``); this module is
``LlamaConfig``'s.

The interface (what the engine calls; ``opts`` is the engine's
``ServeOpts``: ``kv_int8``, ``numerics``, ``ragged``, ``prefix`` (the
pool-name prefix of a draft model) and ``mesh``):

``num_layers``, ``vocab_size``, ``dtype``
``cache_kind``                        "kv" or "latent": what a pool row is
``unsupported``                       engine features this model refuses,
                                      each with its reason
``make_pools(nb, bs, kv_int8)``       the zeroed pool entries
                                      ``{name: [L, nb, bs, ...]}``
``state_entries``, ``make_state(N)``  names of per-SLOT entries ``{name:
                                      [L', N + 1, ...]}`` kept beside the
                                      cache (``()`` here: nothing; a short
                                      convolution's last inputs in
                                      models/lfm2_moe.py) and their zeros
``window_entries``, ``window``        names of the pool entries of the
                                      WINDOW kind and the window's tokens
                                      (absent here: every entry is of the
                                      full kind; models/mellum.py names its
                                      window layers' pools, the engine keeps
                                      a second block ledger for them and
                                      passes ``make_pools`` an
                                      ``nb_window``, ``prefill_begin`` a
                                      ``win`` and ``decode_begin`` a
                                      ``win_table``)
``ragged_refusal(kv_int8)``           why the chip's compiler refuses the
                                      decode walk at this shape, or None
``history_blocks(hist_blocks, mb)``   how wide a row's history table is
``embed(params, tokens)``
``final_norm(params, x)``, ``head(params, x)``, ``decode_head(params)``
``prefill_begin(...)`` -> aux         positions, masks, gathered history
``prefill_layer(params, l, x, aux, pools, opts)`` -> (x, new entries)
``pack_entries(stacked, opts)``       new entries as the pools store them
``ring_init(N, S, opts)``             the in-call ring of a decode call
``decode_begin(...)`` -> aux          what is frozen for the whole call
``decode_layer(params, l, x, aux, step, ring, t, pools, opts)``
``prefill_mix``, ``decode_mix``, ``ffn``  OPTIONAL: a layer's two halves
                                      apart, for ONE program in which a
                                      step's last prefill piece carries the
                                      decode rows (docs/served_models.md):
                                      ``prefill_mix(params, l, x, aux,
                                      pools, opts) -> (x, entries)`` and
                                      ``decode_mix(params, l, x, aux, step,
                                      ring, t, pools, act, opts) -> (x [N,
                                      h], ring)`` are the token-mixing half
                                      with its residual, ``ffn(params, l,
                                      rows [T, h], valid) -> (rows, counts)``
                                      the row-wise half (norm, router,
                                      experts or dense FFN, residual: no
                                      cross-row state). Absent here: the
                                      dense family keeps its two programs
``piece_flash_tiles(S, hist, pnbk, bs)``  OPTIONAL (the chunked families):
                                      ``{kernel: (interior, edge, skipped)}``,
                                      the grid steps of a piece's blockwise
                                      attention by what the kernel does in
                                      them, from numbers the engine holds
                                      (``serving_flash_tiles_total``)
``shard(params, pools, mesh, ...)``   a tp mesh's placements
``spec_verify``                       llama's own extra: a model without
                                      it lists ``spec`` as unsupported

The bodies below are the engine's former llama layer, moved (PR 28); the
q/k/v projections are one expression for every program (``_qkv``, PR 29).
"""
from __future__ import annotations

import math
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp

from ..kernels.paged_attention import (ragged_decode_partial,
                                       ragged_tpu_refusal)
from ..kernels.quant_matmul import (attn_pv, attn_qk, quantize_kv,
                                    weight_only_matmul as _wo_mm)
from ..observability import numerics as _nm
from .llama import (LlamaConfig, _apply_rope, _apply_rope_at, _attention,
                    _rms_norm, make_replicated_shardings,
                    make_serving_shardings)

__all__ = ["LlamaServed", "ServeOpts"]


class ServeOpts(NamedTuple):
    """What the engine fixes for one compiled program beyond the model."""
    kv_int8: bool = False
    numerics: bool = False
    ragged: bool = False
    prefix: str = ""
    mesh: object = None


class LlamaServed:
    cache_kind = "kv"
    unsupported: Dict[str, str] = {}
    state_entries = ()       # nothing is kept per slot beside the cache

    def __init__(self, config: LlamaConfig):
        self.config = config
        self.num_layers = config.num_layers
        self.vocab_size = config.vocab_size
        self.dtype = config.dtype

    # -- the cache -----------------------------------------------------------
    def make_pools(self, nb: int, bs: int, kv_int8: bool = False,
                   prefix: str = "") -> Dict:
        c = self.config
        shape = (c.num_layers, nb, bs, c.num_kv_heads, c.head_dim)
        if kv_int8:
            # int8 payload + f32 per-entry scales (~3% overhead at D=128)
            return {prefix + "k": jnp.zeros(shape, jnp.int8),
                    prefix + "v": jnp.zeros(shape, jnp.int8),
                    prefix + "ks": jnp.zeros(shape[:-1], jnp.float32),
                    prefix + "vs": jnp.zeros(shape[:-1], jnp.float32)}
        return {prefix + "k": jnp.zeros(shape, c.dtype),
                prefix + "v": jnp.zeros(shape, c.dtype)}

    def ragged_refusal(self, kv_int8: bool):
        return ragged_tpu_refusal(self.config.head_dim, kv_int8)

    @staticmethod
    def history_blocks(hist_blocks: int, mb: int) -> int:
        """The power of two over the row's history: the history is
        gathered dense, so its width is a program shape."""
        return (1 << (hist_blocks - 1).bit_length()) if hist_blocks else 0

    def shard(self, params, pools, mesh, draft_params=None):
        """tp serving (r19): target params shard Megatron-style, the KV
        pools shard over their kv-head axis; a draft stays replicated."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        c = self.config
        tp = dict(mesh.shape).get("tp", 1)
        if c.num_kv_heads % max(tp, 1):
            raise ValueError(
                f"tp={tp} must divide num_kv_heads={c.num_kv_heads}")
        params = jax.device_put(
            params, make_serving_shardings(params, c, mesh, fsdp=False))
        if draft_params is not None:
            draft_params = jax.device_put(
                draft_params, make_replicated_shardings(draft_params, mesh))
        pool_sh = NamedSharding(mesh, P(None, None, None, "tp", None))
        scale_sh = NamedSharding(mesh, P(None, None, None, "tp"))
        rep_sh = NamedSharding(mesh, P())
        pools = {k: jax.device_put(v, rep_sh if k.startswith("d")
                                   else pool_sh if v.ndim == 5 else scale_sh)
                 for k, v in pools.items()}
        return params, pools, draft_params

    # -- top of the model ----------------------------------------------------
    def embed(self, params, tokens):
        return params["embed"].astype(self.dtype)[tokens]

    def final_norm(self, params, x):
        return _rms_norm(x, params["final_norm"], self.config.rms_eps)

    def head(self, params, x):
        dt = self.dtype
        if self.config.tie_embeddings:
            return (x @ params["embed"].astype(dt).T).astype(jnp.float32)
        return _wo_mm(x, params["lm_head"], dt).astype(jnp.float32)

    def decode_head(self, params):
        """The dense head operand (incl. its dtype convert), hoisted out of
        the decode scan — XLA does not lift the loop-invariant
        [hidden, vocab] astype out of the body on its own. An int8
        weight-only lm_head has nothing to hoist: it contracts unconverted
        in-body (weight_only_matmul)."""
        dt = self.dtype
        if self.config.tie_embeddings:
            return params["embed"].astype(dt).T
        if not isinstance(params["lm_head"], dict):
            return params["lm_head"].astype(dt)
        return None

    def decode_logits(self, params, head_w, xf):
        if head_w is not None:
            return (xf @ head_w).astype(jnp.float32)
        return _wo_mm(xf, params["lm_head"], self.dtype).astype(jnp.float32)

    def _freq(self):
        c = self.config
        return c.rope_theta ** (-jnp.arange(0, c.head_dim, 2, jnp.float32)
                                / c.head_dim)

    def _qkv(self, hn, p):
        """The attention projections of ``hn`` [..., h], in heads:
        q [..., H, D], k and v [..., Hkv, D]. The barrier holds the three
        products [..., out] in the compiled program: without it XLA folds
        the reshape into heads (and rope's split of a head) back into the
        dot, wants the dot's output heads-major, and pays for that by
        transposing the WEIGHT, in every call (3.3 ms of a 12.8 ms decode
        step at Mistral-7B widths, PR 29).
        tests/test_aot_chip_compile_decode.py reads the compiled text."""
        c = self.config
        q, k, v = jax.lax.optimization_barrier(
            tuple(_wo_mm(hn, p[w], self.dtype) for w in ("wq", "wk", "wv")))
        kv_heads = hn.shape[:-1] + (c.num_kv_heads, c.head_dim)
        return (q.reshape(hn.shape[:-1] + (c.num_heads, c.head_dim)),
                k.reshape(kv_heads), v.reshape(kv_heads))

    def _mlp(self, x, p):
        c, dt = self.config, self.dtype
        hn = _rms_norm(x, p["mlp_norm"], c.rms_eps)
        gate = jax.nn.silu(_wo_mm(hn, p["w_gate"], dt))
        return x + _wo_mm(gate * _wo_mm(hn, p["w_up"], dt), p["w_down"], dt)

    # -- prefill -------------------------------------------------------------
    def prefill_begin(self, params, pools, tokens, true_len, hist_len,
                      ctx_tbl, prefix_nbk: int, opts: ServeOpts):
        c, dt = self.config, self.dtype
        pk, pv = opts.prefix + "k", opts.prefix + "v"
        B, S = tokens.shape
        bs = pools[pk].shape[2]
        freq = self._freq()
        aux = {"prefix_nbk": prefix_nbk}
        if prefix_nbk:
            Lc, Hkv, D = c.num_layers, c.num_kv_heads, c.head_dim
            Pp = prefix_nbk * bs
            # per-row absolute positions: row b's piece starts hist_len[b]
            # tokens into its sequence
            pos = (hist_len.astype(jnp.float32)[:, None]
                   + jnp.arange(S, dtype=jnp.float32)[None, :])
            ang = pos[:, :, None] * freq[None, None, :]        # [B, S, D/2]
            aux["cos"], aux["sin"] = jnp.cos(ang), jnp.sin(ang)
            # one dense gather of every row's history (the decode hoist,
            # applied to prefill); int8 pools dequantize here — prefill is
            # compute-bound, the simple form wins over fused-scale dots
            kpre = pools[pk][:, ctx_tbl].reshape(Lc, B, Pp, Hkv, D)
            vpre = pools[pv][:, ctx_tbl].reshape(Lc, B, Pp, Hkv, D)
            if opts.kv_int8:
                ksc = pools[pk + "s"][:, ctx_tbl].reshape(Lc, B, Pp, Hkv)
                vsc = pools[pv + "s"][:, ctx_tbl].reshape(Lc, B, Pp, Hkv)
                kpre = kpre.astype(dt) * ksc[..., None].astype(dt)
                vpre = vpre.astype(dt) * vsc[..., None].astype(dt)
            aux["kpre"], aux["vpre"] = kpre, vpre
            # [B,1,1,1,Pp] over scores [B,Hkv,G,S,Pp]
            aux["pre_mask"] = (jnp.arange(Pp)[None, :]
                               < hist_len[:, None])[:, None, None, None, :]
            aux["in_mask"] = jnp.tril(jnp.ones((S, S), bool))[None, None,
                                                             None]
        else:
            pos = jnp.arange(S, dtype=jnp.float32)
            ang = pos[:, None] * freq[None, :]
            aux["cos"], aux["sin"] = jnp.cos(ang), jnp.sin(ang)
        return aux

    def prefill_layer(self, params, l: int, x, aux, pools, opts: ServeOpts):
        c, dt = self.config, self.dtype
        B, S, _ = x.shape
        cos, sin = aux["cos"], aux["sin"]
        p = jax.tree_util.tree_map(lambda a: a[l], params["layers"])
        hn = _rms_norm(x, p["attn_norm"], c.rms_eps)
        q, k, v = self._qkv(hn, p)
        if aux["prefix_nbk"]:
            Hkv, D = c.num_kv_heads, c.head_dim
            G = c.num_heads // c.num_kv_heads
            scale = 1.0 / math.sqrt(D)
            kpre, vpre = aux["kpre"], aux["vpre"]
            Pp = kpre.shape[2]
            q = _apply_rope_at(q, cos, sin)
            k = _apply_rope_at(k, cos, sin)
            # piece attention: softmax over [history ; causal in-piece],
            # the decode program's concat structure at prefill width —
            # masked history positions contribute an exact 0.0
            qg = q.reshape(B, S, Hkv, G, D)
            s_pre = jnp.einsum("bshgd,bphd->bhgsp", qg, kpre[l],
                               preferred_element_type=jnp.float32) * scale
            s_in = jnp.einsum("bshgd,bthd->bhgst", qg, k,
                              preferred_element_type=jnp.float32) * scale
            s_pre = jnp.where(aux["pre_mask"], s_pre, -1e30)
            s_in = jnp.where(aux["in_mask"], s_in, -1e30)
            probs = jax.nn.softmax(
                jnp.concatenate([s_pre, s_in], axis=-1), axis=-1)
            att = (jnp.einsum("bhgsp,bphd->bshgd",
                              probs[..., :Pp].astype(dt), vpre[l])
                   + jnp.einsum("bhgst,bthd->bshgd",
                                probs[..., Pp:].astype(dt), v))
            att = att.reshape(B, S, c.num_heads * c.head_dim).astype(dt)
        else:
            q = _apply_rope(q, cos, sin)
            k = _apply_rope(k, cos, sin)
            # plain causal GQA attention — the model's own core
            # (llama._attention)
            att = _attention(q, k, v, c, opts.mesh).reshape(
                B, S, c.num_heads * c.head_dim)
        x = x + _wo_mm(att, p["wo"], dt)
        return self._mlp(x, p), {"k": k, "v": v}

    def pack_entries(self, new: Dict, opts: ServeOpts) -> Dict:
        """New cache rows ``{"k", "v"}`` (any leading shape, [..., Hkv, D])
        as the pools store them, under the pools' names."""
        pk, pv = opts.prefix + "k", opts.prefix + "v"
        if not opts.kv_int8:
            return {pk: new["k"], pv: new["v"]}
        qk, sk = quantize_kv(new["k"])
        qv, sv = quantize_kv(new["v"])
        if opts.numerics:
            # paired pre/post-quant probe for the int8-KV site: one tiny
            # fused reduction over these rows, shipped async — the
            # numerics_quant_error{site="kv_int8"} error budget
            _nm.record_quant_error("kv_int8", [(new["k"], qk, sk, -1),
                                               (new["v"], qv, sv, -1)])
        return {pk: qk, pv: qv, pk + "s": sk, pv + "s": sv}

    # -- decode --------------------------------------------------------------
    def ring_init(self, N: int, S: int, opts: ServeOpts) -> Dict:
        c = self.config
        shape = (c.num_layers, N, S, c.num_kv_heads, c.head_dim)
        return {"k": jnp.zeros(shape, c.dtype), "v": jnp.zeros(shape, c.dtype)}

    def decode_begin(self, params, pools, block_table, lens0, active,
                     n_steps: int, opts: ServeOpts):
        c = self.config
        pk, pv = opts.prefix + "k", opts.prefix + "v"
        Lc = c.num_layers
        N, MB = block_table.shape
        k_pool, v_pool = pools[pk], pools[pv]
        bs = k_pool.shape[2]
        Hkv, D = k_pool.shape[3], k_pool.shape[4]
        P = MB * bs
        aux = {"freq": self._freq(), "block_table": block_table}
        if opts.ragged:
            # true-length walk: no gather, no mask — the kernel reads only
            # real blocks. Slots outside the decode set (inactive or
            # mid-chunked-prefill) walk zero blocks.
            aux["walk_lens"] = jnp.where(active, lens0.astype(jnp.int32), 0)
        else:
            # ---- hoist: one dense gather of every slot's (frozen) prefix
            # (int8 pools: the dense arrays stay int8 — half the bytes)
            aux["kd"] = k_pool[:, block_table].reshape(Lc, N, P, Hkv, D)
            aux["vd"] = v_pool[:, block_table].reshape(Lc, N, P, Hkv, D)
            if opts.kv_int8:
                aux["ksc"] = pools[pk + "s"][:, block_table].reshape(
                    Lc, N, P, Hkv)
                aux["vsc"] = pools[pv + "s"][:, block_table].reshape(
                    Lc, N, P, Hkv)
            aux["pre_mask"] = (jnp.arange(P)[None, :]
                               < lens0[:, None])[:, None, None, :]  # [N,1,1,P]
        return aux

    def decode_step_begin(self, aux, lens, t, S: int):
        return {"ang": lens.astype(jnp.float32)[:, None]
                * aux["freq"][None, :],
                "ring_mask": (jnp.arange(S) <= t)[None, None, None, :]}

    def decode_layer(self, params, l: int, x, aux, step, ring, t, pools,
                     act, opts: ServeOpts):
        c, dt = self.config, self.dtype
        pk, pv = opts.prefix + "k", opts.prefix + "v"
        N = x.shape[0]
        Hkv, D = c.num_kv_heads, c.head_dim
        G = c.num_heads // c.num_kv_heads
        scale = 1.0 / math.sqrt(D)
        ang = step["ang"]
        rk, rv = ring["k"], ring["v"]

        def rope1(t_, ang):                  # t_: [N, H, D]; ang: [N, D/2]
            d2 = t_.shape[-1] // 2
            t1, t2 = t_[..., :d2], t_[..., d2:]
            cc = jnp.cos(ang)[:, None, :].astype(t_.dtype)
            ss = jnp.sin(ang)[:, None, :].astype(t_.dtype)
            return jnp.concatenate([t1 * cc - t2 * ss, t2 * cc + t1 * ss],
                                   -1)

        p = jax.tree_util.tree_map(lambda a: a[l], params["layers"])
        hn = _rms_norm(x, p["attn_norm"], c.rms_eps)
        q, kk, vv = self._qkv(hn[:, 0], p)
        q, kk = rope1(q, ang), rope1(kk, ang)
        # uniform step index: dynamic_update_slice, no scatter
        rk = jax.lax.dynamic_update_slice(
            rk, kk[None, :, None], (l, 0, t, 0, 0))
        rv = jax.lax.dynamic_update_slice(
            rv, vv[None, :, None], (l, 0, t, 0, 0))
        qg = q.reshape(N, Hkv, G, D)
        s_rng = jnp.einsum(
            "nhgd,nshd->nhgs", qg, rk[l],
            preferred_element_type=jnp.float32) * scale
        s_rng = jnp.where(step["ring_mask"], s_rng, -1e30)
        if opts.ragged:
            # flash-decoding combine: the kernel's online-softmax partials
            # over the pool prefix merge with the in-call ring's scores —
            # one softmax over [prefix ; ring], computed blockwise (exact
            # up to f32 rounding). The ring always holds >= 1 live
            # position, so l_tot >= 1.
            acc_p, m_p, l_p = ragged_decode_partial(
                q, pools[pk], pools[pv], aux["block_table"],
                aux["walk_lens"], layer=l, ks_pool=pools.get(pk + "s"),
                vs_pool=pools.get(pv + "s"), mesh=opts.mesh)
            m_tot = jnp.maximum(m_p, jnp.max(s_rng, axis=-1))
            corr = jnp.exp(m_p - m_tot)
            p_rng = jnp.exp(s_rng - m_tot[..., None])
            l_tot = l_p * corr + jnp.sum(p_rng, axis=-1)
            acc_tot = (acc_p * corr[..., None]
                       + jnp.einsum(
                           "nhgs,nshd->nhgd", p_rng, rv[l],
                           preferred_element_type=jnp.float32))
            att = acc_tot / l_tot[..., None]
        else:
            kv_int8 = opts.kv_int8
            P = aux["kd"].shape[2]
            s_pre = attn_qk(qg, aux["kd"][l],
                            aux["ksc"][l] if kv_int8 else None) * scale
            s_pre = jnp.where(aux["pre_mask"], s_pre, -1e30)
            probs = jax.nn.softmax(
                jnp.concatenate([s_pre, s_rng], axis=-1), axis=-1)
            p_rng = probs[..., P:].astype(dt)
            att = (attn_pv(probs[..., :P], aux["vd"][l],
                           aux["vsc"][l] if kv_int8 else None,
                           out_dtype=dt)
                   + jnp.einsum("nhgs,nshd->nhgd", p_rng, rv[l]))
        att = att.reshape(N, 1, Hkv * G * D).astype(dt)
        x = x + _wo_mm(att, p["wo"], dt)
        return self._mlp(x, p), {"k": rk, "v": rv}

    # -- llama's own extra ---------------------------------------------------
    def spec_verify(self, params, block_table, last, draft_toks, lengths,
                    active, pools, *, n_spec: int, kv_int8: bool = False,
                    numerics: bool = False, max_model_len: int = 0):
        """Score a speculative wave in ONE target forward: for every slot
        the piece ``[last, d_1 .. d_k]`` (k = ``n_spec``) runs a
        prefill-shaped pass against the slot's resident KV — the
        chunked-prefill program's structure (dense history gather over the
        power-of-two ``block_table`` bucket, per-row RoPE offsets at
        ``lengths``, softmax over [masked history ; causal in-piece]) at
        the fixed piece width k+1 — and returns the target's GREEDY token
        at ALL k+1 positions: ``out[b, j]`` is what the target would emit
        after consuming piece token j. The host accepts the longest prefix
        where the draft agreed (MPK's collapse-many-small-launches
        argument: k draft steps verify in one launch whose arithmetic
        intensity is prefill's, not decode's).

        Writeback is decode-shaped, not prefill-shaped: pieces start at
        ``lengths[b]``, which is NOT block-aligned mid-decode, so each
        position scatters individually via its (physical block, offset)
        pair. ALL k+1 positions write — a later host commit of c <= k
        tokens simply leaves positions >= lengths+c stale, which the
        length invariant makes unreadable and the next wave overwrites
        (that IS the rejected-suffix rollback). Inactive rows and
        positions past ``max_model_len`` divert to trash block 0.

        draft_toks: [k, N] (the draft call's emitted grid, fed back
        without a host round-trip); returns (greedy [N, k+1] int32,
        pools)."""
        c = self.config
        dt = c.dtype
        N, nbk = block_table.shape
        S = n_spec + 1
        bs = pools["k"].shape[2]
        Lc, Hkv, D = c.num_layers, c.num_kv_heads, c.head_dim
        G = c.num_heads // c.num_kv_heads
        Pp = nbk * bs
        scale = 1.0 / math.sqrt(D)

        tokens = jnp.concatenate(
            [last[:, None], draft_toks.T.astype(jnp.int32)], axis=1)
        tokens = jnp.clip(tokens, 0, c.vocab_size - 1)  # -1 pads embed-safe
        hist = jnp.where(active, lengths.astype(jnp.int32), 0)

        x = params["embed"].astype(dt)[tokens]
        freq = self._freq()
        pos = (hist.astype(jnp.float32)[:, None]
               + jnp.arange(S, dtype=jnp.float32)[None, :])
        ang = pos[:, :, None] * freq[None, None, :]       # [N, S, D/2]
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        pre_mask = (jnp.arange(Pp)[None, :]
                    < hist[:, None])[:, None, None, None, :]
        in_mask = jnp.tril(jnp.ones((S, S), bool))[None, None, None]

        k_all, v_all = [], []
        for l in range(Lc):
            p = jax.tree_util.tree_map(lambda a: a[l], params["layers"])
            hn = _rms_norm(x, p["attn_norm"], c.rms_eps)
            q, k, v = self._qkv(hn, p)
            q = _apply_rope_at(q, cos, sin)
            k = _apply_rope_at(k, cos, sin)
            k_all.append(k)
            v_all.append(v)
            # the prefill piece attention verbatim: int8 history
            # dequantizes up front (verify is prefill-shaped —
            # compute-bound, the simple form wins over fused-scale dots)
            kpre = pools["k"][l][block_table].reshape(N, Pp, Hkv, D)
            vpre = pools["v"][l][block_table].reshape(N, Pp, Hkv, D)
            if kv_int8:
                ksc = pools["ks"][l][block_table].reshape(N, Pp, Hkv)
                vsc = pools["vs"][l][block_table].reshape(N, Pp, Hkv)
                kpre = kpre.astype(dt) * ksc[..., None].astype(dt)
                vpre = vpre.astype(dt) * vsc[..., None].astype(dt)
            qg = q.reshape(N, S, Hkv, G, D)
            s_pre = jnp.einsum("bshgd,bphd->bhgsp", qg, kpre,
                               preferred_element_type=jnp.float32) * scale
            if kv_int8:
                # in-piece K/V BELOW the diagonal must read as the
                # step-wise decode path would read them: from the pool,
                # int8-quantized. Round-trip the piece through quantize_kv
                # (the exact writeback transform) for t < s; the diagonal
                # (each position's own K/V — the decode ring) stays raw.
                # Without this, verify attends unquantized neighbors and
                # the ~1% quant delta can flip near-tie argmaxes vs the
                # non-speculative stream.
                qk_p, sk_p = quantize_kv(k)
                qv_p, sv_p = quantize_kv(v)
                k_rt = qk_p.astype(dt) * sk_p[..., None].astype(dt)
                v_rt = qv_p.astype(dt) * sv_p[..., None].astype(dt)
            else:
                k_rt, v_rt = k, v
            s_in = jnp.einsum("bshgd,bthd->bhgst", qg, k_rt,
                              preferred_element_type=jnp.float32) * scale
            if kv_int8:
                eye = jnp.eye(S, dtype=bool)[None, None, None]
                s_diag = jnp.einsum("bshgd,bshd->bhgs", qg, k,
                                    preferred_element_type=jnp.float32) \
                    * scale
                s_in = jnp.where(eye, s_diag[..., None], s_in)
            s_pre = jnp.where(pre_mask, s_pre, -1e30)
            s_in = jnp.where(in_mask, s_in, -1e30)
            probs = jax.nn.softmax(
                jnp.concatenate([s_pre, s_in], axis=-1), axis=-1)
            p_in = probs[..., Pp:].astype(dt)
            if kv_int8:
                eye_f = jnp.eye(S, dtype=p_in.dtype)[None, None, None]
                att_in = (jnp.einsum("bhgst,bthd->bshgd",
                                     p_in * (1 - eye_f), v_rt)
                          + jnp.einsum("bhgs,bshd->bshgd",
                                       jnp.sum(p_in * eye_f, -1), v))
            else:
                att_in = jnp.einsum("bhgst,bthd->bshgd", p_in, v)
            att = jnp.einsum("bhgsp,bphd->bshgd",
                             probs[..., :Pp].astype(dt), vpre) + att_in
            att = att.reshape(N, S, c.num_heads * D).astype(dt)
            x = x + _wo_mm(att, p["wo"], dt)
            x = self._mlp(x, p)

        # positional writeback (the decode ring's scatter at piece width):
        # invalid lanes — inactive rows, positions past max_model_len —
        # divert to the trash block
        j = jnp.arange(S)[None, :]
        wpos = hist[:, None] + j                              # [N, S]
        valid = active[:, None] & (wpos < max_model_len)
        wposc = jnp.minimum(wpos, max_model_len - 1)
        log_blk = jnp.minimum(wposc // bs, nbk - 1)
        phys = jnp.take_along_axis(block_table, log_blk, axis=1)
        phys = jnp.where(valid, phys, 0)
        off = wposc % bs
        new = {"k": jnp.stack(k_all), "v": jnp.stack(v_all)}  # [L,N,S,Hkv,D]
        pools = dict(pools)
        for name, val in self.pack_entries(
                new, ServeOpts(kv_int8=kv_int8, numerics=numerics)).items():
            pools[name] = pools[name].at[:, phys, off].set(val)

        x = self.final_norm(params, x)
        return jnp.argmax(self.head(params, x), axis=-1).astype(jnp.int32), \
            pools
