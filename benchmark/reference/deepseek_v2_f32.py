"""Plain reference for the DeepSeek-V2 decoder: float32 ``jax.numpy``.

Follows the published architecture (``config.json`` and the modelling code
of deepseek-ai/DeepSeek-V2) equation by equation, with no kernel, cache,
batching or absorbed form, and imports nothing of the program. Matmuls run
at ``highest`` precision (the caller sets it).

A layer: ``x <- x + MLA(rms(x))``, ``x <- x + FFN_l(rms(x))``.

MLA     ``c_q = rms(x W_DQ)``; ``q = c_q W_UQ`` -> heads x [q_nope ;
        q_rope]. ``[c_kv ; k_r] = x W_DKV``; ``c_kv <- rms(c_kv)``;
        ``k_r <- rope(k_r)``, one row for all heads; ``q_rope <-
        rope(q_rope)``. ``[k_nope ; v]_h = c_kv W_UKV`` per head; ``k_h =
        [k_nope_h ; k_r]``. Causal softmax of ``q_h . k_h * s`` with ``s =
        (nope + rope)^-0.5 * m^2``, ``m = 0.1 * mscale_all_dim * ln(factor)
        + 1``; ``o = concat_h(softmax . v_h) W_O``. No biases.
YaRN    on the rope dims: ``f_i = theta^(-2i/d)``; ``low, high =
        floor/ceil(d ln(orig / (beta 2 pi)) / (2 ln theta))`` for beta_fast,
        beta_slow, clipped to [0, d - 1]; ``ramp_i = clip((i - low) / (high
        - low), 0, 1)``; ``inv_freq_i = (f_i / factor) ramp_i + f_i (1 -
        ramp_i)``; cos and sin times ``yarn_mscale(factor, mscale) /
        yarn_mscale(factor, mscale_all_dim)``. The rotation pairs channels
        (2i, 2i+1) of the stored layout, as the published code does.
FFN     layer l < first_k_dense_replace: SwiGLU of width
        ``intermediate_size``. Otherwise ``Shared(x) + sum_{e in top-k(x)}
        g_e(x) Expert_e(x)``: ``p = softmax(x W_r)`` over the router's
        width; a group's score is the max of ``p`` within it (``n_group``
        groups of consecutive experts); the best ``topk_group`` groups
        stay, the rest are masked to 0; top-k of what is left (ties to the
        lower index); ``g_e = routed_scaling_factor * p_e``, not
        renormalised. No token is dropped.
share   ``held = (first, count)``: the sum runs over the held experts
        only, ``Shared`` is whole: one chip's part of a layer whose routed
        experts are spread over chips (guide section 4). With ``held``
        covering the router's width this is the uncut layer. A
        configuration states its share as ``held_first`` and
        ``n_routed_experts`` (the count) beside ``router_width``.

Departures from a textbook forward, none of them numerical: attention runs
in blocks of query rows, and the held experts are visited one at a time
over all tokens with the gate as a mask (dense over the share: ``count``
times the routed FLOPs, and no sort to get wrong). ``quant="int8"`` gives
the control of the ``correct`` check: every matmul weight rounded to int8
per output channel.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

# sequences are padded to a multiple of Q_BLOCK by the comparison: a coarse
# grid, so that the padded lengths (program shapes) repeat from run to run
# and the persistent cache holds them; attention works in ATTN_BLOCK rows
Q_BLOCK = 2048
ATTN_BLOCK = 128


def fake_int8(w):
    """Round a [..., K, N] weight to int8 per output channel, in float32."""
    s = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
    return jnp.clip(jnp.round(w / jnp.maximum(s, 1e-30)), -127, 127) * s


def _w(p, name, quant):
    w = p[name].astype(jnp.float32)
    return fake_int8(w) if quant == "int8" else w


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_tables(m: Dict, S: int):
    """cos, sin [S, d/2] of the YaRN-scaled rope over the rope dims."""
    rs, d, theta = m["rope_scaling"], m["qk_rope_head_dim"], m["rope_theta"]
    f = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)

    def corr(beta):
        return (d * math.log(rs["original_max_position_embeddings"]
                             / (beta * 2 * math.pi)) / (2 * math.log(theta)))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    inv = f / rs["factor"] * ramp + f * (1.0 - ramp)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    ms = (yarn_mscale(rs["factor"], rs["mscale"])
          / yarn_mscale(rs["factor"], rs["mscale_all_dim"]))
    return jnp.cos(ang) * ms, jnp.sin(ang) * ms


def rope_pairs(x, cos, sin):
    """Rotate channel pairs (2i, 2i+1) of x [B, S, ..., d]; cos/sin
    [S, d/2]."""
    shape = x.shape
    x = x.reshape(shape[:-1] + (shape[-1] // 2, 2))
    a, b = x[..., 0], x[..., 1]
    ex = (None, slice(None)) + (None,) * (a.ndim - 3) + (slice(None),)
    c, s = cos[ex], sin[ex]
    return jnp.stack([a * c - b * s, b * c + a * s], -1).reshape(shape)


def softmax_scale(m: Dict) -> float:
    rs = m["rope_scaling"]
    ms = yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    return (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) ** -0.5 * ms * ms


def attention(q, k, v, scale):
    """Causal attention, q/k [B, S, H, Dk], v [B, S, H, Dv], in blocks of
    query rows."""
    B, S, H, _ = q.shape
    blk = ATTN_BLOCK if S % ATTN_BLOCK == 0 else S
    qb = q.reshape(B, S // blk, blk, H, q.shape[-1])
    pos = jnp.arange(S)

    def one(i):
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb[:, i], k) * scale
        mask = (i * blk + jnp.arange(blk))[:, None] >= pos[None, :]
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    out = jax.lax.map(one, jnp.arange(S // blk))        # [nb, B, blk, H, Dv]
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, v.shape[-1])


def mla(hn, p, m: Dict, quant):
    B, S, _ = hn.shape
    H, dn, dr, dv = (m["num_attention_heads"], m["qk_nope_head_dim"],
                     m["qk_rope_head_dim"], m["v_head_dim"])
    r, eps = m["kv_lora_rank"], m["rms_norm_eps"]
    cos, sin = yarn_tables(m, S)
    cq = rms_norm(hn @ _w(p, "w_dq", quant), p["q_norm"], eps)
    q = (cq @ _w(p, "w_uq", quant)).reshape(B, S, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], rope_pairs(q[..., dn:], cos, sin)], -1)
    ckv = hn @ _w(p, "w_dkv", quant)
    lat = rms_norm(ckv[..., :r], p["kv_norm"], eps)
    k_r = rope_pairs(ckv[..., r:], cos, sin)                     # [B, S, dr]
    kv = (lat @ _w(p, "w_ukv", quant)).reshape(B, S, H, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r[:, :, None], (B, S, H, dr))], -1)
    o = attention(q, k, kv[..., dn:], softmax_scale(m))
    return o.reshape(B, S, H * dv) @ _w(p, "w_o", quant)


def route(probs, n_group: int, topk_group: int, top_k: int, scale: float):
    """The gate of every expert of the router's width, [T, E]: ``scale *
    p_e`` for the chosen ones, 0 for the rest (group-limited greedy, ties
    to the lower index)."""
    T, E = probs.shape
    per = E // n_group
    group = probs.reshape(T, n_group, per).max(axis=-1)
    _, keep = jax.lax.top_k(group, topk_group)
    kept = jnp.any(keep[:, :, None] == jnp.arange(n_group)[None, None, :],
                   axis=1)                                       # [T, G]
    masked = jnp.where(jnp.repeat(kept, per, axis=1), probs, 0.0)
    _, idx = jax.lax.top_k(masked, top_k)
    chosen = jnp.any(idx[:, :, None] == jnp.arange(E)[None, None, :], axis=1)
    return jnp.where(chosen, probs * scale, 0.0)


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def share_of(m: Dict) -> Tuple[int, int]:
    """(first, count) of the experts a configuration holds."""
    return int(m.get("held_first", 0)), int(m["n_routed_experts"])


def moe(hn, p, m: Dict, quant, held: Optional[Tuple[int, int]] = None):
    """Shared(x) + the routed sum over the held experts. ``p`` holds the
    router over its whole width and ``e_gate``/``e_up``/``e_down`` stacked
    over the held experts only."""
    B, S, h = hn.shape
    first, count = held if held is not None else share_of(m)
    x = hn.reshape(B * S, h)
    probs = jax.nn.softmax(x @ _w(p, "router", quant), axis=-1)
    gates = route(probs, m["n_group"], m["topk_group"],
                  m["num_experts_per_tok"], float(m["routed_scaling_factor"]))
    gates = gates[:, first:first + count]                        # [T, count]
    eg, eu, ed = (_w(p, n, quant) for n in ("e_gate", "e_up", "e_down"))

    def one(y, e):
        return y + gates[:, e, None] * swiglu(x, eg[e], eu[e], ed[e]), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(count))
    y = y + swiglu(x, _w(p, "s_gate", quant), _w(p, "s_up", quant),
                   _w(p, "s_down", quant))
    return y.reshape(B, S, h)


def layer(x, p, m: Dict, quant: Optional[str] = None, l: int = 0,
          held: Optional[Tuple[int, int]] = None):
    """Layer ``l`` on x [B, S, h] float32; ``p`` holds its matrices in the
    published layout."""
    eps = m["rms_norm_eps"]
    x = x + mla(rms_norm(x, p["attn_norm"], eps), p, m, quant)
    hn = rms_norm(x, p["mlp_norm"], eps)
    if l < m["first_k_dense_replace"]:
        return x + swiglu(hn, _w(p, "w_gate", quant), _w(p, "w_up", quant),
                          _w(p, "w_down", quant))
    return x + moe(hn, p, m, quant, held)


def head_logits(x, params, m: Dict, quant: Optional[str] = None):
    x = rms_norm(x, params["final_norm"], m["rms_norm_eps"])
    return x @ _w(params, "lm_head", quant)
