"""Plain reference for the LFM2 sparse-expert decoder (``lfm2_moe``):
float32 ``jax.numpy``.

Follows the published architecture (``config.json`` of
LiquidAI/LFM2-8B-A1B and the family's modelling code) equation by
equation, with no kernel, cache, state, batching or packing, and imports
nothing of the program. Matmuls run at ``highest`` precision (the caller
sets it).

``rms(x; g) = x / sqrt(mean(x^2) + norm_eps) * g``. A layer ``l`` of
``layer_types``: ``x <- x + Op_l(rms(x; op_norm))``, ``x <- x +
FFN_l(rms(x; ffn_norm))``. No biases anywhere (``conv_bias`` false).

conv    the gated short convolution, ``conv_L_cache`` 3: ``[B ; C ; x~] =
        h W_in`` (h -> 3h, split in that order); ``u = B (.) x~``; ``c_t =
        w_0 (.) u_{t-2} + w_1 (.) u_{t-1} + w_2 (.) u_t`` (depthwise,
        causal, per channel; ``u`` before the sequence's start is 0; ``w``
        is [h, 3]); ``y = (C (.) c) W_out``. No activation: the two gates
        are the nonlinearity.
attn    ``full_attention``: ``q, k, v = h W_q, h W_k, h W_v`` in heads of
        ``hidden / num_attention_heads``; ``q <- rms(q; q_norm)``, ``k <-
        rms(k; k_norm)`` over each head's channels, one weight vector for
        q and one for k; rope on all the head's dims, ``rope_theta``, the
        half-split pairing (channel i with i + d/2); causal softmax of
        ``q . k / sqrt(d)``, query head h on KV head ``h // (H / H_kv)``;
        ``o = concat(heads) W_o``.
FFN     ``l < num_dense_layers``: SwiGLU ``W_down(silu(W_gate h) (.) W_up
        h)`` of width ``intermediate_size``. Otherwise ``s =
        sigmoid(h W_r)`` over ``num_experts``; ``sel = top-k(s + b)`` with
        ``b`` the expert bias, which enters the SELECTION only
        (``use_expert_bias``; ties to the lower index); ``g_e = s_e /
        (sum_{e in sel} s_e + 1e-6)`` (``norm_topk_prob``), times
        ``routed_scaling_factor``; ``y = sum_{e in sel} g_e Expert_e(h)``,
        ``Expert_e`` a SwiGLU of width ``moe_intermediate_size``. No
        shared expert, no dropped token.
head    one more ``rms`` after the last layer (``final_norm``; the
        published code calls it ``embedding_norm``), then ``logits = x
        Embed^T`` (tied).

Departures from a textbook forward, none of them numerical: attention runs
in blocks of query rows, and the experts are visited one at a time over
all tokens with the gate as a mask (dense over the experts: ``num_experts
/ k`` times the routed FLOPs, and no sort to get wrong). ``quant="int8"``
gives the control of the ``correct`` check: every matmul weight rounded to
int8 per output channel.
"""
from __future__ import annotations

import math
from typing import Dict, Optional

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


def head_dim(m: Dict) -> int:
    return m["hidden_size"] // m["num_attention_heads"]


def rope_half(x, theta: float):
    """Rotate x [B, S, H, d]: channel i with channel i + d/2."""
    S, d = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], -1)


def attention(hn, p, m: Dict, quant):
    B, S, _ = hn.shape
    H, Hkv, d = m["num_attention_heads"], m["num_key_value_heads"], head_dim(m)
    eps = m["norm_eps"]
    q = (hn @ _w(p, "wq", quant)).reshape(B, S, H, d)
    k = (hn @ _w(p, "wk", quant)).reshape(B, S, Hkv, d)
    v = (hn @ _w(p, "wv", quant)).reshape(B, S, Hkv, d)
    q = rope_half(rms_norm(q, p["q_norm"], eps), float(m["rope_theta"]))
    k = rope_half(rms_norm(k, p["k_norm"], eps), float(m["rope_theta"]))
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    blk = ATTN_BLOCK if S % ATTN_BLOCK == 0 else S
    qb = q.reshape(B, S // blk, blk, H, d)
    pos = jnp.arange(S)

    def one(i):
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb[:, i], k) / math.sqrt(d)
        mask = (i * blk + jnp.arange(blk))[:, None] >= pos[None, :]
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    out = jax.lax.map(one, jnp.arange(S // blk))         # [nb, B, blk, H, d]
    out = jnp.moveaxis(out, 0, 1).reshape(B, S, H * d)
    return out @ _w(p, "wo", quant)


def short_conv(hn, p, m: Dict, quant):
    h = m["hidden_size"]
    bcx = hn @ _w(p, "w_in", quant)
    b, c, x = bcx[..., :h], bcx[..., h:2 * h], bcx[..., 2 * h:]
    u = b * x
    w = p["conv_w"].astype(jnp.float32)                           # [h, 3]
    u1 = jnp.pad(u, ((0, 0), (1, 0), (0, 0)))[:, :-1]             # u_{t-1}
    u2 = jnp.pad(u, ((0, 0), (2, 0), (0, 0)))[:, :-2]             # u_{t-2}
    conv = w[:, 0] * u2 + w[:, 1] * u1 + w[:, 2] * u
    return (c * conv) @ _w(p, "w_out", quant)


def route(scores, bias, top_k: int, renorm: bool, scale: float):
    """The gate of every expert, [T, E]: the chosen experts' unbiased
    scores (renormalised over the chosen), 0 for the rest. The bias moves
    the selection only; ties go to the lower index."""
    E = scores.shape[1]
    _, idx = jax.lax.top_k(scores + bias[None, :], top_k)
    chosen = jnp.any(idx[:, :, None] == jnp.arange(E)[None, None, :], axis=1)
    g = jnp.where(chosen, scores, 0.0)
    if renorm:
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-6)
    return g * scale


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def moe(hn, p, m: Dict, quant):
    B, S, h = hn.shape
    x = hn.reshape(B * S, h)
    scores = jax.nn.sigmoid(x @ _w(p, "router", quant))
    bias = (p["expert_bias"].astype(jnp.float32) if m["use_expert_bias"]
            else jnp.zeros((m["num_experts"],), jnp.float32))
    gates = route(scores, bias, m["num_experts_per_tok"],
                  bool(m["norm_topk_prob"]),
                  float(m["routed_scaling_factor"]))
    eg, eu, ed = (_w(p, n, quant) for n in ("e_gate", "e_up", "e_down"))

    def one(y, e):
        return y + gates[:, e, None] * swiglu(x, eg[e], eu[e], ed[e]), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(m["num_experts"]))
    return y.reshape(B, S, h)


def layer(x, p, m: Dict, quant: Optional[str] = None, l: int = 0):
    """Layer ``l`` on x [B, S, h] float32; ``p`` holds its matrices in the
    published layout."""
    eps = m["norm_eps"]
    hn = rms_norm(x, p["op_norm"], eps)
    if m["layer_types"][l] == "conv":
        x = x + short_conv(hn, p, m, quant)
    else:
        x = x + attention(hn, p, m, quant)
    hn = rms_norm(x, p["ffn_norm"], eps)
    if l < m["num_dense_layers"]:
        return x + swiglu(hn, _w(p, "w_gate", quant), _w(p, "w_up", quant),
                          _w(p, "w_down", quant))
    return x + moe(hn, p, m, quant)


def head_logits(x, params, m: Dict, quant: Optional[str] = None):
    x = rms_norm(x, params["final_norm"], m["norm_eps"])
    emb = params["embed"].astype(jnp.float32)
    if quant == "int8":
        # the head's matrix is Embed^T: rounded per output channel (a row
        # of the embedding)
        emb = jnp.swapaxes(fake_int8(jnp.swapaxes(emb, 0, 1)), 0, 1)
    return x @ emb.T
