"""Plain reference for the Llama/Mistral decoder: float32 ``jax.numpy``.

Follows the published architecture (Mistral-7B-v0.3 ``config.json`` and the
Llama equations it shares: pre-norm RMSNorm, rotary embeddings in the
rotate-half convention of the HF implementation, grouped-query causal
attention, SwiGLU, untied output head), with no kernel, cache or batching,
and imports nothing of the program. Matmuls run at ``highest`` precision:
on a TPU a float32 matmul is otherwise a single bf16 pass. The float32
comparison in ``chip_smoke.py`` (PR 21) is where this started.

Departures from a textbook forward, none of them numerical: attention is
computed in blocks of query rows so that the scores of a long sequence fit
the device, and the training loss scans the layers under
``jax.checkpoint``. ``quant="int8"`` gives the control of the ``correct``
check: the same mathematics with every matmul weight rounded to int8 per
output channel (the program's ``quantize_params`` recipe), the step a
later PR would be tempted by.
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

Q_BLOCK = 512


def fake_int8(w):
    """Round a [K, N] weight to int8 per output channel, back in float32;
    the gradient passes straight through."""
    s = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
    q = jnp.clip(jnp.round(w / jnp.maximum(s, 1e-30)), -127, 127) * s
    return w + jax.lax.stop_gradient(q - w)


def _w(p, name, quant):
    w = p[name].astype(jnp.float32)
    return fake_int8(w) if quant == "int8" else w


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def rope(x, theta):
    """x: [B, S, H, D]; positions 0..S-1, rotate-half."""
    S, D = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq[None, :]
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(q, k, v):
    """Causal grouped-query attention, [B, S, H, D], in blocks of queries."""
    B, S, H, D = q.shape
    g = H // k.shape[2]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    blk = Q_BLOCK if S % Q_BLOCK == 0 else S
    qb = q.reshape(B, S // blk, blk, H, D)
    pos = jnp.arange(S)

    @jax.checkpoint     # a gradient keeps one block's scores, not all
    def one(i):
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb[:, i], k) / math.sqrt(D)
        mask = (i * blk + jnp.arange(blk))[:, None] >= pos[None, :]
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    out = jax.lax.map(one, jnp.arange(S // blk))        # [nb, B, blk, H, D]
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, D)


def layer(x, p, m: Dict, quant: Optional[str] = None):
    """One decoder layer on x [B, S, h] float32; ``p`` holds its matrices."""
    B, S, _ = x.shape
    d, nq, nkv = m["head_dim"], m["num_attention_heads"], \
        m["num_key_value_heads"]
    hn = rms_norm(x, p["attn_norm"], m["rms_norm_eps"])
    q = (hn @ _w(p, "wq", quant)).reshape(B, S, nq, d)
    k = (hn @ _w(p, "wk", quant)).reshape(B, S, nkv, d)
    v = (hn @ _w(p, "wv", quant)).reshape(B, S, nkv, d)
    att = attention(rope(q, m["rope_theta"]), rope(k, m["rope_theta"]), v)
    x = x + att.reshape(B, S, nq * d) @ _w(p, "wo", quant)
    hn = rms_norm(x, p["mlp_norm"], m["rms_norm_eps"])
    gate = jax.nn.silu(hn @ _w(p, "w_gate", quant))
    return x + (gate * (hn @ _w(p, "w_up", quant))) @ _w(p, "w_down", quant)


def head_logits(x, params, m: Dict, quant: Optional[str] = None):
    x = rms_norm(x, params["final_norm"], m["rms_norm_eps"])
    return x @ _w(params, "lm_head", quant)


def loss(params, tokens, m: Dict, quant: Optional[str] = None,
         constrain=lambda x: x, gather=lambda p: p):
    """Mean next-token cross-entropy of rows [B, S + 1]; the layers are
    stacked on a leading axis. Where the caller spreads the work over
    devices, ``constrain`` pins the activations' layout and ``gather``
    brings a layer's weights together where they are used."""
    top = gather({k: v for k, v in params.items() if k != "layers"})
    x = constrain(top["embed"].astype(jnp.float32)[tokens[:, :-1]])

    @jax.checkpoint
    def body(x, p):
        return constrain(layer(x, gather(p), m, quant)), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    logits = head_logits(x, top, m, quant)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def adamw(p, g, mu, nu, t, hp: Dict, scale):
    """One AdamW update of a leaf (Loshchilov & Hutter, decoupled decay)
    at step ``t`` (from 1), the gradient already scaled by the clip."""
    g = g * scale
    mu = hp["beta1"] * mu + (1 - hp["beta1"]) * g
    nu = hp["beta2"] * nu + (1 - hp["beta2"]) * g * g
    u = (mu / (1 - hp["beta1"] ** t)) / (
        jnp.sqrt(nu / (1 - hp["beta2"] ** t)) + hp["eps"])
    return p - hp["lr"] * (u + hp["weight_decay"] * p), mu, nu


def clip_scale(grads, clip_norm: float):
    gnorm = jnp.sqrt(sum(jnp.sum(g * g)
                         for g in jax.tree_util.tree_leaves(grads)))
    return jnp.minimum(1.0, clip_norm / (gnorm + 1e-6))
