"""Plain float32 reference of the ``renamed`` test family: the Llama
equations (pre-norm RMSNorm, rotate-half rotary embeddings, grouped-query
causal attention, SwiGLU, untied head) read from a configuration whose keys
have other names, written out in full so that it shares no code with
``benchmark/reference/llama_f32.py``. The FFN width is taken from the
layer's own matrices, so a first layer of another width runs the same
equations. There is no lower-precision control here."""
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 8
TRACED_AT = []      # the layer index of every trace of ``layer``


def _f32(w):
    return w.astype(jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(w)


def rope(x, base):
    S, D = x.shape[1], x.shape[-1]
    freq = base ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq[None, :]
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def embed(tokens, top):
    return _f32(top["embed"])[tokens]


def layer(x, p, m, quant, l):
    if quant is not None:
        raise ValueError("the renamed family has no control")
    TRACED_AT.append(l)
    B, S, _ = x.shape
    d, nq, nkv = m["d_head"], m["n_heads"], m["n_kv_heads"]
    hn = rms_norm(x, p["attn_norm"], m["norm_eps"])
    q = rope((hn @ _f32(p["wq"])).reshape(B, S, nq, d), m["rope_base"])
    k = rope((hn @ _f32(p["wk"])).reshape(B, S, nkv, d), m["rope_base"])
    v = (hn @ _f32(p["wv"])).reshape(B, S, nkv, d)
    k, v = (jnp.repeat(t, nq // nkv, axis=2) for t in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, S, nq * d)
    x = x + att @ _f32(p["wo"])
    hn = rms_norm(x, p["mlp_norm"], m["norm_eps"])
    return x + (jax.nn.silu(hn @ _f32(p["w_gate"]))
                * (hn @ _f32(p["w_up"]))) @ _f32(p["w_down"])


def head_logits(x, top, m, quant=None):
    return rms_norm(x, top["final_norm"], m["norm_eps"]) @ _f32(top["lm_head"])
