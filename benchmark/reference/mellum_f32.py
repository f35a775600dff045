"""Plain reference for the Mellum2 sparse-expert decoder (``mellum``):
float32 ``jax.numpy``.

Follows the published configuration (``config.json`` of
JetBrains/Mellum2-12B-A2.5B-Instruct) equation by equation, with no kernel,
cache, ring, batching or packing, and imports nothing of the program.
Matmuls run at ``highest`` precision (the caller sets it).

``rms(x; g) = x / sqrt(mean(x^2) + rms_norm_eps) * g``. A layer ``l`` of
``layer_types``: ``x <- x + Attn_l(rms(x; attn_norm))``, ``x <- x +
MoE(rms(x; ffn_norm))``. No biases (``attention_bias`` false).

attn    both kinds: ``q, k, v = h W_q, h W_k, h W_v`` in heads of
        ``head_dim`` (32 query heads on 4 KV heads: query head h reads KV
        head ``h // 8``); ``q <- rms(q; q_norm)``, ``k <- rms(k;
        k_norm)`` over each head's channels, one weight vector for q and
        one for k (ASSUMED: the config has no key for it either way; the
        configuration file says why); rope on all the head's dims, the
        half-split pairing (channel i with i + d/2); softmax of ``q . k /
        sqrt(d)`` under the layer's mask; ``o = concat(heads) W_o``.
sliding_attention   rope with ``inv_freq_i = theta^(-2i/d)``; mask ``j <=
        i`` and ``i - j < sliding_window``.
full_attention      causal mask; YaRN: ``f_i = theta^(-2i/d)``, ``ramp_i =
        clip((i - low) / (high - low), 0, 1)`` with ``low = floor(d ln(L /
        (beta_fast 2 pi)) / (2 ln theta))``, ``high = ceil(d ln(L /
        (beta_slow 2 pi)) / (2 ln theta))`` (L the original context), both
        held inside [0, d/2 - 1]; ``inv_freq_i = (f_i / factor) ramp_i +
        f_i (1 - ramp_i)``; cos and sin both times ``attention_factor``.
MoE     every layer (``mlp_layer_types`` all sparse): ``p = softmax(h
        W_r)`` in float32 over ``num_experts``; ``sel = top-k(p)`` (ties to
        the lower index); ``g_e = p_e / sum_{e in sel} p_e``
        (``norm_topk_prob``); ``y = sum_{e in sel} g_e Expert_e(h)``,
        ``Expert_e`` a SwiGLU of width ``moe_intermediate_size``. No
        shared expert, no bias, no scale, no dropped token.
head    one more ``rms`` after the last layer, then ``logits = x
        W_head^T`` (``tie_word_embeddings`` false).

Departures from a textbook forward, none of them numerical: attention runs
in blocks of query rows, each over the stretch of keys that its layer's mask
can let it see (all of them in a full layer; the block's own rows and the
``sliding_window`` before them in a window layer: what lies before is masked
for every row of the block), so a 33k-position check fits and a window
layer's cost does not grow with the context squared; and the experts are
visited with the (token, choice) pairs laid out expert by expert
(``jax.lax.ragged_dot``: one matmul a group, eight of 64 experts' work a
token and not all 64), every token then taking its eight rows back and
summing them under their gates. ``quant="int8"`` gives the control of the ``correct`` check:
every matmul weight rounded to int8 per output channel. Three more values
of ``quant`` are PLANTED FAULTS, for setting the check's limits only
(``PERF.md`` section 6; no run of the benchmark passes them): the weights
stay float32 and ``"whole_history"`` lets a window layer attend to all of
its history, ``"one_block"`` to one block of 16 tokens too much,
``"no_yarn"`` leaves YaRN's attention factor off the full layers.
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

# sequences are padded to a multiple of Q_BLOCK by the comparison (the padded
# lengths are program shapes, which repeat from run to run). A fine grid: the
# comparison holds the float32 logits of a WHOLE sequence, 393 KB a position,
# 13.3 GB for the 33,792 positions of the longest request, beside the hidden
# states of every other sampled request; a grid of 4,096 would pad that one
# to 14.5 GB. Attention works in ATTN_BLOCK rows
Q_BLOCK = 1024
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


def inv_freq(m: Dict, kind: str):
    """(inverse frequencies [d/2], factor on cos and sin) of a layer type,
    from ``rope_parameters``."""
    rp = m["rope_parameters"][kind]
    d = m["head_dim"]
    theta = float(rp["rope_theta"])
    idx = jnp.arange(d // 2, dtype=jnp.float32)
    f = theta ** (-2.0 * idx / d)
    if rp["rope_type"] == "default":
        return f, 1.0
    if rp["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rp['rope_type']!r}")

    def corr(beta):
        return (d * math.log(rp["original_max_position_embeddings"]
                             / (beta * 2 * math.pi))
                / (2 * math.log(theta)))

    low = min(max(math.floor(corr(rp["beta_fast"])), 0), d // 2 - 1)
    high = min(max(math.ceil(corr(rp["beta_slow"])), 0), d // 2 - 1)
    ramp = jnp.clip((idx - low) / max(high - low, 0.001), 0.0, 1.0)
    return (f / rp["factor"]) * ramp + f * (1.0 - ramp), \
        float(rp["attention_factor"])


def rope_half(x, inv, factor: float):
    """Rotate x [B, S, H, d]: channel i with channel i + d/2."""
    S, d = x.shape[1], x.shape[-1]
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    c = (jnp.cos(ang) * factor)[None, :, None, :]
    s = (jnp.sin(ang) * factor)[None, :, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], -1)


def attention(hn, p, m: Dict, quant, kind: str):
    B, S, _ = hn.shape
    H, Hkv, d = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    eps = m["rms_norm_eps"]
    q = (hn @ _w(p, "wq", quant)).reshape(B, S, H, d)
    k = (hn @ _w(p, "wk", quant)).reshape(B, S, Hkv, d)
    v = (hn @ _w(p, "wv", quant)).reshape(B, S, Hkv, d)
    inv, factor = inv_freq(m, kind)
    if quant == "no_yarn":
        factor = 1.0
    q = rope_half(rms_norm(q, p["q_norm"], eps), inv, factor)
    k = rope_half(rms_norm(k, p["k_norm"], eps), inv, factor)
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    blk = ATTN_BLOCK if S % ATTN_BLOCK == 0 else S
    qb = q.reshape(B, S // blk, blk, H, d)
    window = m["sliding_window"] if kind == "sliding_attention" else S
    if quant in ("whole_history", "one_block"):
        window = S if quant == "whole_history" else window + 16
    # keys before row - window are masked for every row of a block: a
    # block reads the ``span`` keys that end with its own last row
    span = min(S, window + blk)

    def one(i):
        lo = jnp.clip((i + 1) * blk - span, 0, S - span)
        ks = jax.lax.dynamic_slice_in_dim(k, lo, span, 1)
        vs = jax.lax.dynamic_slice_in_dim(v, lo, span, 1)
        pos = lo + jnp.arange(span)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb[:, i], ks) / math.sqrt(d)
        row = (i * blk + jnp.arange(blk))[:, None]
        mask = (row >= pos[None, :]) & (row - pos[None, :] < window)
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, vs)

    out = jax.lax.map(one, jnp.arange(S // blk))         # [nb, B, blk, H, d]
    out = jnp.moveaxis(out, 0, 1).reshape(B, S, H * d)
    return out @ _w(p, "wo", quant)


def route(logits, top_k: int, renorm: bool):
    """(gates [T, k], experts [T, k]): the chosen experts' softmax
    probabilities, renormalised over the chosen. Ties go to the lower
    index."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    g, idx = jax.lax.top_k(probs, top_k)
    if renorm:
        g = g / jnp.sum(g, axis=-1, keepdims=True)
    return g, idx


def moe(hn, p, m: Dict, quant):
    B, S, h = hn.shape
    E, k = m["num_experts"], m["num_experts_per_tok"]
    x = hn.reshape(B * S, h)
    gates, idx = route(x @ _w(p, "router", quant), k,
                       bool(m["norm_topk_prob"]))
    # the pairs (token, choice) laid out expert by expert: a pair's row is
    # its expert's first row plus the earlier tokens that chose that expert
    # too (a token chooses an expert at most once)
    chose = jnp.any(idx[:, :, None] == jnp.arange(E), axis=1).astype(jnp.int32)
    sizes = chose.sum(axis=0)
    row = (jnp.cumsum(sizes) - sizes)[None, :] + jnp.cumsum(chose, 0) - chose
    dest = jnp.take_along_axis(row, idx, axis=1)                   # [T, k]
    tok = jnp.zeros((B * S * k,), jnp.int32).at[dest.reshape(-1)].set(
        jnp.repeat(jnp.arange(B * S, dtype=jnp.int32), k))
    dot = lambda a, w: jax.lax.ragged_dot(
        a, w, sizes, precision=jax.lax.Precision.HIGHEST)
    xs = x[tok]
    mid = jax.nn.silu(dot(xs, _w(p, "e_gate", quant))) \
        * dot(xs, _w(p, "e_up", quant))
    out = dot(mid, _w(p, "e_down", quant))
    # each token takes its k rows back and sums them under their gates
    y = jnp.sum(out[dest] * gates[:, :, None], axis=1)
    return y.reshape(B, S, h)


def layer(x, p, m: Dict, quant: Optional[str] = None, l: int = 0):
    """Layer ``l`` on x [B, S, h] float32; ``p`` holds its matrices in the
    published layout."""
    eps = m["rms_norm_eps"]
    x = x + attention(rms_norm(x, p["attn_norm"], eps), p, m, quant,
                      m["layer_types"][l])
    return x + moe(rms_norm(x, p["ffn_norm"], eps), p, m, quant)


def head_logits(x, params, m: Dict, quant: Optional[str] = None):
    x = rms_norm(x, params["final_norm"], m["rms_norm_eps"])
    head = params["head"].astype(jnp.float32)                  # [vocab, h]
    if quant == "int8":
        # the head's matrix is W_head^T: rounded per output channel (a row
        # of W_head)
        head = jnp.swapaxes(fake_int8(jnp.swapaxes(head, 0, 1)), 0, 1)
    return x @ head.T
