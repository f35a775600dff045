"""Plain reference for the Trinity sparse-expert decoder (``afmoe``):
float32 ``jax.numpy``.

Follows the published configuration (``config.json`` of
arcee-ai/Trinity-Large-Preview, ``model_type: afmoe``) and, where no key of
it settles a step, what the configuration file lists under ``assumed``;
equation by equation, with no kernel, cache, ring, batching or packing, and
imports nothing of the program. Matmuls run at ``highest`` precision (the
caller sets it).

``rms(x; g) = x / sqrt(mean(x^2) + rms_norm_eps) * g``. ``x0 = E[t] *
sqrt(hidden_size)`` (``mup_enabled``). A layer ``l`` of the run::

    a = rms(x; attn_norm)
    x <- x + rms(Attn_l(a); attn_post_norm)
    b = rms(x; ffn_norm)
    x <- x + rms(FFN_l(b); ffn_post_norm)

attn    ``q, k, v = a W_q, a W_k, a W_v`` in heads of ``head_dim`` (48 query
        heads on 8 KV heads: query head h reads KV head ``h // 6``); ``q <-
        rms(q; q_norm)``, ``k <- rms(k; k_norm)`` over each head's channels;
        softmax of ``q . k / sqrt(d)`` under the layer's mask; the heads'
        outputs side by side times ``sigmoid(a W_g)``, elementwise over all
        ``heads x head_dim`` columns; then ``W_o``. No biases.
sliding_attention   rope on all the head's dims, half-split pairing
        (channel i with i + d/2), ``inv_freq_i = theta^(-2i/d)``; mask ``j
        <= i`` and ``i - j < sliding_window``.
full_attention      NO rope, nothing positional; causal mask.
FFN     the first ``num_dense_layers`` layers of the run: a SwiGLU of width
        ``intermediate_size``. The others: ``s = sigmoid(b W_r)`` in float32
        over the router's whole width; ``S = top-k(s + bias)`` (ties to the
        lower index; the bias enters the selection only); ``w_e =
        route_scale * s_e / (sum_{e' in S} s_e' + 1e-20)``
        (``route_norm``); ``m = Shared(b) + sum_{e in S, e held} w_e
        Expert_e(b)``, all SwiGLUs of width ``moe_intermediate_size``. A
        configuration states its share as ``held_first`` and
        ``n_routed_experts`` (the count) beside ``router_width``: what the
        absent experts would add is left out, here as in the program.
head    ``rms(x; final_norm) W_head^T`` (untied), over the configuration's
        slice of the vocabulary.

A configuration cut in depth keeps the published ``layer_types`` whole and
names the published indices it runs (``layers_run``); its ``num_dense_layers``
counts the dense layers among those.

Departures from a textbook forward, none of them numerical: attention runs
in blocks of query rows, each over the stretch of keys that its layer's mask
can let it see (all of them in a full layer; the block's own rows and the
``sliding_window`` before them in a window layer), so a 35k-position check
fits; and the experts are visited in blocks of MOE_BLOCK tokens with the
block's (token, choice) pairs laid out held expert by held expert
(``jax.lax.ragged_dot``; the pairs routed elsewhere lie behind the last
group and are given a weight of exactly 0), every token then taking its
rows back and summing them under their weights.

``quant="int8"`` gives the control of the ``correct`` check: every matmul
weight rounded to int8 per output channel. The other values of ``quant`` are
PLANTED FAULTS, for setting the check's limits only (``FAULTS``; no run of
the benchmark passes them): the weights stay float32 and one step of the
mathematics is wrong.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

# sequences are padded to a multiple of Q_BLOCK by the comparison (the padded
# lengths are program shapes, which repeat from run to run); attention works
# in ATTN_BLOCK rows, the expert layer in MOE_BLOCK tokens
Q_BLOCK = 1024
ATTN_BLOCK = 128
MOE_BLOCK = 1024

FAULTS = {
    "no_gate": "the gate left off the attention's output",
    "rope_on_full": "rope on the full layers too",
    "whole_history": "a window layer attends to its whole history",
    "one_block": "a window one block of 16 tokens too wide",
    "no_post_norms": "attn_post_norm and ffn_post_norm left out",
    "no_route_scale": "route_scale left off the routed weights",
    "wrong_share": "the held experts computed under the router's columns of "
                   "the NEXT share",
    "bias_in_weights": "the selection bias counted into the weights",
}


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


def layer_types(m: Dict):
    """The types of the layers that are run, in order."""
    run = m.get("layers_run") or range(m["num_hidden_layers"])
    return [m["layer_types"][i] for i in run]


def is_dense(m: Dict, l: int) -> bool:
    return l < m["num_dense_layers"]


def share_of(m: Dict) -> Tuple[int, int]:
    """(first, count) of the experts a configuration holds."""
    return int(m.get("held_first", 0)), int(m["n_routed_experts"])


def rope_half(x, theta: float):
    """Rotate x [B, S, H, d] by its position: channel i with i + d/2."""
    S, d = x.shape[1], x.shape[-1]
    inv = theta ** (-2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], -1)


def attention(hn, p, m: Dict, quant, kind: str):
    B, S, _ = hn.shape
    H, Hkv, d = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    eps = m["rms_norm_eps"]
    q = rms_norm((hn @ _w(p, "wq", quant)).reshape(B, S, H, d),
                 p["q_norm"], eps)
    k = rms_norm((hn @ _w(p, "wk", quant)).reshape(B, S, Hkv, d),
                 p["k_norm"], eps)
    v = (hn @ _w(p, "wv", quant)).reshape(B, S, Hkv, d)
    if kind == "sliding_attention" or quant == "rope_on_full":
        theta = float(m["rope_theta"])
        q, k = rope_half(q, theta), rope_half(k, theta)
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    blk = ATTN_BLOCK if S % ATTN_BLOCK == 0 else S
    qb = q.reshape(B, S // blk, blk, H, d)
    window = m["sliding_window"] if kind == "sliding_attention" else S
    if kind == "sliding_attention" and quant in ("whole_history",
                                                 "one_block"):
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
    if quant != "no_gate":
        out = out * jax.nn.sigmoid(hn @ _w(p, "wg", quant))
    return out @ _w(p, "wo", quant)


def route(scores, bias, top_k: int, scale: float, renorm: bool,
          bias_in_weights: bool = False):
    """(weights [T, k], experts [T, k]) over the router's whole width: the
    top-k of ``scores + bias`` (ties to the lower index), weighted by their
    UNBIASED scores over the chosen ones' sum, times ``scale``."""
    _, idx = jax.lax.top_k(scores + bias[None, :], top_k)
    w = jnp.take_along_axis(
        scores + bias[None, :] if bias_in_weights else scores, idx, axis=-1)
    if renorm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * scale, idx


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _held_pairs(x, w, idx, first: int, count: int, eg, eu, ed):
    """``sum_{e in S, e held} w_e Expert_e(x)`` for a block of tokens x [T,
    h]: the pairs laid out held expert by held expert, the others behind
    the last group with a weight of 0."""
    T, k = idx.shape
    held = (idx >= first) & (idx < first + count)
    local = jnp.where(held, idx - first, count)                   # [T, k]
    # a pair's row is its group's first row plus the earlier tokens that
    # chose that group too (a token chooses an expert at most once; the
    # pairs that are not held share group ``count`` and are told apart by
    # their choice's rank)
    onehot = (local[:, :, None] == jnp.arange(count + 1)).astype(jnp.int32)
    chose = onehot.sum(axis=1)                                # [T, count+1]
    sizes = chose.sum(axis=0)
    base = (jnp.cumsum(sizes) - sizes)[None, :] + jnp.cumsum(chose, 0) - chose
    rank = jnp.cumsum(onehot, axis=1) - onehot                # [T, k, c+1]
    dest = jnp.take_along_axis(base, local, axis=1) + jnp.take_along_axis(
        rank, local[:, :, None], axis=2)[:, :, 0]                 # [T, k]
    tok = jnp.zeros((T * k,), jnp.int32).at[dest.reshape(-1)].set(
        jnp.repeat(jnp.arange(T, dtype=jnp.int32), k))
    dot = lambda a, mat: jax.lax.ragged_dot(
        a, mat, sizes[:count], precision=jax.lax.Precision.HIGHEST)
    xs = x[tok]
    out = dot(jax.nn.silu(dot(xs, eg)) * dot(xs, eu), ed)
    rows = jnp.where(held[:, :, None], out[dest], 0.0)
    return jnp.sum(rows * jnp.where(held, w, 0.0)[:, :, None], axis=1)


def moe(hn, p, m: Dict, quant, held: Optional[Tuple[int, int]] = None):
    """Shared(x) + the routed sum over the held experts. ``p`` holds the
    router and the bias over the whole width and ``e_gate``/``e_up``/
    ``e_down`` stacked over the held experts only."""
    B, S, h = hn.shape
    first, count = held if held is not None else share_of(m)
    x = hn.reshape(B * S, h)
    scores = jax.nn.sigmoid(x @ _w(p, "router", quant))
    w, idx = route(scores, p["expert_bias"].astype(jnp.float32),
                   m["num_experts_per_tok"],
                   1.0 if quant == "no_route_scale"
                   else float(m["route_scale"]), bool(m["route_norm"]),
                   quant == "bias_in_weights")
    if quant == "wrong_share":
        first = first + count
    eg, eu, ed = (_w(p, n, quant) for n in ("e_gate", "e_up", "e_down"))
    T = B * S
    blk = MOE_BLOCK if T % MOE_BLOCK == 0 else T
    split = lambda a: a.reshape((T // blk, blk) + a.shape[1:])
    routed = jax.lax.map(
        lambda a: _held_pairs(a[0], a[1], a[2], first, count, eg, eu, ed),
        (split(x), split(w), split(idx))).reshape(T, h)
    y = routed + swiglu(x, _w(p, "s_gate", quant), _w(p, "s_up", quant),
                        _w(p, "s_down", quant))
    return y.reshape(B, S, h)


def layer(x, p, m: Dict, quant: Optional[str] = None, l: int = 0,
          held: Optional[Tuple[int, int]] = None):
    """Layer ``l`` of the run on x [B, S, h] float32; ``p`` holds its
    matrices in the published layout."""
    eps = m["rms_norm_eps"]
    post = ((lambda y, g: y) if quant == "no_post_norms"
            else (lambda y, g: rms_norm(y, p[g], eps)))
    a = rms_norm(x, p["attn_norm"], eps)
    x = x + post(attention(a, p, m, quant, layer_types(m)[l]),
                 "attn_post_norm")
    b = rms_norm(x, p["ffn_norm"], eps)
    if is_dense(m, l):
        y = swiglu(b, _w(p, "w_gate", quant), _w(p, "w_up", quant),
                   _w(p, "w_down", quant))
    else:
        y = moe(b, p, m, quant, held)
    return x + post(y, "ffn_post_norm")


def embed(tokens, top):
    """``E[t] * sqrt(hidden_size)`` (``mup_enabled``; the family module
    refuses a configuration without it)."""
    e = top["embed"].astype(jnp.float32)
    return e[tokens] * math.sqrt(e.shape[-1])


def head_logits(x, params, m: Dict, quant: Optional[str] = None):
    x = rms_norm(x, params["final_norm"], m["rms_norm_eps"])
    head = params["head"].astype(jnp.float32)                  # [vocab, h]
    if quant == "int8":
        # the head's matrix is W_head^T: rounded per output channel (a row
        # of W_head)
        head = jnp.swapaxes(fake_int8(jnp.swapaxes(head, 0, 1)), 0, 1)
    return x @ head.T
