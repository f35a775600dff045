"""Plain reference for the Ling-3.0 hybrid decoder (``bailing_hybrid``):
float32 ``jax.numpy``.

Follows the published architecture (``config.json`` of
inclusionAI/Ling-3.0-flash; KDA as the Kimi Linear report, arXiv:2510.26692,
and ``fla``'s ``kda`` operators define it; MLA and the group-limited
sigmoid router as DeepSeek-V2 and -V3 define them) equation by equation,
with no kernel, cache, state, chunking or batching, and imports nothing of
the program. Matmuls run at ``highest`` precision (the caller sets it).
What the config's keys name and do not define is read as the
configuration file's ``assumed`` says.

``rms(x; g) = x / sqrt(mean(x^2) + rms_norm_eps) * g``. Layer ``l`` of the
run is the PUBLISHED layer ``L = first_layer + l``: ``x <- x + Mix_L(rms(x;
attn_norm))``, ``x <- x + FFN_L(rms(x; mlp_norm))``. ``Mix_L`` is MLA where
``(L + 1) % layer_group_size == 0`` and KDA otherwise; ``FFN_L`` a dense
SwiGLU of ``intermediate_size`` where ``L < first_k_dense_replace`` and the
sparse layer otherwise. No biases.

KDA     per head h of ``num_attention_heads``, d = ``head_dim``, state ``S``
        in R^{d x d}, zero before the sequence. ``q, k, v = silu(conv4(x
        W_q)), silu(conv4(x W_k)), silu(conv4(x W_v))``: a causal depthwise
        convolution of ``short_conv_kernel_size`` 4 taps a channel (``y_t =
        sum_j w_j x_{t-3+j}``, zero before the sequence). ``q_h <- q_h /
        sqrt(|q_h|^2 + 1e-6) d^-1/2``, ``k_h <- k_h / sqrt(|k_h|^2 +
        1e-6)``. The log-decay a channel: ``g_t = kda_lower_bound
        sigmoid(exp(A_log_h) (x W_f + dt_bias))``, ``W_f`` full rank;
        ``beta_t = sigmoid(x W_beta)`` a head. Token by token: ``S' =
        Diag(exp g_t) S_{t-1}``; ``S_t = S' + beta_t k_t (v_t - S'^T
        k_t)^T``; ``o_t = S_t^T q_t``. Then ``o <- rms`` over each of
        ``group_norm_size`` groups of the concatenated H d channels (weight
        ``o_norm``), ``o_h <- o_h sigmoid(x W_g)_h`` (a scalar a head),
        ``y = o W_o``. No rope.
MLA     ``q = x W_q`` -> heads x [q_nope ; q_rope] (no compression);
        ``[c ; k_r] = x W_dkv``; ``c <- rms(c; kv_norm)``; rope at
        ``rope_theta`` on ``q_rope`` and ``k_r``, pairs (2i, 2i+1), no
        scaling; ``[k_nope ; v]_h = c W_ukv``; causal softmax of ``(q_nope
        . k_nope + q_rope . k_r) / sqrt(nope + rope)``; the same head-wise
        gate; ``W_o``.
FFN     sparse: ``s = sigmoid(x W_r)`` over the router's width; selection
        on ``s + b``: a group's score is the sum of its top two (``n_group``
        groups of consecutive experts), the best ``topk_group`` groups
        stay, the ``num_experts_per_tok`` best among their experts are
        chosen (ties to the lower index); ``w_e = s_e / sum_{chosen} s *
        routed_scaling_factor``; ``y = Shared(x) + sum_{chosen AND held}
        w_e Expert_e(x)``, SwiGLUs of ``moe_intermediate_size`` and
        ``moe_shared_expert_intermediate_size``; no clamp
        (``expert_swiglu_limit_list`` is 0 on the layers run).
share   ``held = (first, count)``: the routed sum runs over the held
        experts only, the sum that renormalises over ALL the chosen; with
        ``held`` covering the router's width this is the uncut layer. A
        configuration states its share as ``held_first`` and
        ``n_routed_experts`` (the count) beside ``router_width``.
head    ``rms(x; final_norm) W_head`` (untied), over the configuration's
        slice of the vocabulary.

Departures from a textbook forward, none of them numerical: attention runs
in blocks of query rows, and the held experts are visited one at a time
over all tokens with the gate as a mask. ``quant="int8"`` gives the control
of the ``correct`` check: every matmul weight rounded to int8 per output
channel.
"""
from __future__ import annotations

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


def published_index(m: Dict, l: int) -> int:
    return int(m.get("first_layer", 0)) + l


def is_mla(m: Dict, l: int) -> bool:
    return (published_index(m, l) + 1) % m["layer_group_size"] == 0


def is_dense(m: Dict, l: int) -> bool:
    return published_index(m, l) < m["first_k_dense_replace"]


# -- KDA ----------------------------------------------------------------------
def short_conv(x, w):
    """Causal depthwise convolution: x [B, S, C], w [C, K]; ``y_t = sum_j
    w_j x_{t-K+1+j}``, zero before the sequence."""
    K, S = w.shape[1], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(w[:, j] * xp[:, j:j + S] for j in range(K))


def kda_recurrence(q, k, v, g, beta):
    """The recurrence token by token. q, k, v, g [B, S, H, d], beta
    [B, S, H] -> o [B, S, H, d]."""
    B, S, H, d = q.shape

    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = state * jnp.exp(g_t)[..., None]                 # rows: dk
        u = (v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t)) * b_t[..., None]
        state = state + k_t[..., None] * u[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    tm = lambda t: jnp.moveaxis(t, 1, 0)
    _, o = jax.lax.scan(step, jnp.zeros((B, H, d, d), jnp.float32),
                        tuple(map(tm, (q, k, v, g, beta))))
    return jnp.moveaxis(o, 0, 1)


def head_gate(hn, p, quant):
    return jax.nn.sigmoid(hn @ _w(p, "w_g", quant))             # [B, S, H]


def kda(hn, p, m: Dict, quant):
    B, S, _ = hn.shape
    H, d = m["num_attention_heads"], m["head_dim"]
    heads = lambda t: t.reshape(B, S, H, d)
    q, k, v = (heads(jax.nn.silu(short_conv(
        hn @ _w(p, "w_" + n, quant), p["conv_" + n].astype(jnp.float32))))
        for n in ("q", "k", "v"))
    unit = lambda t: t * jax.lax.rsqrt(
        jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)
    f = heads(hn @ _w(p, "w_f", quant) + p["dt_bias"].astype(jnp.float32))
    g = float(m["kda_lower_bound"]) * jax.nn.sigmoid(
        jnp.exp(p["a_log"].astype(jnp.float32))[:, None] * f)
    beta = jax.nn.sigmoid(hn @ _w(p, "w_beta", quant))
    o = kda_recurrence(unit(q) * d ** -0.5, unit(k), v, g, beta)
    og = o.reshape(B, S, m["group_norm_size"], -1)
    og = og * jax.lax.rsqrt(jnp.mean(og * og, axis=-1, keepdims=True)
                            + m["rms_norm_eps"])
    o = heads(og.reshape(B, S, H * d) * p["o_norm"].astype(jnp.float32))
    o = o * head_gate(hn, p, quant)[..., None]
    return o.reshape(B, S, H * d) @ _w(p, "w_o", quant)


# -- MLA ----------------------------------------------------------------------
def rope_pairs(x, theta: float):
    """Rotate channel pairs (2i, 2i+1) of x [B, S, ..., d] by the
    position's angle ``t theta^(-2i/d)``."""
    S, d = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    ex = (None, slice(None)) + (None,) * (x.ndim - 3) + (slice(None),)
    c, s = jnp.cos(ang)[ex], jnp.sin(ang)[ex]
    xs = x.reshape(x.shape[:-1] + (d // 2, 2))
    a, b = xs[..., 0], xs[..., 1]
    return jnp.stack([a * c - b * s, b * c + a * s], -1).reshape(x.shape)


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
    r, theta = m["kv_lora_rank"], float(m["rope_theta"])
    q = (hn @ _w(p, "w_q", quant)).reshape(B, S, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], rope_pairs(q[..., dn:], theta)], -1)
    ckv = hn @ _w(p, "w_dkv", quant)
    lat = rms_norm(ckv[..., :r], p["kv_norm"], m["rms_norm_eps"])
    k_r = rope_pairs(ckv[..., r:], theta)                        # [B, S, dr]
    kv = (lat @ _w(p, "w_ukv", quant)).reshape(B, S, H, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r[:, :, None], (B, S, H, dr))], -1)
    o = attention(q, k, kv[..., dn:], (dn + dr) ** -0.5)
    o = o * head_gate(hn, p, quant)[..., None]
    return o.reshape(B, S, H * dv) @ _w(p, "w_o", quant)


# -- the sparse layer ---------------------------------------------------------
def route(scores, bias, n_group: int, topk_group: int, top_k: int,
          scale: float):
    """The weight of every expert of the router's width, [T, E]: the
    chosen experts' unbiased scores over their sum, times ``scale``; 0 for
    the rest."""
    T, E = scores.shape
    per = E // n_group
    biased = scores + bias[None, :]
    top2, _ = jax.lax.top_k(biased.reshape(T, n_group, per), 2)
    _, keep = jax.lax.top_k(top2.sum(-1), topk_group)
    kept = jnp.any(keep[:, :, None] == jnp.arange(n_group)[None, None, :],
                   axis=1)                                       # [T, G]
    masked = jnp.where(jnp.repeat(kept, per, axis=1), biased, -jnp.inf)
    _, idx = jax.lax.top_k(masked, top_k)
    chosen = jnp.any(idx[:, :, None] == jnp.arange(E)[None, None, :], axis=1)
    w = jnp.where(chosen, scores, 0.0)
    return w / jnp.sum(w, axis=-1, keepdims=True) * scale


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def share_of(m: Dict) -> Tuple[int, int]:
    """(first, count) of the experts a configuration holds."""
    return int(m.get("held_first", 0)), int(m["n_routed_experts"])


def moe(hn, p, m: Dict, quant, held: Optional[Tuple[int, int]] = None):
    """Shared(x) + the routed sum over the held experts. ``p`` holds the
    router and the bias over the whole width and ``e_gate``/``e_up``/
    ``e_down`` stacked over the held experts only."""
    B, S, h = hn.shape
    first, count = held if held is not None else share_of(m)
    x = hn.reshape(B * S, h)
    scores = jax.nn.sigmoid(x @ _w(p, "router", quant))
    w = route(scores, p["expert_bias"].astype(jnp.float32), m["n_group"],
              m["topk_group"], m["num_experts_per_tok"],
              float(m["routed_scaling_factor"]))[:, first:first + count]
    eg, eu, ed = (_w(p, n, quant) for n in ("e_gate", "e_up", "e_down"))

    def one(y, e):
        return y + w[:, e, None] * swiglu(x, eg[e], eu[e], ed[e]), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(count))
    y = y + swiglu(x, _w(p, "s_gate", quant), _w(p, "s_up", quant),
                   _w(p, "s_down", quant))
    return y.reshape(B, S, h)


def layer(x, p, m: Dict, quant: Optional[str] = None, l: int = 0,
          held: Optional[Tuple[int, int]] = None):
    """Layer ``l`` of the run on x [B, S, h] float32; ``p`` holds its
    matrices in the published layout."""
    eps = m["rms_norm_eps"]
    hn = rms_norm(x, p["attn_norm"], eps)
    x = x + (mla if is_mla(m, l) else kda)(hn, p, m, quant)
    hn = rms_norm(x, p["mlp_norm"], eps)
    if is_dense(m, l):
        return x + swiglu(hn, _w(p, "w_gate", quant), _w(p, "w_up", quant),
                          _w(p, "w_down", quant))
    return x + moe(hn, p, m, quant, held)


def head_logits(x, params, m: Dict, quant: Optional[str] = None):
    x = rms_norm(x, params["final_norm"], m["rms_norm_eps"])
    return x @ _w(params, "lm_head", quant)
