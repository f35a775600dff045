"""Fused int8 weight-only matmuls + int8 KV-pool quantization helpers.

Parity surface: the reference's weight_only_linear path keeps int8 weights
resident and fuses dequantization into the GEMM epilogue (nn/quant/
quantized_linear.py over the cutlass fpA_intB kernels in
phi/kernels/fusion/cutlass_kernels/). TPU-native version: the int8 operand
is fed DIRECTLY to ``lax.dot_general`` (mixed-dtype dot with
``preferred_element_type=f32``) and the per-output-channel scales are
applied to the f32 accumulator — the [K, N] bf16 dequantized weight copy
the naive ``(q * s).astype(bf16)`` epilogue materializes per step never
exists, so a weight-bandwidth-bound decode step reads half the bytes.

The same trick serves the int8 KV pools of the serving engine
(serving/engine.py): K stays int8 through the QK^T contraction with the
per-entry scale folded into the score, and the V scale is folded into the
softmax probabilities BEFORE the PV contraction (the scale depends on the
contracted position axis, so it must ride the probabilities, not the
output).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "weight_only_matmul", "quantize_kv", "dequantize_kv",
    "attn_qk", "attn_pv",
    "quantize_grouped", "is_quantized_weight", "dequantize_channels",
]


def dequantize_channels(q, scale, axis: int):
    """f32 reconstruction of a per-channel int8 tensor: ``q *
    expand_dims(scale, axis)`` where ``axis`` is the dim the scale was
    reduced over — the shared inverse of :func:`quantize_grouped`
    (``axis``), :func:`quantize_kv` (``axis=-1``) and
    ``models.llama.quantize_params`` (``axis=-2``). Also the
    reconstruction the numerics observatory's paired quant-error probes
    measure against (observability.numerics.record_quant_error)."""
    return (q.astype(jnp.float32)
            * jnp.expand_dims(scale.astype(jnp.float32), axis))


def _is_quantized(w) -> bool:
    return isinstance(w, dict) and "q" in w


def is_quantized_weight(w) -> bool:
    """True for an int8 weight-only leaf ``{"q": int8, "s": f32}`` (the
    quantize_params / quantize_grouped layout)."""
    return _is_quantized(w)


def quantize_grouped(w, axis: int):
    """Symmetric per-channel int8 for stacked per-expert weights.

    ``w``: [E, ...] grouped weights; ``axis`` is the axis the scale is
    *shared over* (reduced by absmax), e.g.:

    - gate/up ``[E, h, f]`` with ``axis=1`` → ``s`` [E, f]: one scale per
      (expert, output channel), applied to the GEMM *output* — the
      weight_only_matmul idiom, grouped;
    - down ``[E, f, h]`` with ``axis=2`` → ``s`` [E, f]: one scale per
      (expert, *input* channel), folded into the GEMM *input* — it rides
      the same elementwise chain as the MoE combine weights
      (``z * w * s``), so the dequantization costs nothing extra.

    Returns ``{"q": int8 (w.shape), "s": f32 (w.shape minus axis)}``.
    Scales are constants at use sites (stop_gradient): quantization never
    leaks into any gradient."""
    wf = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(wf), axis=axis) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.round(wf / jnp.expand_dims(scale, axis))
    return {"q": jnp.clip(q, -127, 127).astype(jnp.int8), "s": scale}


def dequantize_grouped(w, axis: int, dtype):
    """Materialize the dense weights of a :func:`quantize_grouped` leaf
    (the slow exact fallback — paths that can't keep the int8 operand
    resident, e.g. the shard_map expert-parallel forms)."""
    return dequantize_channels(w["q"], w["s"], axis).astype(dtype)


def weight_only_matmul(x, w, out_dtype):
    """``x @ w`` where ``w`` is a dense [K, N] array OR an int8
    weight-only leaf ``{"q": int8 [K, N], "s": [N]}`` (models/llama.
    quantize_params layout, sliced to one layer).

    Dense leaves reproduce the historical ``x @ w.astype(out_dtype)``
    exactly. int8 leaves contract x against the int8 matrix directly
    (f32 accumulator) and scale the OUTPUT per channel — no dequantized
    weight copy, no [K, N]-sized multiply.
    """
    if not _is_quantized(w):
        return x @ w.astype(out_dtype)
    q, s = w["q"], w["s"]
    dn = (((x.ndim - 1,), (0,)), ((), ()))
    y = jax.lax.dot_general(x, q, dn, preferred_element_type=jnp.float32)
    return (y * s.astype(jnp.float32)).astype(out_dtype)


# ---------------------------------------------------------------------------
# int8 KV pools: symmetric per-entry absmax over the head dim
# ---------------------------------------------------------------------------
def quantize_kv(x):
    """[..., D] K/V values -> (int8 [..., D], f32 scale [...]).

    One scale per pool entry (token, kv-head) — the fine-grained limit of
    per-block scaling. Coarser per-block scales break under the decode
    writeback, which APPENDS tokens into partially-filled blocks: the
    block's old scale would clip (or force a requantization of) every new
    entry. Per-entry scales make each write self-contained and the
    round-trip error bound exact (<= absmax/254 per element).
    Overhead at D=128: 4 bytes per 128 int8 bytes (~3%).
    """
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1) / 127.0
    q = jnp.round(xf / jnp.maximum(scale[..., None], 1e-9))
    return jnp.clip(q, -127, 127).astype(jnp.int8), scale


def dequantize_kv(q, scale, dtype):
    return dequantize_channels(q, scale, -1).astype(dtype)


# ---------------------------------------------------------------------------
# GQA decode attention contractions over (possibly int8) gathered prefixes
#   qg: [N, Hkv, G, D]   queries grouped by kv head
#   kd/vd: [N, P, Hkv, D] gathered prefix (model dtype, or int8 + scales)
#   ks/vs: [N, P, Hkv]   f32 per-entry scales (None for dense pools)
# ---------------------------------------------------------------------------
_QK_DN = (((3,), (3,)), ((0, 1), (0, 2)))   # contract D; batch (N, Hkv)
_PV_DN = (((3,), (1,)), ((0, 1), (0, 2)))   # contract P; batch (N, Hkv)


def attn_qk(qg, kd, ks=None):
    """QK^T scores [N, Hkv, G, P] in f32. int8 K contracts directly; the
    per-entry scale multiplies the f32 score (it is constant over the
    contracted D axis, so it commutes out of the dot)."""
    s = jax.lax.dot_general(qg, kd, _QK_DN,
                            preferred_element_type=jnp.float32)
    if ks is not None:
        s = s * jnp.transpose(ks, (0, 2, 1))[:, :, None, :]
    return s


def attn_pv(p, vd, vs=None, *, out_dtype):
    """probs @ V -> [N, Hkv, G, D] in ``out_dtype``. ``p``: f32 softmax
    probabilities [N, Hkv, G, P]. The V scale varies along the CONTRACTED
    P axis, so it is folded into the probabilities (a tensor that already
    exists at this size) and the int8 V feeds the dot unconverted."""
    if vs is not None:
        p = p * jnp.transpose(vs, (0, 2, 1))[:, :, None, :]
        out = jax.lax.dot_general(p, vd, _PV_DN,
                                  preferred_element_type=jnp.float32)
        return out.astype(out_dtype)
    # dense pools: match the historical bf16 einsum numerics exactly
    return jax.lax.dot_general(p.astype(out_dtype), vd, _PV_DN)
