"""Flash attention kernel entry.

Replaces the reference's FlashAttention-2 third_party dependency
(reference: paddle/phi/kernels/gpu/flash_attn_kernel.cu +
python/paddle/nn/functional/flash_attention.py:358).

The Pallas TPU kernel lives in pallas_attention.py; this module picks the best
implementation for the current backend (Pallas on TPU, fused-XLA reference
math elsewhere) behind one API: inputs [batch, seq, heads, head_dim].
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..ops.dispatch import apply


def _reference_attention(q, k, v, causal):
    if k.shape[2] != q.shape[2]:  # GQA: expand K/V for the dense fallback
        g = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    scale = 1.0 / math.sqrt(q.shape[-1])
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    scores = jnp.einsum("bhsd,bhtd->bhst", qt, kt) * scale
    if causal:
        s, t = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((s, t), bool))
        scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bhst,bhtd->bhsd", probs, vt)
    return jnp.swapaxes(out, 1, 2)


def _use_pallas(q_val) -> bool:
    try:
        plat = next(iter(q_val.devices())).platform
    except jax.errors.ConcretizationTypeError:
        plat = jax.default_backend()     # a tracer lives nowhere yet
    if plat != "tpu":
        return False
    # pallas kernel wants MXU-friendly shapes
    return q_val.shape[1] >= 128 and q_val.shape[-1] % 128 == 0


def flash_attention(query, key, value, causal: bool = False):
    def fn(q, k, v):
        if _use_pallas(q):
            # selected by backend and shape; a failure to build the
            # kernel after that is an error, not reference math
            from .pallas_attention import flash_attention_fwd

            return flash_attention_fwd(q, k, v, causal=causal)
        return _reference_attention(q, k, v, causal)

    return apply("flash_attention", fn,
                 query if isinstance(query, Tensor) else Tensor(query),
                 key if isinstance(key, Tensor) else Tensor(key),
                 value if isinstance(value, Tensor) else Tensor(value))
