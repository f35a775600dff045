"""Rope tables that more than one served model shares: YaRN's inverse
frequencies (DeepSeek-V2 on every layer, Mellum2 on its full-attention
layers only) and the half-split rotation."""
from __future__ import annotations

import math

import jax.numpy as jnp

__all__ = ["yarn_frequencies", "rope_half"]


def yarn_frequencies(dim: int, theta: float, factor: float,
                     original_max: int, beta_fast: float, beta_slow: float):
    """YaRN's inverse frequencies over ``dim`` rope dims, float32 [dim/2]:
    ``f_i = theta^(-2i/dim)``; dims that turn more than ``beta_fast``
    times over the original context keep ``f_i``, those that turn fewer
    than ``beta_slow`` times are interpolated (``f_i / factor``), a linear
    ramp between: ``low = floor(corr(beta_fast))``, ``high =
    ceil(corr(beta_slow))`` with ``corr(b) = dim ln(original_max / (2 pi
    b)) / (2 ln theta)``, ``low`` held at 0 and ``high`` at ``dim - 1`` as the
    published code holds them."""
    f = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)

    def corr(beta):
        return (dim * math.log(original_max / (beta * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return f / factor * ramp + f * (1.0 - ramp)


def rope_half(x, ang, mscale):
    """Half-split rotation of x [..., d] by angles [..., d/2] (broadcast
    over the head axis by the caller); cos and sin both times ``mscale``."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    c = (jnp.cos(ang) * mscale).astype(x.dtype)
    s = (jnp.sin(ang) * mscale).astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)
