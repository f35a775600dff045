"""Seeded random weights, made by the benchmark and handed to the program.

Every leaf is drawn from its own key, ``fold_in(fold_in(key, leaf), layer)``,
so the plain reference can make one layer again from the seed alone and
takes nothing that the program has made. The tree has the layout the
program's entry points accept (``paddle_tpu/models/llama.py`` ``init_params``:
a dict with the layers stacked on a leading axis), and the scales are that
function's (1/sqrt(fan_in), the residual outputs divided by sqrt(2L)), which
make the logits of unit scale.
"""
from __future__ import annotations

import math
from typing import Dict

LAYER_LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
_TOP = {"embed": 100, "lm_head": 101}


def seed_key(seed: int):
    """A PRNG key from any whole number: ``--seed`` may exceed 31 bits."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def layer_shapes(m: Dict) -> Dict[str, tuple]:
    h, f, d = m["hidden_size"], m["intermediate_size"], m["head_dim"]
    nq, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    return {"wq": (h, nq * d), "wk": (h, nkv * d), "wv": (h, nkv * d),
            "wo": (nq * d, h), "w_gate": (h, f), "w_up": (h, f),
            "w_down": (f, h)}


def _scale(m: Dict, name: str) -> float:
    h, f, L = m["hidden_size"], m["intermediate_size"], m["num_hidden_layers"]
    if name == "wo":
        return 1.0 / math.sqrt(h) / math.sqrt(2 * L)
    if name == "w_down":
        return 1.0 / math.sqrt(f) / math.sqrt(2 * L)
    return 1.0 / math.sqrt(h)


def make_layer(m: Dict, key, layer, dtype):
    """The matrices of one layer (``layer`` may be traced)."""
    import jax
    import jax.numpy as jnp

    out = {}
    for i, name in enumerate(LAYER_LEAVES):
        k = jax.random.fold_in(jax.random.fold_in(key, i), layer)
        w = jax.random.normal(k, layer_shapes(m)[name], jnp.float32)
        out[name] = (w * _scale(m, name)).astype(dtype)
    h = m["hidden_size"]
    out["attn_norm"] = jnp.ones((h,), dtype)
    out["mlp_norm"] = jnp.ones((h,), dtype)
    return out


def make_top(m: Dict, key, name: str, dtype):
    """``embed`` [vocab, h] or ``lm_head`` [h, vocab]."""
    import jax
    import jax.numpy as jnp

    h, v = m["hidden_size"], m["vocab_size"]
    shape = (v, h) if name == "embed" else (h, v)
    w = jax.random.normal(jax.random.fold_in(key, _TOP[name]), shape,
                          jnp.float32)
    return (w / math.sqrt(h)).astype(dtype)


def make_params(m: Dict, key, dtype):
    """The whole tree, layers stacked. Call under ``jax.jit`` with the key
    as an argument, so that one compiled program serves every seed."""
    import jax
    import jax.numpy as jnp

    L = m["num_hidden_layers"]
    layers = jax.vmap(lambda l: make_layer(m, key, l, dtype))(jnp.arange(L))
    params = {"embed": make_top(m, key, "embed", dtype), "layers": layers,
              "final_norm": jnp.ones((m["hidden_size"],), dtype)}
    if not m.get("tie_word_embeddings"):
        params["lm_head"] = make_top(m, key, "lm_head", dtype)
    return params
