"""The lfm2_moe family: gated short convolutions with a per-slot state,
grouped-query attention at head dim 64 in one layer of four, and sparse
experts under a sigmoid router with a selection bias. ``families/llama.py``
states the interface that every family module gives the harness.

Here: the program is ``paddle_tpu/models/lfm2_moe.py`` behind ``LLMEngine``,
the reference ``reference/lfm2_moe_f32.py``, the costs
``families/lfm2_moe_costs.py``. The family is served, not trained.

``make_layer`` draws the leaves in the PUBLISHED layout (what the
reference takes: gate and up of the experts apart); ``make_params`` hands
each layer to the program's ``from_published`` (gate and up side by side;
nothing is permuted). The scales are llama's: 1/sqrt(fan_in), the residual
outputs (``w_out``, ``wo``, ``w_down``, ``e_down``) divided by sqrt(2L),
norms at 1; the convolution's taps at 1/sqrt(3), so that ``c`` has the
scale of ``u``; the router's columns at 1/sqrt(h), which gives logits of
unit scale and sigmoids spread over (0.1, 0.9). The expert bias is drawn
at ``EXPERT_BIAS_SCALE``: large enough to change who is selected on some
percent of tokens (9.5% at the published widths, 3.4% at the tiny size;
at 0.05 it was 67%, the fourth and fifth sigmoids of 32 lie ~0.03 apart),
too small to empty or to flood an expert (the fullest expert 1.13 times
the mean; all read on the CPU, router and bias alone, PR 32).
"""
from __future__ import annotations

import math
import os
from typing import Dict

from benchmark.manifest import load_file
from benchmark.reference import lfm2_moe_f32 as _ref

# a member of the interface; beside this file, which lies on no package path
costs = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "lfm2_moe_costs.py"))

_TOP = {"embed": 100}
EXPERT_BIAS_SCALE = 0.005
_RESIDUAL_OUT = ("w_out", "wo", "w_down", "e_down")


# -- the program --------------------------------------------------------------
def program_config(model: Dict, **over):
    import jax.numpy as jnp

    from paddle_tpu.models import lfm2_moe

    if model["conv_bias"] or model["conv_L_cache"] != 3 \
            or len(model["layer_types"]) < model["num_hidden_layers"]:
        raise ValueError("the program has a bias-free short convolution of "
                         "three taps, and a layer type for every layer")
    kw = dict(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"],
        moe_intermediate_size=model["moe_intermediate_size"],
        # a configuration cut in depth keeps the published list whole
        layer_types=tuple(costs.layer_types(model)),
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"],
        num_dense_layers=model["num_dense_layers"],
        num_experts=model["num_experts"],
        num_experts_per_tok=model["num_experts_per_tok"],
        conv_L_cache=model["conv_L_cache"],
        norm_topk_prob=bool(model["norm_topk_prob"]),
        use_expert_bias=bool(model["use_expert_bias"]),
        routed_scaling_factor=float(model["routed_scaling_factor"]),
        rope_theta=float(model["rope_theta"]), norm_eps=model["norm_eps"],
        dtype=jnp.bfloat16)
    kw.update(over)
    return lfm2_moe.Lfm2MoeConfig(**kw)


def engine_kwargs(model: Dict) -> Dict:
    return {}


def trainer(model: Dict):
    raise NotImplementedError("the lfm2_moe family is served, not trained, "
                              "by this benchmark")


# -- seeded weights -----------------------------------------------------------
def layer_kind(model: Dict, l: int) -> str:
    op = "conv" if model["layer_types"][l] == "conv" else "attn"
    return op + ("-dense" if l < model["num_dense_layers"] else "-moe")


def layer_shapes(m: Dict, l: int) -> Dict[str, tuple]:
    h = m["hidden_size"]
    H, Hkv = m["num_attention_heads"], m["num_key_value_heads"]
    d = h // H
    if m["layer_types"][l] == "conv":
        out = {"w_in": (h, 3 * h), "conv_w": (h, 3), "w_out": (h, h)}
    else:
        out = {"wq": (h, H * d), "wk": (h, Hkv * d), "wv": (h, Hkv * d),
               "wo": (H * d, h)}
    if l < m["num_dense_layers"]:
        f = m["intermediate_size"]
        out.update(w_gate=(h, f), w_up=(h, f), w_down=(f, h))
    else:
        f, E = m["moe_intermediate_size"], m["num_experts"]
        out.update(router=(h, E), expert_bias=(E,), e_gate=(E, h, f),
                   e_up=(E, h, f), e_down=(E, f, h))
    return out


def make_layer(m: Dict, key, l: int, dtype):
    """Layer ``l`` (a Python int) in the published layout: each leaf from
    a key of its own, folded from the leaf's name and the layer."""
    import jax
    import jax.numpy as jnp

    res = 1.0 / math.sqrt(2 * m["num_hidden_layers"])
    h = m["hidden_size"]
    out = {}
    for i, (name, shape) in enumerate(sorted(layer_shapes(m, l).items())):
        k = jax.random.fold_in(jax.random.fold_in(key, 1000 + i), l)
        w = jax.random.normal(k, shape, jnp.float32)
        if name == "expert_bias":
            out[name] = w * EXPERT_BIAS_SCALE      # float32 whatever dtype
            continue
        scale = (1.0 / math.sqrt(3.0) if name == "conv_w"
                 else 1.0 / math.sqrt(shape[-2]))
        if name in _RESIDUAL_OUT:
            scale *= res
        out[name] = (w * scale).astype(dtype)
    out["op_norm"] = jnp.ones((h,), dtype)
    out["ffn_norm"] = jnp.ones((h,), dtype)
    if m["layer_types"][l] != "conv":
        d = h // m["num_attention_heads"]
        out["q_norm"] = jnp.ones((d,), dtype)
        out["k_norm"] = jnp.ones((d,), dtype)
    return out


def make_top(m: Dict, key, name: str, dtype):
    """``embed`` [vocab, h] (the head is tied to it) or ``final_norm``."""
    import jax
    import jax.numpy as jnp

    h, v = m["hidden_size"], m["vocab_size"]
    if name == "final_norm":
        return jnp.ones((h,), dtype)
    w = jax.random.normal(jax.random.fold_in(key, _TOP[name]), (v, h),
                          jnp.float32)
    return (w / math.sqrt(h)).astype(dtype)


def make_params(m: Dict, key, dtype):
    """The tree the program accepts: the layers a list (their kinds
    differ), each laid out by the program's ``from_published``."""
    from paddle_tpu.models import lfm2_moe

    cfg = program_config(m)
    layers = [lfm2_moe.from_published(make_layer(m, key, l, dtype), cfg)
              for l in range(m["num_hidden_layers"])]
    return {"embed": make_top(m, key, "embed", dtype), "layers": layers,
            "final_norm": make_top(m, key, "final_norm", dtype)}


# -- the yardstick ------------------------------------------------------------
class reference:
    """``reference/lfm2_moe_f32.py`` as the comparison calls it."""

    Q_BLOCK = _ref.Q_BLOCK
    layer = staticmethod(_ref.layer)
    head_logits = staticmethod(_ref.head_logits)

    @staticmethod
    def embed(tokens, top):
        import jax.numpy as jnp

        return top["embed"].astype(jnp.float32)[tokens]


def tiny(model: Dict) -> Dict:
    """Every mechanism kept: a dense convolution layer, an attention and a
    convolution layer with experts (three kinds), two KV heads of 64 in one
    packed row under four query heads, 8 experts with top-2 and a bias."""
    return {"hidden_size": 256, "intermediate_size": 128,
            "moe_intermediate_size": 64, "num_attention_heads": 4,
            "num_key_value_heads": 2, "num_dense_layers": 1,
            "num_experts": 8, "n_routed_experts": 8,
            "num_experts_per_tok": 2, "vocab_size": 256,
            "layer_types": ["conv", "full_attention", "conv"],
            "num_hidden_layers": 3}
