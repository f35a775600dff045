"""The mellum family: grouped-query attention whose layers come in two
kinds, ``sliding_attention`` (a window of ``sliding_window`` tokens, plain
rope) and ``full_attention`` (YaRN), a sparse-expert FFN under a softmax
router in every layer, an untied head. ``families/llama.py`` states the
interface that every family module gives the harness.

Here: the program is ``paddle_tpu/models/mellum.py`` behind ``LLMEngine``,
the reference ``reference/mellum_f32.py``, the costs
``families/mellum_costs.py``. The family is served, not trained.

``make_layer`` draws the leaves in the PUBLISHED layout (what the
reference takes: gate and up of the experts apart); ``make_params`` hands
each layer to the program's ``from_published`` (gate and up side by side;
nothing is permuted). The scales are llama's: 1/sqrt(fan_in), the residual
outputs (``wo``, ``e_down``) divided by sqrt(2L), norms at 1; the router's
columns at 1/sqrt(h), which gives logits of unit scale.
"""
from __future__ import annotations

import math
import os
from typing import Dict

from benchmark.manifest import load_file
from benchmark.reference import mellum_f32 as _ref

# a member of the interface; beside this file, which lies on no package path
costs = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "mellum_costs.py"))

_TOP = {"embed": 100, "head": 101}
_RESIDUAL_OUT = ("wo", "e_down")


# -- the program --------------------------------------------------------------
def program_config(model: Dict, **over):
    import jax.numpy as jnp

    from paddle_tpu.models import mellum

    rp = model["rope_parameters"]
    full, slide = rp["full_attention"], rp["sliding_attention"]
    if (model["attention_bias"] or model["tie_word_embeddings"]
            or set(model["mlp_layer_types"]) != {"sparse"}
            or not model["use_sliding_window"]
            or full["rope_type"] != "yarn" or slide["rope_type"] != "default"
            or full["rope_theta"] != slide["rope_theta"]
            or len(model["layer_types"]) < model["num_hidden_layers"]):
        raise ValueError(
            "the program has bias-free attention, an untied head, a sparse "
            "FFN in every layer, a window on the sliding layers, YaRN on "
            "the full ones and plain rope of the same theta on the others")
    kw = dict(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        moe_intermediate_size=model["moe_intermediate_size"],
        # a configuration cut in depth keeps the published list whole
        layer_types=tuple(costs.layer_types(model)),
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"],
        head_dim=model["head_dim"], num_experts=model["num_experts"],
        num_experts_per_tok=model["num_experts_per_tok"],
        norm_topk_prob=bool(model["norm_topk_prob"]),
        sliding_window=model["sliding_window"],
        rope_theta=float(full["rope_theta"]),
        rope_factor=float(full["factor"]),
        rope_original_max=full["original_max_position_embeddings"],
        rope_beta_fast=float(full["beta_fast"]),
        rope_beta_slow=float(full["beta_slow"]),
        rope_attention_factor=float(full["attention_factor"]),
        rms_eps=model["rms_norm_eps"], dtype=jnp.bfloat16)
    kw.update(over)
    return mellum.MellumConfig(**kw)


def engine_kwargs(model: Dict) -> Dict:
    return {}


def trainer(model: Dict):
    raise NotImplementedError("the mellum family is served, not trained, "
                              "by this benchmark")


# -- seeded weights -----------------------------------------------------------
def layer_kind(model: Dict, l: int) -> str:
    return model["layer_types"][l]


def layer_shapes(m: Dict, l: int) -> Dict[str, tuple]:
    h, d = m["hidden_size"], m["head_dim"]
    H, Hkv = m["num_attention_heads"], m["num_key_value_heads"]
    f, E = m["moe_intermediate_size"], m["num_experts"]
    return {"wq": (h, H * d), "wk": (h, Hkv * d), "wv": (h, Hkv * d),
            "wo": (H * d, h), "router": (h, E), "e_gate": (E, h, f),
            "e_up": (E, h, f), "e_down": (E, f, h)}


def make_layer(m: Dict, key, l: int, dtype):
    """Layer ``l`` (a Python int) in the published layout: each leaf from
    a key of its own, folded from the leaf's name and the layer."""
    import jax
    import jax.numpy as jnp

    res = 1.0 / math.sqrt(2 * m["num_hidden_layers"])
    out = {}
    for i, (name, shape) in enumerate(sorted(layer_shapes(m, l).items())):
        k = jax.random.fold_in(jax.random.fold_in(key, 1000 + i), l)
        scale = 1.0 / math.sqrt(shape[-2])
        if name in _RESIDUAL_OUT:
            scale *= res
        out[name] = (jax.random.normal(k, shape, jnp.float32)
                     * scale).astype(dtype)
    out["attn_norm"] = jnp.ones((m["hidden_size"],), dtype)
    out["ffn_norm"] = jnp.ones((m["hidden_size"],), dtype)
    out["q_norm"] = jnp.ones((m["head_dim"],), dtype)
    out["k_norm"] = jnp.ones((m["head_dim"],), dtype)
    return out


def make_top(m: Dict, key, name: str, dtype):
    """``embed`` and ``head``, two matrices [vocab, h], or ``final_norm``."""
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
    from paddle_tpu.models import mellum

    cfg = program_config(m)
    layers = [mellum.from_published(make_layer(m, key, l, dtype), cfg)
              for l in range(m["num_hidden_layers"])]
    return {"embed": make_top(m, key, "embed", dtype),
            "head": make_top(m, key, "head", dtype), "layers": layers,
            "final_norm": make_top(m, key, "final_norm", dtype)}


# -- the yardstick ------------------------------------------------------------
class reference:
    """``reference/mellum_f32.py`` as the comparison calls it."""

    Q_BLOCK = _ref.Q_BLOCK
    layer = staticmethod(_ref.layer)
    head_logits = staticmethod(_ref.head_logits)

    @staticmethod
    def embed(tokens, top):
        import jax.numpy as jnp

        return top["embed"].astype(jnp.float32)[tokens]


def tiny(model: Dict) -> Dict:
    """Every mechanism kept: one period of three window layers and a full
    one, a window of 16 tokens (two blocks of the rehearsal's 8, so that a
    24-token prompt and its answer cross it), two KV heads under four
    query heads, 8 experts with top-2, an untied head."""
    return {"hidden_size": 256, "moe_intermediate_size": 64,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 64, "num_experts": 8, "n_routed_experts": 8,
            "num_experts_per_tok": 2, "vocab_size": 256,
            "sliding_window": 16,
            "layer_types": ["sliding_attention", "sliding_attention",
                            "sliding_attention", "full_attention"],
            "mlp_layer_types": ["sparse"] * 4, "num_hidden_layers": 4}
