"""The afmoe family (Arcee Trinity): grouped-query attention whose output is
gated at full width, ``sliding_attention`` layers (a window of
``sliding_window`` tokens, plain rope) beside ``full_attention`` layers (no
rope at all), four norms a layer, leading dense layers and then expert
layers under a sigmoid router with a selection bias beside one shared
expert, run as one chip's share of an expert-parallel deployment, an untied
head. ``families/llama.py`` states the interface that every family module
gives the harness.

Here: the program is ``paddle_tpu/models/afmoe.py`` behind ``LLMEngine``,
the reference ``reference/afmoe_f32.py``, the costs
``families/afmoe_costs.py``. The family is served, not trained. A
configuration states the layers it runs (``layers_run``: their published
indices; ``layer_types`` stays whole; ``num_dense_layers`` counts the dense
ones among them) and the chip's share of each expert layer:
``n_routed_experts`` is the number of experts HELD (the first of them
``held_first``), ``router_width`` the number the router scores (the
published ``num_experts``); weights are made for the held experts only.

``make_layer`` draws the leaves in the PUBLISHED layout (what the
reference takes: gate and up of the experts apart); ``make_params`` hands
each layer to the program's ``from_published``. The scales are llama's:
1/sqrt(fan_in), the residual outputs (``wo``, ``w_down``, ``s_down``,
``e_down``) divided by sqrt(2L), norms at 1, the router's columns at
1/sqrt(h) (logits of unit scale: sigmoids around 0.5); the expert bias
normal at ``EXPERT_BIAS_SCALE``, float32 (LFM2's and Ling's scale): the
sigmoids of 256 experts lie about 0.01 apart near the fourth, and a bias of
0.005 changes 6% of the selections (a quarter of the tokens choose one
other expert than without it; at zero the fault "bias counted into the
weights" is no fault, and at 0.02 three tokens in four are re-routed).
"""
from __future__ import annotations

import math
import os
from typing import Dict

from benchmark.manifest import load_file
from benchmark.reference import afmoe_f32 as _ref

# a member of the interface; beside this file, which lies on no package path
costs = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "afmoe_costs.py"))
router_width = costs.router_width

_TOP = {"embed": 100, "head": 101}
EXPERT_BIAS_SCALE = 0.005
_RESIDUAL_OUT = ("wo", "w_down", "s_down", "e_down")
_FLOAT32 = ("expert_bias",)
_NORMS = ("attn_norm", "attn_post_norm", "ffn_norm", "ffn_post_norm")


# -- the program --------------------------------------------------------------
def program_config(model: Dict, **over):
    import jax.numpy as jnp

    from paddle_tpu.models import afmoe

    m = model
    if (m["score_func"] != "sigmoid" or m["tie_word_embeddings"]
            or m["rope_scaling"] is not None or m["hidden_act"] != "silu"
            or not m["mup_enabled"]
            or any(m[k] != 1 for k in ("n_group", "topk_group",
                                       "num_expert_groups",
                                       "num_limited_groups"))):
        raise ValueError(
            "the program has a sigmoid router with a selection bias and no "
            "group limit, plain rope on the sliding layers, SiLU, the "
            "embedding scaled by sqrt(hidden_size) and an untied head")
    kw = dict(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        intermediate_size=m["intermediate_size"],
        moe_intermediate_size=m["moe_intermediate_size"],
        # a configuration cut in depth keeps the published list whole
        layer_types=tuple(costs.layer_types(m)),
        num_dense_layers=m["num_dense_layers"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        num_experts=router_width(m),
        num_experts_per_tok=m["num_experts_per_tok"],
        num_shared_experts=m["num_shared_experts"],
        route_norm=bool(m["route_norm"]), route_scale=float(m["route_scale"]),
        held_first=int(m.get("held_first", 0)),
        held_experts=m["n_routed_experts"],
        sliding_window=m["sliding_window"],
        rope_theta=float(m["rope_theta"]), mup_enabled=True,
        rms_eps=m["rms_norm_eps"], dtype=jnp.bfloat16)
    kw.update(over)
    return afmoe.AfmoeConfig(**kw)


def engine_kwargs(model: Dict) -> Dict:
    return {}


def trainer(model: Dict):
    raise NotImplementedError("the afmoe family is served, not trained, by "
                              "this benchmark")


# -- seeded weights -----------------------------------------------------------
def layer_kind(model: Dict, l: int) -> str:
    kind = costs.layer_types(model)[l].split("_")[0]
    return kind + ("-dense" if _ref.is_dense(model, l) else "-moe")


def layer_shapes(m: Dict, l: int) -> Dict[str, tuple]:
    h, d = m["hidden_size"], m["head_dim"]
    H, Hkv = m["num_attention_heads"], m["num_key_value_heads"]
    out = {"wq": (h, H * d), "wk": (h, Hkv * d), "wv": (h, Hkv * d),
           "wg": (h, H * d), "wo": (H * d, h)}
    if _ref.is_dense(m, l):
        f = m["intermediate_size"]
        out.update(w_gate=(h, f), w_up=(h, f), w_down=(f, h))
    else:
        f, E, W = (m["moe_intermediate_size"], m["n_routed_experts"],
                   router_width(m))
        out.update(router=(h, W), expert_bias=(W,), s_gate=(h, f),
                   s_up=(h, f), s_down=(f, h), e_gate=(E, h, f),
                   e_up=(E, h, f), e_down=(E, f, h))
    return out


def make_layer(m: Dict, key, l: int, dtype):
    """Layer ``l`` of the run (a Python int) in the published layout: each
    leaf from a key of its own, folded from the leaf's name and the
    layer."""
    import jax
    import jax.numpy as jnp

    res = 1.0 / math.sqrt(2 * m["num_hidden_layers"])
    out = {}
    for i, (name, shape) in enumerate(sorted(layer_shapes(m, l).items())):
        k = jax.random.fold_in(jax.random.fold_in(key, 1000 + i), l)
        w = jax.random.normal(k, shape, jnp.float32)
        if name == "expert_bias":
            w = w * EXPERT_BIAS_SCALE
        else:
            w = w / math.sqrt(shape[-2]) * (
                res if name in _RESIDUAL_OUT else 1.0)
        out[name] = w if name in _FLOAT32 else w.astype(dtype)
    for name in _NORMS:
        out[name] = jnp.ones((m["hidden_size"],), dtype)
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
    from paddle_tpu.models import afmoe

    cfg = program_config(m)
    layers = [afmoe.from_published(make_layer(m, key, l, dtype), cfg)
              for l in range(m["num_hidden_layers"])]
    return {"embed": make_top(m, key, "embed", dtype),
            "head": make_top(m, key, "head", dtype), "layers": layers,
            "final_norm": make_top(m, key, "final_norm", dtype)}


# -- the yardstick ------------------------------------------------------------
class reference:
    """``reference/afmoe_f32.py`` as the comparison calls it."""

    Q_BLOCK = _ref.Q_BLOCK
    layer = staticmethod(_ref.layer)
    head_logits = staticmethod(_ref.head_logits)
    embed = staticmethod(_ref.embed)


def tiny(model: Dict) -> Dict:
    """Every mechanism kept: a dense sliding layer and then one period of
    three window layers and a full one (the cell's five kinds of layer in
    its order), a window of 16 tokens (two blocks of the rehearsal's 8),
    two KV heads under four query heads, a share of 8 experts (the second
    of four) of a router 32 wide with top-2, a shared expert, an untied
    head."""
    return {"hidden_size": 256, "intermediate_size": 512,
            "moe_intermediate_size": 64, "num_attention_heads": 4,
            "num_key_value_heads": 2, "head_dim": 64, "num_experts": 8,
            "n_routed_experts": 8, "router_width": 32, "held_first": 8,
            "num_experts_per_tok": 2, "vocab_size": 256,
            "sliding_window": 16,
            "layer_types": ["sliding_attention", "sliding_attention",
                            "sliding_attention", "sliding_attention",
                            "full_attention"],
            "layers_run": [0, 1, 2, 3, 4], "num_dense_layers": 1,
            "num_hidden_layers": 5}
