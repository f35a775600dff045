"""The deepseek_v2 family: multi-head latent attention and sparse experts
under group-limited routing, one leading dense layer. ``families/llama.py``
states the interface that every family module gives the harness.

Here: the program is ``paddle_tpu/models/deepseek_v2.py`` behind
``LLMEngine``, the reference ``reference/deepseek_v2_f32.py``, the costs
``families/deepseek_v2_costs.py``. A configuration of this family states
the chip's share of each expert layer: ``n_routed_experts`` is the number
of experts HELD (the first of them ``held_first``), ``router_width`` the
number the router scores (the published ``n_routed_experts``); weights are
made for the held experts only.

``make_layer`` draws the leaves in the PUBLISHED layout (what the
reference takes); ``make_params`` hands each layer to the program's
``from_published``, which lays it out as the program keeps it (rope
columns de-interleaved, ``W_UKV`` split per head, gate and up of the
experts side by side). The scales are llama's: 1/sqrt(fan_in), the
residual outputs divided by sqrt(2L), so that the logits are of unit
scale; the router's columns are drawn at 1/sqrt(h) too, which at h = 5120
gives logits of unit scale over the experts and a routing that is neither
uniform nor collapsed.
"""
from __future__ import annotations

import math
import os
from typing import Dict

from benchmark.manifest import load_file
from benchmark.reference import deepseek_v2_f32 as _ref

# a member of the interface; beside this file, which lies on no package path
costs = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "deepseek_v2_costs.py"))
router_width = costs.router_width

_TOP = {"embed": 100, "lm_head": 101}


# -- the program --------------------------------------------------------------
def program_config(model: Dict, **over):
    import jax.numpy as jnp

    from paddle_tpu.models import deepseek_v2

    rs = model["rope_scaling"]
    if rs["type"] != "yarn" or model["scoring_func"] != "softmax" \
            or model["topk_method"] != "group_limited_greedy" \
            or model["norm_topk_prob"] or model["moe_layer_freq"] != 1:
        raise ValueError("the program has YaRN rope, a softmax router with "
                         "group-limited greedy routing and un-renormalised "
                         "gates on every layer past the dense ones")
    kw = dict(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"],
        moe_intermediate_size=model["moe_intermediate_size"],
        num_layers=model["num_hidden_layers"],
        num_heads=model["num_attention_heads"],
        q_lora_rank=model["q_lora_rank"], kv_lora_rank=model["kv_lora_rank"],
        qk_nope_head_dim=model["qk_nope_head_dim"],
        qk_rope_head_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"],
        n_routed_experts=router_width(model),
        n_shared_experts=model["n_shared_experts"],
        num_experts_per_tok=model["num_experts_per_tok"],
        n_group=model["n_group"], topk_group=model["topk_group"],
        routed_scaling_factor=float(model["routed_scaling_factor"]),
        first_k_dense_replace=model["first_k_dense_replace"],
        held_first=int(model.get("held_first", 0)),
        held_experts=model["n_routed_experts"],
        rope_theta=float(model["rope_theta"]), rope_factor=float(rs["factor"]),
        rope_beta_fast=float(rs["beta_fast"]),
        rope_beta_slow=float(rs["beta_slow"]),
        rope_mscale=float(rs["mscale"]),
        rope_mscale_all_dim=float(rs["mscale_all_dim"]),
        rope_original_max=rs["original_max_position_embeddings"],
        rms_eps=model["rms_norm_eps"], dtype=jnp.bfloat16)
    kw.update(over)
    return deepseek_v2.DeepseekV2Config(**kw)


def engine_kwargs(model: Dict) -> Dict:
    return {}


def trainer(model: Dict):
    raise NotImplementedError("the deepseek_v2 family is served, not "
                              "trained, by this benchmark")


# -- seeded weights -----------------------------------------------------------
def layer_kind(model: Dict, l: int) -> str:
    return "dense" if l < model["first_k_dense_replace"] else "moe"


def layer_shapes(m: Dict, l: int) -> Dict[str, tuple]:
    h, H = m["hidden_size"], m["num_attention_heads"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    qr, r = m["q_lora_rank"], m["kv_lora_rank"]
    out = {"w_dq": (h, qr), "w_uq": (qr, H * (dn + dr)),
           "w_dkv": (h, r + dr), "w_ukv": (r, H * (dn + dv)),
           "w_o": (H * dv, h)}
    if layer_kind(m, l) == "dense":
        f = m["intermediate_size"]
        out.update(w_gate=(h, f), w_up=(h, f), w_down=(f, h))
    else:
        f, E = m["moe_intermediate_size"], m["n_routed_experts"]
        fs = f * m["n_shared_experts"]
        out.update(router=(h, router_width(m)), s_gate=(h, fs), s_up=(h, fs),
                   s_down=(fs, h), e_gate=(E, h, f), e_up=(E, h, f),
                   e_down=(E, f, h))
    return out


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
        if name in ("w_o", "w_down", "s_down", "e_down"):
            scale *= res
        w = jax.random.normal(k, shape, jnp.float32) * scale
        out[name] = w.astype(dtype)
    for name, n in (("attn_norm", m["hidden_size"]),
                    ("mlp_norm", m["hidden_size"]),
                    ("q_norm", m["q_lora_rank"]),
                    ("kv_norm", m["kv_lora_rank"])):
        out[name] = jnp.ones((n,), dtype)
    return out


def make_top(m: Dict, key, name: str, dtype):
    """``embed`` [vocab, h], ``lm_head`` [h, vocab] or ``final_norm`` [h]."""
    import jax
    import jax.numpy as jnp

    h, v = m["hidden_size"], m["vocab_size"]
    if name == "final_norm":
        return jnp.ones((h,), dtype)
    shape = (v, h) if name == "embed" else (h, v)
    w = jax.random.normal(jax.random.fold_in(key, _TOP[name]), shape,
                          jnp.float32)
    return (w / math.sqrt(h)).astype(dtype)


def make_params(m: Dict, key, dtype):
    """The tree the program accepts: the layers a list (their kinds
    differ), each laid out by the program's ``from_published``."""
    from paddle_tpu.models import deepseek_v2

    cfg = program_config(m)
    layers = [deepseek_v2.from_published(make_layer(m, key, l, dtype), cfg)
              for l in range(m["num_hidden_layers"])]
    return {"embed": make_top(m, key, "embed", dtype), "layers": layers,
            "final_norm": make_top(m, key, "final_norm", dtype),
            "lm_head": make_top(m, key, "lm_head", dtype)}


# -- the yardstick ------------------------------------------------------------
class reference:
    """``reference/deepseek_v2_f32.py`` as the comparison calls it."""

    Q_BLOCK = _ref.Q_BLOCK
    layer = staticmethod(_ref.layer)
    head_logits = staticmethod(_ref.head_logits)

    @staticmethod
    def embed(tokens, top):
        import jax.numpy as jnp

        return top["embed"].astype(jnp.float32)[tokens]


def tiny(model: Dict) -> Dict:
    """Every mechanism kept: two dense-or-expert kinds, 4 groups with a
    limit of 2, top-3, a shared expert, rope and nope parts, q and kv
    ranks, a share (8) smaller than the router's width (32)."""
    return {"hidden_size": 64, "intermediate_size": 128,
            "moe_intermediate_size": 32, "num_attention_heads": 4,
            "num_key_value_heads": 4, "q_lora_rank": 48, "kv_lora_rank": 128,
            "qk_nope_head_dim": 32, "qk_rope_head_dim": 16,
            "v_head_dim": 32, "n_routed_experts": 8, "router_width": 32,
            "held_first": 8, "n_group": 4, "topk_group": 2,
            "num_experts_per_tok": 3, "n_shared_experts": 1,
            "vocab_size": 256, "num_hidden_layers": 3}
