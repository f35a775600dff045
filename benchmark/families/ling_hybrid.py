"""The ling_hybrid family (``bailing_hybrid``): KDA linear-attention layers
with a per-slot matrix state, one MLA layer in ``layer_group_size`` on a
paged latent cache, and sparse experts under a sigmoid router with
group-limited selection, run as one chip's share of an expert-parallel
deployment. ``families/llama.py`` states the interface that every family
module gives the harness.

Here: the program is ``paddle_tpu/models/ling_hybrid.py`` behind
``LLMEngine``, the reference ``reference/ling_hybrid_f32.py``, the costs
``families/ling_hybrid_costs.py``. The family is served, not trained. A
configuration states the layers it runs (``first_layer``: the published
index of its first; the kinds keep their published period) and the chip's
share of each expert layer: ``n_routed_experts`` is the number of experts
HELD (the first of them ``held_first``), ``router_width`` the number the
router scores (the published ``num_experts``); weights are made for the
held experts only.

``make_layer`` draws the leaves in the PUBLISHED layout (what the
reference takes); ``make_params`` hands each layer to the program's
``from_published``. The scales are llama's: 1/sqrt(fan_in), the residual
outputs (``w_o``, ``w_down``, ``s_down``, ``e_down``) divided by sqrt(2L),
norms at 1, the router's columns at 1/sqrt(h); the convolutions' four taps
at 1/2, so that a convolved product keeps its input's scale; the expert
bias normal at ``EXPERT_BIAS_SCALE`` (LFM2's file argues for the scale).
The decay's two parameters are drawn so that a layer holds memories of
every length its context has: ``dt_bias`` uniform in (-12, -1) a channel
and ``A_log`` uniform in (-0.7, 0.7) a head. At ``exp(A_log)`` 1 and ``x
W_f`` 0 a channel's log-decay a step ``g = -5 sigmoid(dt_bias)`` runs from
-3.1e-5 (it holds what it was written for 32,000 tokens: the context) to
-1.3 (it forgets within a token), evenly in the logarithm, as Mamba's and
``fla``'s initial ``dt`` is log-uniform; the head's factor, 0.5 to 2,
multiplies the sigmoid's argument. The reason is the comparison: where
most channels forget within ten tokens (``dt_bias`` about -3), a state
LEFT IN A SLOT by the request before has decayed before the first served
position and the served tokens cannot show it; ``limits/
reason-offline.json`` has the readings of both.
"""
from __future__ import annotations

import math
import os
from typing import Dict

from benchmark.manifest import load_file
from benchmark.reference import ling_hybrid_f32 as _ref

# a member of the interface; beside this file, which lies on no package path
costs = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "ling_hybrid_costs.py"))
router_width = costs.router_width

_TOP = {"embed": 100, "lm_head": 101}
EXPERT_BIAS_SCALE = 0.005
DT_BIAS = (-12.0, -1.0)
_RESIDUAL_OUT = ("w_o", "w_down", "s_down", "e_down")
_FLOAT32 = ("expert_bias", "a_log", "dt_bias")


# -- the program --------------------------------------------------------------
def program_config(model: Dict, **over):
    import jax.numpy as jnp

    from paddle_tpu.models import ling_hybrid

    m = model
    first = int(m.get("first_layer", 0))
    run = range(first, first + m["num_hidden_layers"])
    if m["score_function"] != "sigmoid" or m["topk_method"] != "noaux_tc" \
            or m["q_lora_rank"] is not None or m["rope_scaling"] is not None \
            or not (m["no_kda_lora"] and m["kda_safe_gate"]
                    and m["linear_silu"] and m["rope_interleave"]
                    and m["moe_router_enable_expert_bias"]) \
            or m["gated_attention_proj_granularity_type"] != "head_wise" \
            or m["use_bias"] or m["use_qkv_bias"] or m["num_shared_experts"] \
            != 1 or any(m[k][L] for L in run for k in (
                "expert_swiglu_limit_list", "share_expert_swiglu_limit_list")):
        raise ValueError(
            "the program has a sigmoid router with a selection bias and "
            "noaux_tc's group limit, MLA without query compression under "
            "plain interleaved rope, KDA with the safe gate, a full-rank "
            "decay projection and SiLU after the convolutions, a head-wise "
            "output gate, one shared expert, no bias and no SwiGLU clamp "
            "on the layers run")
    kw = dict(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        intermediate_size=m["intermediate_size"],
        moe_intermediate_size=m["moe_intermediate_size"],
        shared_intermediate_size=m["moe_shared_expert_intermediate_size"],
        num_layers=m["num_hidden_layers"], first_layer=first,
        layer_group_size=m["layer_group_size"],
        first_k_dense_replace=m["first_k_dense_replace"],
        num_heads=m["num_attention_heads"], head_dim=m["head_dim"],
        kv_lora_rank=m["kv_lora_rank"],
        qk_nope_head_dim=m["qk_nope_head_dim"],
        qk_rope_head_dim=m["qk_rope_head_dim"], v_head_dim=m["v_head_dim"],
        short_conv_kernel_size=m["short_conv_kernel_size"],
        kda_lower_bound=float(m["kda_lower_bound"]),
        group_norm_size=m["group_norm_size"],
        num_experts=router_width(m),
        num_experts_per_tok=m["num_experts_per_tok"],
        n_group=m["n_group"], topk_group=m["topk_group"],
        norm_topk_prob=bool(m["norm_topk_prob"]),
        routed_scaling_factor=float(m["routed_scaling_factor"]),
        held_first=int(m.get("held_first", 0)),
        held_experts=m["n_routed_experts"],
        rope_theta=float(m["rope_theta"]), rms_eps=m["rms_norm_eps"],
        dtype=jnp.bfloat16)
    kw.update(over)
    cfg = ling_hybrid.LingHybridConfig(**kw)
    _hold_the_state_s_precision(m, cfg)
    return cfg


def _hold_the_state_s_precision(m: Dict, cfg) -> None:
    """The configuration states the precision its matrix state is kept in
    (``serve.state_dtype``), and a run is of THAT configuration: the served
    tokens cannot tell a state kept in bf16 from one in float32 (the
    limits file has the readings), so what ``correct`` cannot see is held
    here, where the harness builds the program. Shapes only: nothing is
    allocated."""
    import jax

    want = m.get("serve", {}).get("state_dtype")
    if want is None:
        return
    served = cfg.served_model()
    state = jax.eval_shape(lambda: served.make_state(1))
    got = {n: str(state[n].dtype) for n in served.state_in_place}
    if set(got.values()) != {want}:
        raise ValueError(
            f"the configuration states its KDA matrix state in {want} "
            f"(serve.state_dtype); the program keeps {got}: a state in "
            "another precision is another configuration, not a faster run "
            "of this one")


def engine_kwargs(model: Dict) -> Dict:
    return {}


def trainer(model: Dict):
    raise NotImplementedError("the ling_hybrid family is served, not "
                              "trained, by this benchmark")


# -- seeded weights -----------------------------------------------------------
def layer_kind(model: Dict, l: int) -> str:
    return ("mla" if _ref.is_mla(model, l) else "kda") + (
        "-dense" if _ref.is_dense(model, l) else "-moe")


def layer_shapes(m: Dict, l: int) -> Dict[str, tuple]:
    h, H, d = m["hidden_size"], m["num_attention_heads"], m["head_dim"]
    if _ref.is_mla(m, l):
        dn, dr, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
        r = m["kv_lora_rank"]
        out = {"w_q": (h, H * (dn + dr)), "w_dkv": (h, r + dr),
               "w_ukv": (r, H * (dn + dv)), "w_g": (h, H),
               "w_o": (H * dv, h)}
    else:
        K = m["short_conv_kernel_size"]
        out = {"w_q": (h, H * d), "w_k": (h, H * d), "w_v": (h, H * d),
               "conv_q": (H * d, K), "conv_k": (H * d, K),
               "conv_v": (H * d, K), "w_f": (h, H * d), "a_log": (H,),
               "dt_bias": (H * d,), "w_beta": (h, H), "w_g": (h, H),
               "w_o": (H * d, h)}
    if _ref.is_dense(m, l):
        f = m["intermediate_size"]
        out.update(w_gate=(h, f), w_up=(h, f), w_down=(f, h))
    else:
        f, E = m["moe_intermediate_size"], m["n_routed_experts"]
        fs = m["moe_shared_expert_intermediate_size"]
        W = router_width(m)
        out.update(router=(h, W), expert_bias=(W,), s_gate=(h, fs),
                   s_up=(h, fs), s_down=(fs, h), e_gate=(E, h, f),
                   e_up=(E, h, f), e_down=(E, f, h))
    return out


def make_layer(m: Dict, key, l: int, dtype):
    """Layer ``l`` (a Python int) in the published layout: each leaf from
    a key of its own, folded from the leaf's name and the layer."""
    import jax
    import jax.numpy as jnp

    res = 1.0 / math.sqrt(2 * m["num_hidden_layers"])
    h, H, d = m["hidden_size"], m["num_attention_heads"], m["head_dim"]
    out = {}
    for i, (name, shape) in enumerate(sorted(layer_shapes(m, l).items())):
        k = jax.random.fold_in(jax.random.fold_in(key, 1000 + i), l)
        if name == "a_log":
            out[name] = jax.random.uniform(k, shape, jnp.float32, -0.7, 0.7)
            continue
        if name == "dt_bias":
            out[name] = jax.random.uniform(k, shape, jnp.float32, *DT_BIAS)
            continue
        w = jax.random.normal(k, shape, jnp.float32)
        if name == "expert_bias":
            w = w * EXPERT_BIAS_SCALE
        elif name.startswith("conv_"):
            w = w / math.sqrt(shape[-1])
        else:
            w = w / math.sqrt(shape[-2]) * (
                res if name in _RESIDUAL_OUT else 1.0)
        out[name] = w if name in _FLOAT32 else w.astype(dtype)
    out["attn_norm"] = jnp.ones((h,), dtype)
    out["mlp_norm"] = jnp.ones((h,), dtype)
    if _ref.is_mla(m, l):
        out["kv_norm"] = jnp.ones((m["kv_lora_rank"],), dtype)
    else:
        out["o_norm"] = jnp.ones((H * d,), dtype)
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
    from paddle_tpu.models import ling_hybrid

    cfg = program_config(m)
    layers = [ling_hybrid.from_published(make_layer(m, key, l, dtype), cfg)
              for l in range(m["num_hidden_layers"])]
    return {"embed": make_top(m, key, "embed", dtype), "layers": layers,
            "final_norm": make_top(m, key, "final_norm", dtype),
            "lm_head": make_top(m, key, "lm_head", dtype)}


# -- the yardstick ------------------------------------------------------------
class reference:
    """``reference/ling_hybrid_f32.py`` as the comparison calls it."""

    Q_BLOCK = _ref.Q_BLOCK
    layer = staticmethod(_ref.layer)
    head_logits = staticmethod(_ref.head_logits)

    @staticmethod
    def embed(tokens, top):
        import jax.numpy as jnp

        return top["embed"].astype(jnp.float32)[tokens]


def tiny(model: Dict) -> Dict:
    """Every mechanism kept: the three kinds of layer (KDA-dense, MLA-moe,
    KDA-moe: published layers 1 to 3 at a period of 3), 4 heads of 32 with
    a matrix state each, rope and nope parts, 4 groups of 8 experts with a
    limit of 2 and top-3, a share of two whole groups (16 of 32, from 8), a
    shared expert."""
    return {"hidden_size": 64, "intermediate_size": 128,
            "moe_intermediate_size": 32,
            "moe_shared_expert_intermediate_size": 32,
            "num_attention_heads": 4, "num_key_value_heads": 4,
            "head_dim": 32, "kv_lora_rank": 128, "qk_nope_head_dim": 32,
            "qk_rope_head_dim": 16, "qk_head_dim": 48, "rotary_dim": 16,
            "v_head_dim": 32, "num_experts": 16, "n_routed_experts": 16,
            "router_width": 32, "held_first": 8, "n_group": 4,
            "topk_group": 2, "num_experts_per_tok": 3, "vocab_size": 256,
            "layer_group_size": 3, "first_layer": 1,
            "num_hidden_layers": 3}
