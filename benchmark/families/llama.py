"""The llama family: Llama/Mistral-shaped dense decoders, and the interface
that every family module gives the harness.

A configuration file names its family (``"family": "llama"``), and
``manifest.load_family`` finds ``families/<family>.py``: the benchmark's
own, or one beside the data files that a later PR added. The harness knows
no model beyond these members (``manifest.FAMILY_MEMBERS``); ``model`` is
always the configuration file's dict, of which the harness itself reads
``family``, ``kind``, ``serve`` or ``train``, ``vocab_size`` and
``num_hidden_layers``, and the family whatever else it needs.

The program:

``program_config(model, **over)``
    The program's config object from the published keys.
``engine_kwargs(model)``
    What ``LLMEngine`` is given beyond the serve shape's keys.
``trainer(model)``
    The program's module whose ``make_shardings``, ``TrainState``,
    ``train_step`` and ``activation_mesh`` the training cell drives.

Seeded weights, in the tree the program accepts, every leaf from a key of
its own, so that the reference makes one layer again from the seed alone:

``layer_kind(model, l)``
    A hashable name of everything about layer ``l`` that is not its
    weights. Layers of one kind have leaves of the same shapes and run the
    same reference program.
``make_layer(model, key, l, dtype)``
    The leaves of layer ``l``. A family with one kind takes a traced ``l``
    (``make_params`` may ``vmap`` over it); a family whose layers differ in
    kind is always given a Python int and makes its layers one by one.
``make_top(model, key, name, dtype)``
    The leaf ``name`` of the tree that is not a layer.
``make_params(model, key, dtype)``
    The whole tree: ``"layers"`` (stacked on a leading axis, or a list
    where the kinds differ) and, under the names ``make_top`` takes, the
    rest. Called under ``jax.jit`` with the key as an argument.

The yardstick:

``reference``
    The family's plain float32 reference as the comparison calls it:
    ``Q_BLOCK`` (sequences are padded to a multiple), ``embed(tokens,
    top)``, ``layer(x, p, model, quant, l)`` (``l`` a Python int: the first
    layer of its kind, so that a kind compiles once), ``head_logits(x, top,
    model, quant)``, and for a family that is trained ``loss(params,
    tokens, model, quant, constrain, gather)``, ``adamw`` and
    ``clip_scale``. ``quant`` names the control's precision or is None.
``costs``
    The module whose ``decode_step_cost``, ``decode_attention_cost``,
    ``prefill_flops``, ``flash_cost`` and ``train_flops_per_token`` the
    roofline readers and ``mfu`` call.
``tiny(model)``
    The keys a CPU rehearsal shrinks, with their values.

Here: the reference is ``reference/llama_f32.py``, the costs are
``costs.py``, the program is ``paddle_tpu/models/llama.py``. The tree has
the layout of that module's ``init_params`` (a dict with the layers stacked
on a leading axis) and its scales (1/sqrt(fan_in), the residual outputs
divided by sqrt(2L)), which make the logits of unit scale.
"""
from __future__ import annotations

import math
from typing import Dict

from benchmark import costs  # noqa: F401  (a member of the interface)
from benchmark.reference import llama_f32 as _ref

LAYER_LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
_TOP = {"embed": 100, "lm_head": 101}


# -- the program --------------------------------------------------------------
def program_config(model: Dict, **over):
    import jax.numpy as jnp

    from paddle_tpu.models import llama

    if model.get("sliding_window") is not None:
        raise ValueError("the engine has no sliding-window attention")
    kw = dict(vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
              intermediate_size=model["intermediate_size"],
              num_layers=model["num_hidden_layers"],
              num_heads=model["num_attention_heads"],
              num_kv_heads=model["num_key_value_heads"],
              head_dim=model["head_dim"], rope_theta=model["rope_theta"],
              rms_eps=model["rms_norm_eps"],
              tie_embeddings=model["tie_word_embeddings"],
              dtype=jnp.bfloat16)
    kw.update(over)
    return llama.LlamaConfig(**kw)


def engine_kwargs(model: Dict) -> Dict:
    return {}


def trainer(model: Dict):
    from paddle_tpu.models import llama

    return llama


# -- seeded weights -----------------------------------------------------------
def layer_kind(model: Dict, l: int) -> str:
    return "decoder"


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


def make_layer(m: Dict, key, l, dtype):
    import jax
    import jax.numpy as jnp

    out = {}
    for i, name in enumerate(LAYER_LEAVES):
        k = jax.random.fold_in(jax.random.fold_in(key, i), l)
        w = jax.random.normal(k, layer_shapes(m)[name], jnp.float32)
        out[name] = (w * _scale(m, name)).astype(dtype)
    h = m["hidden_size"]
    out["attn_norm"] = jnp.ones((h,), dtype)
    out["mlp_norm"] = jnp.ones((h,), dtype)
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
    import jax
    import jax.numpy as jnp

    L = m["num_hidden_layers"]
    layers = jax.vmap(lambda l: make_layer(m, key, l, dtype))(jnp.arange(L))
    params = {"embed": make_top(m, key, "embed", dtype), "layers": layers,
              "final_norm": make_top(m, key, "final_norm", dtype)}
    if not m.get("tie_word_embeddings"):
        params["lm_head"] = make_top(m, key, "lm_head", dtype)
    return params


# -- the yardstick ------------------------------------------------------------
class reference:
    """``reference/llama_f32.py`` as the comparison calls it: every layer
    is the same, so the index is dropped."""

    Q_BLOCK = _ref.Q_BLOCK
    head_logits = staticmethod(_ref.head_logits)
    loss = staticmethod(_ref.loss)
    adamw = staticmethod(_ref.adamw)
    clip_scale = staticmethod(_ref.clip_scale)

    @staticmethod
    def embed(tokens, top):
        import jax.numpy as jnp

        return top["embed"].astype(jnp.float32)[tokens]

    @staticmethod
    def layer(x, p, model: Dict, quant, l: int):
        return _ref.layer(x, p, model, quant)


def tiny(model: Dict) -> Dict:
    return {"hidden_size": 64, "intermediate_size": 128,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 16, "vocab_size": 256, "num_hidden_layers": 2}
