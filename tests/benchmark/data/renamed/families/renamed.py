"""A second family, added by files alone (``test_bench_family.py``):
llama's mathematics under other configuration key names (``d_model``,
``d_ff``, ``n_heads``, ``n_kv_heads``, ``d_head``, ``rope_base``,
``norm_eps``) and another draw of the weights (its own keys and scales),
in the tree the engine accepts, so that the same engine serves it. With
``d_ff_first`` in the configuration, layer 0 has that FFN width: two kinds
of layer, made one by one at a static index (no engine serves those).
The interface is stated in ``benchmark/families/llama.py``."""
import math
import os

from benchmark import manifest

_HERE = os.path.dirname(os.path.abspath(__file__))
reference = manifest.load_file(
    os.path.join(_HERE, os.pardir, "reference", "renamed_f32.py"))
costs = manifest.load_file(os.path.join(_HERE, "renamed_costs.py"))
_LEAVES = ("w_down", "w_up", "w_gate", "wo", "wv", "wk", "wq")


def program_config(model, **over):
    import jax.numpy as jnp

    from paddle_tpu.models import llama

    kw = dict(vocab_size=model["vocab_size"], hidden_size=model["d_model"],
              intermediate_size=model["d_ff"],
              num_layers=model["num_hidden_layers"],
              num_heads=model["n_heads"], num_kv_heads=model["n_kv_heads"],
              head_dim=model["d_head"], rope_theta=model["rope_base"],
              rms_eps=model["norm_eps"], tie_embeddings=False,
              dtype=jnp.bfloat16)
    kw.update(over)
    return llama.LlamaConfig(**kw)


def engine_kwargs(model):
    return {}


def trainer(model):
    raise NotImplementedError("the renamed family is served only")


def layer_kind(model, l):
    return "first" if l == 0 and "d_ff_first" in model else "rest"


def _one_kind(model):
    return "d_ff_first" not in model


def _shapes(model, kind):
    h, d = model["d_model"], model["d_head"]
    f = model["d_ff_first"] if kind == "first" else model["d_ff"]
    nq, nkv = model["n_heads"], model["n_kv_heads"]
    return {"wq": (h, nq * d), "wk": (h, nkv * d), "wv": (h, nkv * d),
            "wo": (nq * d, h), "w_gate": (h, f), "w_up": (h, f),
            "w_down": (f, h)}


def make_layer(model, key, l, dtype):
    import jax
    import jax.numpy as jnp

    # a traced l is of the one kind there is; a static one may be the first
    kind = "rest" if _one_kind(model) else layer_kind(model, l)
    out = {}
    for i, name in enumerate(_LEAVES):
        shape = _shapes(model, kind)[name]
        k = jax.random.fold_in(jax.random.fold_in(key, 1000 + l), 7 * i)
        w = jax.random.normal(k, shape, jnp.float32)
        out[name] = (w * 0.8 / math.sqrt(shape[0])).astype(dtype)
    for name in ("attn_norm", "mlp_norm"):
        out[name] = jnp.ones((model["d_model"],), dtype)
    return out


def make_top(model, key, name, dtype):
    import jax
    import jax.numpy as jnp

    h, v = model["d_model"], model["vocab_size"]
    if name == "final_norm":
        return jnp.ones((h,), dtype)
    shape = {"embed": (v, h), "lm_head": (h, v)}[name]
    k = jax.random.fold_in(key, {"embed": 5, "lm_head": 6}[name])
    return (jax.random.normal(k, shape, jnp.float32)
            / math.sqrt(h)).astype(dtype)


def make_params(model, key, dtype):
    import jax
    import jax.numpy as jnp

    L = model["num_hidden_layers"]
    if _one_kind(model):
        layers = jax.vmap(lambda l: make_layer(model, key, l, dtype))(
            jnp.arange(L))
    else:
        layers = [make_layer(model, key, l, dtype) for l in range(L)]
    return {"layers": layers,
            **{n: make_top(model, key, n, dtype)
               for n in ("embed", "lm_head", "final_norm")}}


def tiny(model):
    return {"d_model": 64, "d_ff": 96, "n_heads": 4, "n_kv_heads": 2,
            "d_head": 16, "vocab_size": 256, "num_hidden_layers": 2}
