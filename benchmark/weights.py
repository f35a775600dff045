"""Seeded random weights, made by the benchmark and handed to the program.

Every leaf is drawn from its own key, so the plain reference can make one
layer again from the seed alone and takes nothing that the program has
made. Which leaves, in which tree and at which scales, is the family's
(``families/<family>.py``, found by the configuration's ``family``); this
module makes the key and passes the calls on.
"""
from __future__ import annotations

from typing import Callable, Dict, List

from .manifest import family_of


def seed_key(seed: int):
    """A PRNG key from any whole number: ``--seed`` may exceed 31 bits."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def make_layer(m: Dict, key, layer, dtype):
    """The leaves of one layer (``layer`` may be traced where the family
    has one kind of layer)."""
    return family_of(m).make_layer(m, key, layer, dtype)


def make_top(m: Dict, key, name: str, dtype):
    """The leaf ``name`` of the tree that is not a layer."""
    return family_of(m).make_top(m, key, name, dtype)


def make_params(m: Dict, key, dtype):
    """The whole tree. Call under ``jax.jit`` with the key as an argument,
    so that one compiled program serves every seed."""
    return family_of(m).make_params(m, key, dtype)


def top_names(m: Dict):
    """The names of the tree's leaves that are not layers."""
    import jax
    import jax.numpy as jnp

    tree = jax.eval_shape(lambda k: make_params(m, k, jnp.float32),
                          seed_key(0))
    return sorted(n for n in tree if n != "layers")


def layer_kinds(m: Dict) -> List:
    """Each layer's kind, in order."""
    fam = family_of(m)
    return [fam.layer_kind(m, l) for l in range(m["num_hidden_layers"])]


def layer_maker(m: Dict, dtype) -> Callable:
    """``make(key, l)`` for a Python ``l``: that layer's leaves as
    ``make_params`` draws them. Where every layer is of one kind, one
    program with ``l`` traced; where the kinds differ, the family is given
    a static ``l``, and each layer is a (small) program of its own."""
    import jax

    one_kind = len(set(layer_kinds(m))) == 1
    return jax.jit(lambda k, l: make_layer(m, k, l, dtype),
                   static_argnums=() if one_kind else 1)
