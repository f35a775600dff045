"""The plain reference follows the first two training steps.

Float32 at ``highest`` matmul precision, AdamW as published, weights made
again from the seed by ``benchmark/weights.py``, the loss and the update the
family's reference (``families/<family>.py``): nothing of the program is
used. It reports what the ``correct`` check compares: each step's loss, the
norm of the first gradient as the optimizer gets it (after the global-norm
clip) leaf by leaf, and the norm of the parameters' change after the two
steps, leaf by leaf. A "leaf" is one matrix of one layer.

Two steps and not three, because every run of every later check pays this
time: the second loss is taken at the parameters the first update made, so
it holds the update to the reference, and the change's norm holds the
second. To fit beside nothing else on four 16 GB chips the state is spread
over the devices given (rows of the batch too, the weights gathered layer
by layer), and Adam's second moment after one step is worked out from the
first ((1-b2)/(1-b1)^2 * mu^2) and not stored.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import weights
from ..manifest import family_of


def leaf_norms(tree) -> Dict[str, jnp.ndarray]:
    """Norm of every matrix: a vector over layers for the stacked leaves."""
    out = {}
    for name, w in tree["layers"].items():
        w = w.astype(jnp.float32)
        out["layers." + name] = jnp.sqrt(jnp.sum(
            w * w, axis=tuple(range(1, w.ndim))))
    for name, w in tree.items():
        if name != "layers":
            w = w.astype(jnp.float32)
            out[name] = jnp.sqrt(jnp.sum(w * w))[None]
    return out


def _spread(shape, n: int) -> P:
    """Storage layout of a leaf over ``n`` devices: the last axis that
    divides, never the layer axis."""
    for ax in range(len(shape) - 1, 0 if len(shape) > 2 else -1, -1):
        if shape[ax] % n == 0 and shape[ax] >= n:
            return P(*([None] * ax + ["x"]))
    return P()


def follow(m: Dict, hp: Dict, seed: int, batches: List[np.ndarray],
           devices, quant: Optional[str] = None) -> Dict:
    """Two reference steps on ``batches[0]`` and ``batches[1]``."""
    ref = family_of(m).reference
    mesh = Mesh(np.asarray(devices), ("x",))
    n = len(devices)
    rep = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P("x") if batches[0].shape[0] % n == 0
                         else P())
    act = NamedSharding(mesh, P(*rows.spec, None, None))
    key = weights.seed_key(seed)
    shapes = jax.eval_shape(lambda k: weights.make_params(m, k, jnp.float32),
                            key)
    psh = jax.tree_util.tree_map(
        lambda a: NamedSharding(mesh, _spread(a.shape, n)), shapes)
    make = jax.jit(lambda k: weights.make_params(m, k, jnp.float32),
                   out_shardings=psh)

    def gathered(p):
        return jax.tree_util.tree_map(
            lambda w: jax.lax.with_sharding_constraint(w, rep), p)

    def loss_fn(p, tok):
        # weights are stored spread out and gathered where they are used
        return ref.loss(p, tok, m, quant,
                        lambda x: jax.lax.with_sharding_constraint(x, act),
                        gathered)

    b1, b2 = hp["beta1"], hp["beta2"]

    def grad(p, tok):
        l, g = jax.value_and_grad(loss_fn)(p, tok)
        return l, g, ref.clip_scale(g, hp["clip_norm"])

    grad = jax.jit(grad, out_shardings=(rep, psh, rep))

    def first(p, g, s):
        # returns the new parameters and mu; nu is a function of mu here
        def one(p, g):
            p1, mu, _ = ref.adamw(p, g, 0.0, 0.0, 1, hp, s)
            return p1, mu
        out = jax.tree_util.tree_map(one, p, g)
        return (jax.tree_util.tree_map(lambda o: o[0], out,
                                       is_leaf=lambda o: isinstance(o, tuple)),
                jax.tree_util.tree_map(lambda o: o[1], out,
                                       is_leaf=lambda o: isinstance(o, tuple)))

    def second(p, g, mu, s):
        def one(p, g, mu):
            nu = (1 - b2) / (1 - b1) ** 2 * mu * mu
            return ref.adamw(p, g, mu, nu, 2, hp, s)[0]
        return jax.tree_util.tree_map(one, p, g, mu)

    first = jax.jit(first, donate_argnums=(0, 1), out_shardings=(psh, psh))
    second = jax.jit(second, donate_argnums=0, out_shardings=psh)
    norms = jax.jit(leaf_norms)
    delta = jax.jit(lambda a, b: leaf_norms(
        jax.tree_util.tree_map(lambda x, y: x - y, a, b)))

    with jax.default_matmul_precision("highest"):
        p = make(key)
        tok = [jax.device_put(jnp.asarray(b), rows) for b in batches[:2]]
        l1, g, s1 = grad(p, tok[0])
        gn = {k: np.asarray(v) * float(s1) for k, v in norms(g).items()}
        p, mu = first(p, g, s1)
        l2, g, s2 = grad(p, tok[1])
        p = second(p, g, mu, s2)
        del g, mu
        dn = {k: np.asarray(v) for k, v in delta(p, make(key)).items()}
        out = {"losses": [float(l1), float(l2)], "grad_norms": gn,
               "delta_norms": dn}
    del p
    return out
