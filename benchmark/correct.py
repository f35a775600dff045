"""The comparison that decides ``correct``.

What the timed path produced is compared with the plain reference (the
family's: ``benchmark/reference/``), number by number, each against a limit
of its own kept in ``limits/<workload>.json`` with the readings it was set
from. Every run prints each number beside its limit. A number with no limit
in the file fails: a limit is never guessed here.
"""
from __future__ import annotations

import json
import os
from typing import Dict, List, Optional

import numpy as np

from . import traffic, weights
from .manifest import family_of


def judge(numbers: Dict[str, float], limits: Dict) -> Dict[str, Dict]:
    """Each number compared beside its limit, and whether it is at or under
    it: ``{name: {"value", "limit", "ok"}}``. A run is correct when all
    are."""
    out = {}
    for name, value in numbers.items():
        lim = limits.get(name, {}).get("limit")
        fine = (lim is not None and value is not None
                and np.isfinite(value) and value <= lim)
        if value is not None and not np.isfinite(value):
            value = repr(float(value))      # "nan" and "inf" are not JSON
        out[name] = {"value": value, "limit": lim, "ok": bool(fine)}
    return out


def lines(compared: Dict[str, Dict]) -> List[str]:
    """One line a number, as a run prints them."""
    return [f"correct: {name} = {c['value']!r} (limit {c['limit']!r}) "
            f"{'ok' if c['ok'] else 'FAIL'}" for name, c in compared.items()]


def load_limits(data_dir: str, workload: str) -> Dict:
    path = os.path.join(data_dir, "limits", workload + ".json")
    with open(path) as f:
        return json.load(f)


# -- serving ------------------------------------------------------------------
def stream_faults(result: Dict, vocab: int) -> int:
    """Streams that ended as finished yet are not what was asked for: the
    count differs, the streamed tokens are not the terminal frame's, or an
    id is outside the vocabulary. Every stream is looked at."""
    bad = 0
    for s in result["streams"]:
        if s["status"] == 200 and s["reason"] == "finished":
            if (len(s["tokens"]) != s["max_new"]
                    or s["tokens"] != s["terminal_tokens"]
                    or any(not 0 <= t < vocab for t in s["tokens"])):
                bad += 1
    return bad


def served_gaps(model: Dict, seed: int, samples: List[Dict],
                control: Optional[str] = None) -> Dict:
    """For each sampled request, one reference pass over its prompt and the
    tokens it was served: at every served position, how far the served
    token's reference logit lies below the reference's best. With
    ``control``, the same for the token that the lower precision puts
    first. The layers are made again from the seed one at a time; what a
    layer is, and the reference, are the family's."""
    import jax
    import jax.numpy as jnp

    ref = family_of(model).reference
    key = weights.seed_key(seed)
    vocab = model["vocab_size"]
    make = weights.layer_maker(model, jnp.bfloat16)
    names = weights.top_names(model)
    top = jax.jit(lambda k: {
        n: weights.make_top(model, k, n, jnp.bfloat16) for n in names})(key)
    # one compiled layer a kind: a kind's layers differ in their weights
    # only, so each runs the program of its kind's first layer
    kinds = weights.layer_kinds(model)
    layer = jax.jit(lambda x, p, q, l: ref.layer(x, p, model, q, l),
                    static_argnums=(2, 3))
    seqs = []
    for s in samples:
        ids = traffic.prompt_tokens(seed, s["tag"], s["prompt_len"], vocab) \
            + list(s["tokens"])
        pad = -len(ids) % ref.Q_BLOCK
        seqs.append(jnp.asarray([ids + [0] * pad], jnp.int32))
    def below_best(logits, tok):
        """How far each position's logit of ``tok`` lies below its best."""
        return logits.max(axis=-1) - jnp.take_along_axis(
            logits, tok[:, None], 1)[:, 0]

    # one program per padded length: the shapes repeat from run to run, so
    # the persistent cache holds them all after a cell's first run. The
    # weights are arguments: closed over, they would be baked into programs
    # of 600 MB that no cache keeps. Position i is judged on token i + 1.
    ends = jax.jit(lambda x, ids, top: below_best(
        ref.head_logits(x[0], top, model), jnp.roll(ids[0], -1)))
    ends_control = jax.jit(lambda x, xq, top: below_best(
        ref.head_logits(x[0], top, model),
        ref.head_logits(xq[0], top, model, control).argmax(axis=-1)))
    embed = jax.jit(ref.embed)
    with jax.default_matmul_precision("highest"):
        xs = [embed(t, top) for t in seqs]
        xc = list(xs) if control else []
        for l, kind in enumerate(kinds):
            p = make(key, l)
            first = kinds.index(kind)
            xs = [layer(x, p, None, first) for x in xs]
            xc = [layer(x, p, control, first) for x in xc]
        gaps, cgaps = [], []
        for i, s in enumerate(samples):
            # position prompt_len - 1 predicts the first served token
            lo, n = s["prompt_len"] - 1, len(s["tokens"])
            gaps.append(np.asarray(ends(xs[i], seqs[i], top))[lo:lo + n])
            if control:
                cgaps.append(np.asarray(
                    ends_control(xs[i], xc[i], top))[lo:lo + n])
    out = {"positions": int(sum(len(g) for g in gaps)),
           "logit_gap_max": float(max(g.max() for g in gaps)),
           "logit_gap_mean": float(np.concatenate(gaps).mean())}
    if control:
        out["control"] = {
            "logit_gap_max": float(max(g.max() for g in cgaps)),
            "logit_gap_mean": float(np.concatenate(cgaps).mean())}
    return out


# -- training -----------------------------------------------------------------
def worst_leaf_gap(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray]
                   ) -> float:
    """The largest gap between a leaf's norm and the reference's, against
    the reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero)."""
    ref_all = np.concatenate([np.ravel(v) for v in want.values()])
    floor = float(np.median(ref_all))
    worst = 0.0
    for name, w in want.items():
        g = np.ravel(got[name])
        w = np.ravel(w)
        worst = max(worst, float(np.max(np.abs(g - w)
                                        / np.maximum(w, floor))))
    return worst


def train_numbers(got: Dict, want: Dict, window_losses: List[float]) -> Dict:
    """What a training run compares: the followed steps' losses, the first
    gradient's norms, the parameters' change, and whether the loss fell
    over the window."""
    n = len(want["losses"])
    return {
        "loss_gap": float(max(abs(a - b) / abs(b) for a, b in
                              zip(got["losses"][:n], want["losses"]))),
        "grad_norm_gap": worst_leaf_gap(got["grad_norms"],
                                        want["grad_norms"]),
        "delta_norm_gap": worst_leaf_gap(got["delta_norms"],
                                         want["delta_norms"]),
        "loss_rise": float(window_losses[-1] - got["losses"][0]),
    }
