"""The one traffic generator: a data file of parameters in, a plan out.

A traffic file names its ``kind`` and parameters. Whatever the seed, a plan
holds the same multiset of request lengths (laid on the quantile grid
(i + 1/2)/N of the file's distribution) and the same multiset of arrival
gaps (the quantile grid of the exponential), shuffled once by the file's
own ``order_seed``: which prompt goes with which output and which gap, and
in what order they come, is part of the traffic, as in a replayed trace.
``--seed`` chooses the token ids (and the weights). Measured on the chip
(PR 24): a fresh shuffle per seed moved tokens per second by 4% and the
median gap between tokens by 2%, and so did turning one sequence round to
another starting point, while two runs of one sequence agreed within 0.3%:
which requests meet in one padded prefill wave decides what the wave
costs, so the order is work, and a seed may not change the work.

kinds
  open_poisson    requests due at fixed times, whatever the system does
  closed_backlog  ``clients`` callers that each send their next request
                  when the last one ends
  train_stream    batches of token rows for a training step
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np


def quantile_lengths(dist: Dict, n: int) -> List[int]:
    """``n`` lengths on the quantile grid of ``dist``, clipped."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    nd = NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        x = dist["median"] * math.exp(dist["sigma"] * z)
        out.append(int(min(dist["max"], max(dist["min"], round(x)))))
    return out


def exponential_gaps(n: int, total: float) -> List[float]:
    """``n`` gaps on the exponential's quantile grid, scaled to ``total``."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    s = total / sum(raw)
    return [g * s for g in raw]


def _requests(spec: Dict, n: int, tag: int,
              gaps_over: float = 0.0) -> List[Dict]:
    """``n`` requests: both length grids (and, for an open loop, the grid
    of n + 1 gaps that spans ``gaps_over`` seconds, so that every arrival
    lies inside), each shuffled by the file's ``order_seed``."""
    rng = np.random.default_rng([int(spec.get("order_seed", 0)), tag, n])
    p = quantile_lengths(spec["prompt"], n)
    o = quantile_lengths(spec["output"], n)
    p = [p[i] for i in rng.permutation(n)]
    o = [o[i] for i in rng.permutation(n)]
    reqs = [{"prompt_len": a, "max_new": b} for a, b in zip(p, o)]
    if gaps_over:
        gaps = exponential_gaps(n + 1, gaps_over)
        for r, i in zip(reqs, rng.permutation(n + 1)):
            r["gap"] = gaps[i]
    for i, r in enumerate(reqs):
        r["tag"] = [tag, i]
    return reqs


def prompt_tokens(seed: int, tag, n: int, vocab: int) -> List[int]:
    """The token ids of one request, from the seed and the request's tag."""
    rng = np.random.default_rng([int(seed), int(tag[0]), int(tag[1])])
    return rng.integers(0, vocab, size=n).tolist()


def open_poisson(spec: Dict, seed: int, seconds: float,
                 tail_s: float = 0.0) -> Dict:
    rate, lead = float(spec["rate_per_s"]), float(spec["lead_in_s"])
    plan = []
    parts = [(-lead, lead), (0.0, float(seconds))]
    if tail_s:          # a traced run goes on past the window at the rate
        parts.append((float(seconds), float(tail_s)))
    for tag, (t0, span) in enumerate(parts):
        n = max(1, round(rate * span))
        reqs = _requests(spec, n, tag, gaps_over=span)
        t = t0
        for r in reqs:
            t += r.pop("gap")
            r["due"] = t
            r["in_window"] = tag == 1
        plan += reqs
    return {"mode": "open", "requests": plan, "lead_in_s": lead,
            "tail_s": float(tail_s)}


def closed_backlog(spec: Dict, seed: int, seconds: float,
                   tail_s: float = 0.0) -> Dict:
    epoch = int(spec["epoch"])
    need = int(math.ceil(float(spec["max_requests_per_s"])
                         * (float(seconds) + float(spec["lead_in_s"])
                            + float(tail_s))))
    plan: List[Dict] = []
    tag = 0
    while len(plan) < need + int(spec["clients"]):
        plan += _requests(spec, epoch, tag)
        tag += 1
    return {"mode": "closed", "requests": plan, "clients": int(spec["clients"]),
            "lead_in_s": float(spec["lead_in_s"]), "tail_s": float(tail_s)}


def train_stream(spec: Dict, seed: int, seconds: float,
                 tail_s: float = 0.0) -> Dict:
    return {"mode": "train", "batch": int(spec["batch"]),
            "seq": int(spec["seq"])}


def train_batch(seed: int, step: int, batch: int, seq: int, vocab: int):
    """Rows of ``seq + 1`` token ids for one step; every row differs."""
    rng = np.random.default_rng([int(seed), 3, int(step)])
    return rng.integers(0, vocab, size=(batch, seq + 1), dtype=np.int32)


GENERATORS = {"open_poisson": open_poisson, "closed_backlog": closed_backlog,
              "train_stream": train_stream}


def make_plan(spec: Dict, seed: int, seconds: float,
              tail_s: float = 0.0) -> Dict:
    try:
        gen = GENERATORS[spec["kind"]]
    except KeyError:
        raise ValueError(f"unknown traffic kind {spec.get('kind')!r}; "
                         f"one of {sorted(GENERATORS)}") from None
    return gen(spec, seed, seconds, tail_s)
