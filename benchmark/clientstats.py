"""From the client's records to latencies and counts.

All times are seconds from the window's opening on the client's clock. A
request is timed from when it was due, not from when it was sent; how late
the generator ran is reported beside it. A request that failed, was refused
or never produced a token counts in ``failed`` and takes the worst latency
there is, the length of the run.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def ok(s: Dict) -> bool:
    return (s["status"] == 200 and s["reason"] == "finished"
            and len(s["tokens"]) == s["max_new"]
            and s["tokens"] == s["terminal_tokens"])


def judged(result: Dict) -> List[Dict]:
    """The requests the window is judged on: those due inside it (open
    loop), or every one that ended or was cut inside it (closed loop)."""
    return [s for s in result["streams"] if s["in_window"]]


def series(result: Dict, field: str) -> np.ndarray:
    seconds = result["seconds"]
    worst = 1000.0 * (seconds + 60.0)
    out: List[float] = []
    if field == "ttft_ms":
        for s in judged(result):
            if s["due"] is None:
                continue
            good = s["t_tokens"] and (ok(s) or s["cut"])
            out.append(1000.0 * (s["t_tokens"][0] - s["due"]) if good
                       else worst)
    elif field == "late_ms":
        out = [1000.0 * (s["sent"] - s["due"]) for s in judged(result)
               if s["due"] is not None]
    elif field == "itl_ms":
        for s in result["streams"]:
            t = np.asarray(s["t_tokens"])
            if len(t) > 1:
                gaps = np.diff(t)
                out.extend((1000.0 * gaps[(t[1:] >= 0) & (t[1:] <= seconds)]
                            ).tolist())
    else:
        raise ValueError(f"unknown client series {field!r}")
    return np.asarray(out, dtype=float)


def stat(values: np.ndarray, how: str):
    if len(values) == 0:
        return None
    if how == "mean":
        return float(values.mean())
    if how == "max":
        return float(values.max())
    if how.startswith("p"):
        return float(np.percentile(values, float(how[1:])))
    raise ValueError(f"unknown statistic {how!r}")


def tokens_in_window(result: Dict) -> int:
    seconds = result["seconds"]
    return int(sum(sum(1 for t in s["t_tokens"] if 0 <= t <= seconds)
                   for s in result["streams"]))


def attempted_failed(result: Dict):
    js = judged(result)
    failed = sum(1 for s in js if not (ok(s) or s["cut"]))
    return len(js) + result.get("unsent", 0), failed + result.get("unsent", 0)
