"""What several trace readers share: the work that fell inside the traced
stretch, from the program's host spans and the load generator's records."""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple


def prefill_waves(rec: Dict, t0: float, t1: float) -> List[Dict]:
    """The prefill waves dispatched in [t0, t1], from the program's
    ``serving.prefill`` spans: each with its padded ``batch`` and ``bucket``
    and the real prompt lengths of its rows (``rows``), looked up by request
    id in what the load generator sent. A wave with a request the client
    does not know (a warm-up) is left out."""
    if "client" not in rec:
        return []
    plen = {s["request_id"]: s["prompt_len"]
            for s in rec["client"]["streams"] if s["request_id"] is not None}
    out = []
    for s in rec.get("spans", []):
        if s["name"] == "serving.prefill" and t0 <= s["t0"] <= t1:
            ids = s["attrs"].get("request_ids") or []
            if ids and all(i in plen for i in ids):
                out.append({"batch": s["attrs"]["batch"],
                            "bucket": s["attrs"]["bucket"],
                            "rows": [plen[i] for i in ids]})
    return out


def traced_prefill_rows(rec: Dict) -> List[List[int]]:
    """Real prompt lengths of each prefill wave dispatched while tracing."""
    span = rec.get("trace_span")
    return [w["rows"] for w in prefill_waves(rec, *span)] if span else []


def decoding_steps(rec: Dict, t0: float, t1: float) -> Tuple[int, int]:
    """(pure decode steps, every step that decoded) dispatched in [t0, t1]:
    the program's ``serving.decode`` spans, and with them the
    ``serving.prefill`` spans whose piece carried the decode rows
    (``decode_slots`` > 0: such a step emits tokens and has no
    ``serving.decode`` span)."""
    pure = carried = 0
    for s in rec.get("spans", []):
        if t0 <= s["t0"] <= t1:
            pure += s["name"] == "serving.decode"
            carried += (s["name"] == "serving.prefill"
                        and s["attrs"].get("decode_slots", 0) > 0)
    return pure, pure + carried


def traced_decode_load(rec: Dict) -> Optional[Tuple[int, float, float, int]]:
    """(pure decode steps, mean active slots, mean live context tokens,
    steps that decoded) while tracing. The load comes from the tokens the
    client received (each token received stands for one slot of one step
    whose context was prompt + tokens before it), as a mean over EVERY step
    that decoded, a piece that carried the decode rows too. The decode
    program ran in the pure steps only, so its work is one step's load
    times the first count; a walk kernel runs in both kinds of program,
    and its work is the load times the last."""
    span = rec.get("trace_span")
    if not span or "client" not in rec:
        return None
    steps, decoded = decoding_steps(rec, *span)
    if not steps:
        return None
    t_open = rec["t_open"]
    toks = live = 0
    for s in rec["client"]["streams"]:
        for i, t in enumerate(s["t_tokens"]):
            if i and span[0] <= t + t_open <= span[1]:
                toks += 1
                live += s["prompt_len"] + i
    return steps, toks / decoded, live / decoded, decoded


def traced_train_steps(rec: Dict) -> int:
    return int(rec.get("traced_steps", 0))
