"""Roofline shares of the kernels that move a PER-SLOT STATE (a
linear-attention layer's matrix a head), and the state's share of a decode
step's memory traffic, from the counts the program's own spans carry and
the family's costs module (``kda_step_cost``, ``kda_chunk_cost``). A
program whose spans or trace lack them reads nothing. ``what``:

kda_step          the one-token state update's operations (``op``) in
                  every program that decodes (the decode program, and a
                  piece that carries the decode rows): the live slots'
                  state read and written once a step
kda_chunk         the chunked scan's operations (``op``) in the prefill
                  programs: each traced piece's ``scan_tokens`` (real
                  tokens x the layers whose state the scan advanced, as
                  the ``serving.prefill`` span counts them)
state_walk_share  over the window's steps that decoded (the
                  ``serving.decode`` spans, and the ``serving.prefill``
                  spans of pieces that carried the decode rows),
                  ``state_bytes`` over ``state_bytes + kv_bytes``, in
                  percent: what of the memory a step walks is per-slot
                  state and not cache rows

A share over 105% is refused: the work would be counted too high or the
time would leave part of it out.
"""
from benchmark import trace
from benchmark.manifest import family_of
from benchmark.readers_util import traced_decode_load


def _spans(rec, name, t0, t1):
    return [s["attrs"] for s in rec.get("spans", [])
            if s["name"] == name and t0 <= s["t0"] <= t1]


def read(rec, what, op=None):
    if what == "state_walk_share":
        if "t_open" not in rec:
            return None
        t0, t1 = rec["t_open"], rec["t_close"]
        steps = [a for a in _spans(rec, "serving.decode", t0, t1)
                 + [p for p in _spans(rec, "serving.prefill", t0, t1)
                    if p.get("decode_slots", 0) > 0]
                 if "state_bytes" in a and "kv_bytes" in a]
        state = sum(a["state_bytes"] for a in steps)
        total = state + sum(a["kv_bytes"] for a in steps)
        return 100.0 * state / total if total else None
    red, span = rec.get("trace"), rec.get("trace_span")
    if not red or not span:
        return None
    seconds = trace.op_seconds(red, op)
    if not seconds:
        return None
    m, peak = rec["model"], rec["peak"]
    costs = family_of(m).costs
    if what == "kda_step":
        load = traced_decode_load(rec)
        if not load:
            return None
        _steps, slots, _live, decoded = load
        flops, nbytes = costs.kda_step_cost(m, slots)
        flops, nbytes = flops * decoded, nbytes * decoded
    elif what == "kda_chunk":
        rows = [int(a["scan_tokens"])
                for a in _spans(rec, "serving.prefill", *span)
                if "scan_tokens" in a]
        if not rows:
            return None
        flops, nbytes = costs.kda_chunk_cost(m, rows)
    else:
        raise ValueError(f"unknown work {what!r}")
    share = 100.0 * max(flops / peak.flops, nbytes / peak.hbm_bw) / seconds
    if share > 105.0:
        raise ValueError(f"{what} roofline share {share:.1f}% > 105%: the "
                         "work is counted too high or the time leaves part "
                         "of it out")
    return share
