"""Roofline shares and counts of a cell whose layers come in two kinds,
window and full: what a window layer MAY see of a slot is the last
``sliding_window`` tokens, so the work of its walk is not a function of
the mean context, and ``trace_roofline`` / ``moe_trace_roofline`` (which
take the mean) cannot count it. This reader takes each token the client
received while tracing (one slot of one decode step, whose context was
prompt + tokens before it) and gives the family's costs module the
visible tokens of each kind. ``what``:

decode   runs of the decode program: fixed weights + the hit experts'
         weights once a step, both kinds' visible cache rows once
walk     one kind's walk kernel (``kind``: ``full`` | ``window``): its
         operations' time against max(FLOPs, bytes) of the tokens its
         layers may see, so a window walk that reads behind the window
         shows a LOW share, not more work
share    ``window_bytes / kv_bytes`` (%) summed over the window's
         ``serving.decode`` spans: the part of the walks' bytes that the
         window bounds

``program`` ({pattern, contains, lacks}) picks program runs and ``op`` a
kernel's operations, as in ``trace_roofline``. A program whose spans lack
the attributes, or a trace without the kernel, reads nothing. A share over
105% is refused: the work would be counted too high or the time would
leave part of it out."""
from benchmark import trace
from benchmark.manifest import family_of
from benchmark.readers_util import decoding_steps


def _decode_spans(rec, t0, t1):
    return [s["attrs"] for s in rec.get("spans", [])
            if s["name"] == "serving.decode" and t0 <= s["t0"] <= t1]


def _traced_load(rec, window):
    """(pure decode steps, every step that decoded, slot-steps, visible
    tokens of a full layer, of a window layer) while tracing, the last
    three summed over the tokens the client received there: from every
    step that decoded, a piece that carried the decode rows too
    (``readers_util.decoding_steps``)."""
    span = rec.get("trace_span")
    if not span or "client" not in rec:
        return None
    steps, decoded = decoding_steps(rec, *span)
    if not steps:
        return None
    t_open = rec["t_open"]
    toks = full = win = 0
    for s in rec["client"]["streams"]:
        for i, t in enumerate(s["t_tokens"]):
            if i and span[0] <= t + t_open <= span[1]:
                toks += 1
                full += s["prompt_len"] + i
                win += min(s["prompt_len"] + i, window)
    return steps, decoded, toks, full, win


def read(rec, what, kind=None, program=None, op=None):
    if what == "share":
        if "t_open" not in rec:
            return None
        have = [a for a in _decode_spans(rec, rec["t_open"], rec["t_close"])
                if "window_bytes" in a and a.get("kv_bytes")]
        total = sum(a["kv_bytes"] for a in have)
        return (100.0 * sum(a["window_bytes"] for a in have) / total
                if total else None)
    red, span = rec.get("trace"), rec.get("trace_span")
    if not red or not span:
        return None
    m, peak = rec["model"], rec["peak"]
    if "sliding_window" not in m:
        return None
    costs = family_of(m).costs
    runs = trace.module_runs(red, **program) if program else None
    seconds = (trace.op_seconds(red, op, None, runs) if op
               else sum(d for _s, d in runs) / 1e9)
    load = _traced_load(rec, m["sliding_window"])
    if not seconds or not load:
        return None
    steps, decoded, toks, full, win = load
    if what == "walk":
        flops, nbytes = costs.walk_cost(m, kind,
                                        full if kind == "full" else win)
    elif what == "decode":
        have = [a for a in _decode_spans(rec, *span) if "expert_rows" in a]
        if not have:
            return None
        # per step, as the costs function counts one step: the load a
        # mean over every step that decoded, the experts' counts and the
        # work over the decode program's own steps
        flops, nbytes = costs.decode_step_cost(
            m, toks / decoded, full / decoded,
            expert_rows=sum(a["expert_rows"] for a in have) / steps,
            experts_hit=sum(a["experts_hit"] for a in have) / steps,
            window_tokens=win / decoded)
        flops, nbytes = flops * steps, nbytes * steps
    else:
        raise ValueError(f"unknown work {what!r}")
    share = 100.0 * max(flops / peak.flops, nbytes / peak.hbm_bw) / seconds
    if share > 105.0:
        raise ValueError(f"{what} roofline share {share:.1f}% > 105%: the "
                         "work is counted too high or the time leaves part "
                         "of it out")
    return share
