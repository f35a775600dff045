"""Roofline shares and counts of a chunked, sparse-expert serving cell,
from the counts that the program's own spans carry.

``trace_roofline``, ``prefill_fill`` and ``trace_program per="ktok"`` take a
prefill wave for whole prompts and a decode step for a fixed set of
weights. Here a wave holds PIECES of prompts and a step reads the weights
of the experts that had a row, so this reader takes each
``serving.prefill`` span's ``tokens`` and ``start`` (real tokens of each
row in that wave, and what of the row was cached before it) and each
``serving.decode`` / ``serving.prefill`` span's ``expert_rows`` and
``experts_hit`` (token-expert pairs computed here, held experts with a
row, summed over the expert layers), and gives them to the family's costs
module. A program whose spans lack them reads nothing. ``what``:

decode               runs of the decode program: fixed weights + the hit
                     experts' weights once a step, live latent rows once
latent_walk          the decode latent kernel's operations
prefill              runs of the prefill programs: the real pieces' FLOPs
prefill_attn         the prefill attention kernels' operations
expert_gmm           the routed experts' grouped matmuls, decode and
                     prefill runs alike
prefill_ms_per_ktok  device ms of the prefill runs per 1000 real tokens of
                     the traced waves
row_fill             real tokens over rows x bucket of the window's waves
expert_rows_per_step, experts_hit_share
                     means over the window's decode steps (the share is of
                     held experts x expert layers, in percent)

``program`` ({pattern, contains, lacks}) picks program runs and ``op`` /
``op_lacks`` a kernel's operations, as in ``trace_roofline``. A share over
105% is refused: the work would be counted too high or the time would
leave part of it out.
"""
from benchmark import trace
from benchmark.manifest import family_of
from benchmark.readers_util import traced_decode_load


def _spans(rec, name, t0, t1):
    return [s["attrs"] for s in rec.get("spans", [])
            if s["name"] == name and t0 <= s["t0"] <= t1]


def _pieces(spans):
    """(tokens, start) of every row of the waves that say so."""
    return [(int(t), int(h)) for a in spans if "tokens" in a and "start" in a
            for t, h in zip(a["tokens"], a["start"])]


def _experts(spans):
    """(expert_rows, experts_hit) summed over the spans, or None where no
    span carries them."""
    have = [a for a in spans if "expert_rows" in a]
    if not have:
        return None
    return (float(sum(a["expert_rows"] for a in have)),
            float(sum(a["experts_hit"] for a in have)))


def _window_counts(rec, what):
    if "t_open" not in rec:
        return None
    t0, t1 = rec["t_open"], rec["t_close"]
    if what == "row_fill":
        waves = [a for a in _spans(rec, "serving.prefill", t0, t1)
                 if "tokens" in a]
        padded = sum(a["batch"] * a["bucket"] for a in waves)
        return 100.0 * sum(sum(a["tokens"]) for a in waves) / padded \
            if padded else None
    steps = [a for a in _spans(rec, "serving.decode", t0, t1)
             if "expert_rows" in a]
    if not steps:
        return None
    if what == "expert_rows_per_step":
        return sum(a["expert_rows"] for a in steps) / len(steps)
    m = rec["model"]
    costs = family_of(m).costs
    full = m["n_routed_experts"] * costs.expert_layers(m)
    return 100.0 * sum(a["experts_hit"] for a in steps) / (len(steps) * full)


def read(rec, what, program=None, op=None, op_lacks=None):
    if what in ("row_fill", "expert_rows_per_step", "experts_hit_share"):
        return _window_counts(rec, what)
    red, span = rec.get("trace"), rec.get("trace_span")
    if not red or not span:
        return None
    m, peak = rec["model"], rec["peak"]
    costs = family_of(m).costs
    runs = trace.module_runs(red, **program) if program else None
    if op:
        seconds = trace.op_seconds(red, op, op_lacks, runs)
    else:
        seconds = sum(d for _s, d in runs) / 1e9
    if not seconds:
        return None
    decodes = _spans(rec, "serving.decode", *span)
    prefills = _spans(rec, "serving.prefill", *span)
    pieces = _pieces(prefills)
    if what in ("decode", "latent_walk"):
        load = traced_decode_load(rec)
        if not load:
            return None
        steps, slots, live, decoded = load
        if what == "latent_walk":
            # the kernel walks in every program that decodes, a piece that
            # carries the decode rows too
            flops, nbytes = costs.decode_attention_cost(m, slots, live)
            steps = decoded
        else:
            ex = _experts(decodes)
            if ex is None:
                return None
            flops, nbytes = costs.decode_step_cost(
                m, slots, live, expert_rows=ex[0] / steps,
                experts_hit=ex[1] / steps)
        flops, nbytes = flops * steps, nbytes * steps
    elif what in ("prefill", "prefill_ms_per_ktok", "prefill_attn"):
        if not pieces:
            return None
        if what == "prefill_ms_per_ktok":
            return 1e3 * seconds / (sum(t for t, _h in pieces) / 1000.0)
        if what == "prefill_attn":
            flops, nbytes = costs.flash_cost(
                m, [t for t, _h in pieces], starts=[h for _t, h in pieces])
        else:
            ex = _experts(prefills)
            if ex is None:
                return None
            # the head's FLOPs are left out: which pieces end a prompt is
            # not in the span, and they are 1e-4 of a piece's
            flops = sum(costs.prefill_flops(m, t, h, final=False)
                        for t, h in pieces) + costs.expert_gmm_cost(
                            m, ex[0], 0.0)[0]
            nbytes = 0.0
    elif what == "expert_gmm":
        ex = _experts(decodes + prefills)
        if ex is None:
            return None
        flops, nbytes = costs.expert_gmm_cost(m, *ex)
    else:
        raise ValueError(f"unknown work {what!r}")
    share = 100.0 * max(flops / peak.flops, nbytes / peak.hbm_bw) / seconds
    if share > 105.0:
        raise ValueError(f"{what} roofline share {share:.1f}% > 105%: the "
                         "work is counted too high or the time leaves part "
                         "of it out")
    return share
