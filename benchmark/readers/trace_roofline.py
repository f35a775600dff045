"""A program's or kernel's share (%) of its roofline in the traced
stretch: the least time the chip could take for the work the algorithm
needs (the ``costs`` of the configuration's family, peaks from
``benchmark/peaks.py``) over the device time the trace shows. ``what`` picks
the work:

decode        runs of the decode program: weights once a step + live KV
prefill       runs of the prefill programs: the real prompt tokens' FLOPs
ragged_walk   the decode attention kernel's operations: live KV only
flash         the flash kernel's operations in the prefill programs
train_flash   the flash kernel's operations in the train step, fwd + bwd

``program`` ({pattern, contains, lacks}) picks program runs as
``trace_program`` does; ``op``/``op_lacks`` pick a kernel's operations by
their text, inside those runs where ``program`` is given. A share over 105%
is refused: the work would be counted too high or the time would leave
part of it out."""
from benchmark import trace
from benchmark.manifest import family_of
from benchmark.readers_util import (traced_decode_load, traced_prefill_rows,
                                    traced_train_steps)


def read(rec, what, program=None, op=None, op_lacks=None):
    red = rec.get("trace")
    if not red:
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
    if what in ("decode", "ragged_walk"):
        load = traced_decode_load(rec)
        if not load:
            return None
        steps, slots, live, decoded = load
        # the program ran in the pure decode steps; the kernel walks in
        # every program that decodes
        fn, n = ((costs.decode_step_cost, steps) if what == "decode"
                 else (costs.decode_attention_cost, decoded))
        flops, nbytes = fn(m, slots, live)
        flops, nbytes = flops * n, nbytes * n
    elif what in ("prefill", "flash"):
        rows = [t for r in traced_prefill_rows(rec) for t in r]
        if not rows:
            return None
        if what == "prefill":
            flops, nbytes = sum(costs.prefill_flops(m, t) for t in rows), 0.0
        else:
            flops, nbytes = costs.flash_cost(m, rows)
    elif what == "train_flash":
        steps = traced_train_steps(rec)
        if not steps:
            return None
        plan = rec["plan"]
        flops, nbytes = costs.flash_cost(
            m, [plan["seq"]] * plan["batch"] * steps, backward=True)
        flops, nbytes = flops / red["chips"], nbytes / red["chips"]
    else:
        raise ValueError(f"unknown work {what!r}")
    share = 100.0 * max(flops / peak.flops, nbytes / peak.hbm_bw) / seconds
    if share > 105.0:
        raise ValueError(f"{what} roofline share {share:.1f}% > 105%: the "
                         "work is counted too high or the time leaves part "
                         "of it out")
    return share
