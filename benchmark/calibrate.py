#!/usr/bin/env python3
"""The readings that the limits of ``correct`` are set from.

``python3 benchmark/calibrate.py --workload <name> --seeds 1,2,3 [--control
1] [--faults 1] [--seconds 15]`` runs, in one process and on the chips, the cell's
sound path on each seed (a short window at the cell's own load for a
serving cell; the first steps for a training cell) and prints every number
the check compares; with ``--control 1`` it also prints what the control
gives: the reference computed in int8, the nearest precision below the
bf16 the configurations state. The benchmark's own runs never call this;
``PERF.md`` holds the readings and ``limits/<workload>.json`` the limits
set from them.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:] = [ROOT] + [p for p in sys.path
                        if os.path.abspath(p or ".") not in (HERE, ROOT)]

from benchmark import correct, manifest, run, traffic  # noqa: E402


def serve_seed(model, spec, seed, seconds, control, faults, devices):
    from benchmark import serve_cell

    plan = traffic.make_plan(spec, seed, seconds)
    eng, front, params = serve_cell.build(model, seed, run.log)
    serve_cell.warm(eng, model, plan, run.log)
    _host, port = front.start()
    rec = serve_cell.drive(port, model, spec, plan, seed, seconds, None,
                           run.log)
    serve_cell.close(front)
    samples = serve_cell.sample_finished(rec["client"], seed,
                                         int(spec["check_requests"]))
    serve_cell.free(eng.pools, params)
    del eng, front, params
    out = correct.served_gaps(model, seed, samples,
                              "int8" if control else None)
    out["stream_faults"] = correct.stream_faults(rec["client"],
                                                 model["vocab_size"])
    return out


def train_seed(model, spec, seed, seconds, control, faults, devices):
    from benchmark import serve_cell, train_cell
    from benchmark.reference import train_ref

    plan = traffic.make_plan(spec, seed, seconds)
    built = train_cell.build(model, plan, seed, devices, run.log)
    feed = train_cell.Feed(seed, plan, model["vocab_size"], built["rows"])
    try:
        got = train_cell.first_steps(built, model, feed, seed, run.log)
        again = None
        if faults:      # the same seed gives the same first loss
            serve_cell.free(built.pop("state"))
            feed.close()
            built = train_cell.build(model, plan, seed, devices, run.log)
            feed = train_cell.Feed(seed, plan, model["vocab_size"],
                                   built["rows"])
            again = train_cell.first_steps(built, model, feed, seed,
                                           run.log)["losses"][0]
    finally:
        feed.close()
    hp = built["hp"]
    serve_cell.free(built.pop("state"))
    del built
    batches = [traffic.train_batch(seed, i, plan["batch"], plan["seq"],
                                   model["vocab_size"])
               for i in range(train_cell.FOLLOWED_STEPS)]
    want = train_ref.follow(model, hp, seed, batches, devices)
    out = correct.train_numbers(got, want, got["losses"])
    out["losses"] = got["losses"]
    if again is not None:
        out["first_loss_again_gap"] = abs(again - got["losses"][0])
    if control:
        ctl = train_ref.follow(model, hp, seed, batches, devices, "int8")
        # the control in the program's place: its numbers against the
        # reference's
        ctl["losses"] = ctl["losses"] + got["losses"][2:]
        out["control"] = correct.train_numbers(ctl, want, got["losses"])
    if faults:          # a step that leaves out half of the batch
        half = train_ref.follow(model, hp, seed,
                                [b[:plan["batch"] // 2] for b in batches],
                                devices)
        out["half_batch_loss_gap"] = correct.train_numbers(
            dict(got, losses=half["losses"]), want, got["losses"])["loss_gap"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--faults", type=int, choices=(0, 1), default=0,
                    help="training: also a batch with half its rows left "
                    "out, and the first loss of a second build")
    args = ap.parse_args(argv)
    man = manifest.Manifest()
    cell = man.workload(args.workload)
    model = man.config(cell["config"])
    spec = man.traffic(cell["traffic"])
    devices = run.find_devices(cell["chips"])
    run.configure_jax()
    fn = {"serve": serve_seed, "train": train_seed}[model["kind"]]
    for seed in (int(s) for s in args.seeds.split(",")):
        out = fn(model, spec, seed, args.seconds, bool(args.control),
                 bool(args.faults), devices)
        print("CALIBRATE " + json.dumps(
            {"workload": args.workload, "seed": seed, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
