#!/usr/bin/env python3
"""One run of one cell: ``python3 benchmark/run.py --workload <name>
--seed <n> --seconds <s> --trace <0|1>``.

Fails, with no result line, where JAX finds no TPU or fewer chips than the
cell asks for. Otherwise the last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device`` and, last, ``compared``: each number that decided ``correct``
beside its limit, which are also the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.monotonic()          # set-up is counted from here

import argparse                     # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import shutil                       # noqa: E402
import sys                          # noqa: E402
import tempfile                     # noqa: E402
from typing import Dict             # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# run as a script, this directory leads sys.path: its modules (trace,
# client, ...) must not stand in for others of the same name
sys.path[:] = [ROOT] + [p for p in sys.path
                        if os.path.abspath(p or ".") not in (HERE, ROOT)]

from benchmark import clientstats, correct, manifest, peaks  # noqa: E402
from benchmark import trace as trace_mod                     # noqa: E402
from benchmark import traffic                                # noqa: E402


def log(msg: str) -> None:
    print(f"[bench {time.monotonic() - T_START:7.1f}s] {msg}", flush=True)


def find_devices(chips: int):
    """The chips, or an error: a measurement never falls back to the CPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devs[0].platform} "
                         f"({devs[0].device_kind}); nothing is measured")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs[:chips]


def configure_jax() -> None:
    """Every program goes to the persistent cache, small and quick ones
    too, so that a second run compiles nothing. Where the cache is:
    ``paddle_tpu`` places it on import (JAX_COMPILATION_CACHE_DIR if set,
    else ``<checkout>/.jax_cache``)."""
    import jax

    import paddle_tpu  # noqa: F401

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def memory_peak(devices) -> int:
    """The backend's high-water mark on the fullest chip. The CPU backend
    of the rehearsals keeps none and reads 0; a TPU that keeps none is an
    error."""
    stats = [d.memory_stats() for d in devices]
    if not all(s and "peak_bytes_in_use" in s for s in stats):
        if devices[0].platform == "tpu":
            raise RuntimeError(f"a device reports no memory statistics: "
                               f"{stats}")
        return 0
    return max(int(s["peak_bytes_in_use"]) for s in stats)


class CompileLog:
    """When the backend compiled a program: the benchmark's own count of
    compilations inside the window (a hit in the persistent cache is not
    one)."""

    def __init__(self):
        import jax.monitoring

        self.times = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.times.append(time.monotonic())

    def between(self, t0, t1) -> int:
        return sum(t0 <= t <= t1 for t in self.times)


def reduce_trace(rec: Dict) -> None:
    rec["trace"] = None
    path = rec.get("trace_dir") and trace_mod.find(rec["trace_dir"])
    if path:
        red = trace_mod.reduce(trace_mod.load(path))
        if not red["chips"]:
            return              # no device plane: nothing ran on a chip
        rec["trace"] = red
        log(f"trace: {os.path.getsize(path)} bytes, busy "
            f"{rec['trace']['busy_s']:.3f}s of {rec['trace']['window_s']:.3f}s"
            f" on {rec['trace']['chips']} chip(s)")


def run_serve(model: Dict, spec: Dict, args, devices, trace_dir,
              compiles) -> Dict:
    from benchmark import serve_cell

    plan = traffic.make_plan(
        spec, args.seed, args.seconds,
        serve_cell.TRACE_TAIL_S if trace_dir else 0.0)
    eng, front, params = serve_cell.build(model, args.seed, log)
    serve_cell.warm(eng, model, plan, log)
    _host, port = front.start()
    rec = serve_cell.drive(port, model, spec, plan, args.seed, args.seconds,
                           trace_dir, log)
    # the lead-in before the window opens is set-up too
    rec["scalars"] = {"setup_s": rec["t_open"] - T_START,
                      "compiles_in_window": compiles.between(
                          rec["t_open"], rec["t_close"])}
    rec["plan"] = plan
    serve_cell.close(front)
    rec["memory_peak_bytes"] = memory_peak(devices)
    result = rec["client"]
    rec["attempted"], rec["failed"] = clientstats.attempted_failed(result)
    rec["scalars"]["tokens_per_s"] = (
        clientstats.tokens_in_window(result) / result["seconds"])
    if rec["memory_peak_bytes"]:
        rec["scalars"]["hbm_peak_gb"] = rec["memory_peak_bytes"] / 1e9
    samples = serve_cell.sample_finished(result, args.seed,
                                         int(spec["check_requests"]))
    serve_cell.free(eng.pools, params)
    del eng, front, params
    t0 = time.monotonic()
    numbers = {"stream_faults": correct.stream_faults(
        result, model["vocab_size"])}
    if samples:
        gaps = correct.served_gaps(model, args.seed, samples)
        numbers.update(logit_gap_max=gaps["logit_gap_max"],
                       logit_gap_mean=gaps["logit_gap_mean"])
        log(f"reference: {len(samples)} requests, {gaps['positions']} served "
            f"positions in {time.monotonic() - t0:.1f}s")
    else:
        numbers.update(logit_gap_max=None, logit_gap_mean=None)
    rec["numbers"] = numbers
    return rec


def run_train(model: Dict, spec: Dict, args, devices, trace_dir,
              compiles) -> Dict:
    from benchmark import train_cell
    from benchmark.reference import train_ref
    from benchmark.serve_cell import free

    plan = traffic.make_plan(spec, args.seed, args.seconds)
    built = train_cell.build(model, plan, args.seed, devices, log)
    feed = train_cell.Feed(args.seed, plan, model["vocab_size"],
                           built["rows"])
    try:
        got = train_cell.first_steps(built, model, feed, args.seed, log)
        setup_s = time.monotonic() - T_START
        win = train_cell.window(built, feed, plan, args.seconds, trace_dir,
                                int(spec["trace_steps"]), log)
    finally:
        feed.close()
    rec = {"scalars": {
        "setup_s": setup_s,
        "tokens_per_s": win["tokens"] / win["window_s"],
        "step_ms_p50": 1000.0 * sorted(win["step_s"])[len(win["step_s"]) // 2],
        "input_wait_ms_per_step":
            1000.0 * sum(win["wait_s"]) / len(win["wait_s"]),
    }, "trace_dir": win["trace_dir"], "traced_steps": int(spec["trace_steps"]),
        "attempted": win["steps"], "failed": 0, "plan": plan}
    rec["memory_peak_bytes"] = memory_peak(devices)
    if rec["memory_peak_bytes"]:
        # the backend's mark leaves out the running program's temporaries
        rec["scalars"]["hbm_peak_gb"] = max(
            rec["memory_peak_bytes"], built["compiler_peak_bytes"]) / 1e9
    hp = built["hp"]
    free(built.pop("state"))
    del built
    t0 = time.monotonic()
    batches = [traffic.train_batch(args.seed, i, plan["batch"], plan["seq"],
                                   model["vocab_size"])
               for i in range(train_cell.FOLLOWED_STEPS)]
    want = train_ref.follow(model, hp, args.seed, batches, devices)
    log(f"reference: {train_cell.FOLLOWED_STEPS} steps in "
        f"{time.monotonic() - t0:.1f}s; losses {want['losses']}")
    rec["numbers"] = correct.train_numbers(got, want, win["losses"])
    return rec


RUNNERS = {"serve": run_serve, "train": run_train}


def measure(man, args, devices) -> Dict:
    """One run of ``args.workload`` on ``devices``, as the result line's
    object. ``main`` gives it the chips it found; the CPU rehearsals of the
    tests give it what they have."""
    cell = man.workload(args.workload)
    model = man.config(cell["config"])
    spec = man.traffic(cell["traffic"])
    limits = correct.load_limits(man.data_dir, args.workload)
    log(f"{args.workload} seed {args.seed} on {len(devices)} x "
        f"{devices[0].device_kind}")
    compiles = CompileLog()
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace \
        else None
    try:
        rec = RUNNERS[model["kind"]](model, spec, args, devices, trace_dir,
                                     compiles)
        reduce_trace(rec)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    rec.update(kind=model["kind"], model=model, chips=cell["chips"])
    if devices[0].platform == "tpu":
        rec["peak"] = peaks.peak(devices[0].device_kind)
    compared = correct.judge(rec["numbers"], limits)
    kind = "per_layer" if args.trace else "end_to_end"
    out = {"correct": all(c["ok"] for c in compared.values()),
           "attempted": int(rec["attempted"]),
           "failed": int(rec["failed"]),
           "metrics": manifest.read_metrics(man, args.workload, kind, rec),
           "device": {"platform": devices[0].platform,
                      "kind": devices[0].device_kind, "count": len(devices),
                      "memory_peak_bytes": rec["memory_peak_bytes"]}}
    if args.trace and rec["trace"]:
        red = rec["trace"]
        out["device"].update(busy_s=red["busy_s"], window_s=red["window_s"])
        out["breakdown"] = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}
    out["compared"] = compared      # last in the line: what decided correct
    for line in correct.lines(compared):
        log(line)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    man = manifest.Manifest()
    devices = find_devices(man.workload(args.workload)["chips"])
    configure_jax()
    out = measure(man, args, devices)
    if args.trace and out["device"].get("busy_s", 0.0) <= 0.0:
        raise SystemExit("the traced run saw no operation on the device")
    print(json.dumps(out), flush=True)
    print("\n".join(correct.lines(out["compared"])), file=sys.stderr,
          flush=True)       # and last on standard error
    return 0


if __name__ == "__main__":
    sys.exit(main())
