"""A serving cell: the engine behind its front door, under generated load.

The system under test is the program's ``LLMEngine`` wired as
``tools/serve.py`` wires it (``HTTPFrontDoor(ResilientEngine(engine))``),
at the shape the configuration file states, with the program config and
the weights that the configuration's family gives. The benchmark makes the
weights from the seed in one jitted call, warms the programs that this
cell's traffic uses (its prompt buckets in both batch forms, the decode
program), starts ``benchmark/client.py`` as a child, and collects: the
client's records, the program's counters before and after the window, its
host spans, and in a traced run a profiler trace of a few seconds and
samples of the gauges.
"""
from __future__ import annotations

import gc
import json
import os
import subprocess
import sys
import threading
import time
from typing import Dict, List

from . import clientstats, traffic, weights
from .manifest import family_of

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE_STEPS = 240         # engine steps traced: about four seconds
TRACE_TAIL_S = 8.0        # a traced run's load goes on this long past the
#                           window, and the capture is taken there: when it
#                           stops, the profiler stalls the step thread for
#                           half a minute, which the window must not pay


def build(model: Dict, seed: int, log):
    """Weights on the device from the seed, the engine, the front door."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu.observability as obs
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.serving import (AdmissionConfig, HTTPFrontDoor, LLMEngine,
                                    ResilientEngine)

    sv = model["serve"]
    if sv["dtype"] != "bfloat16":
        raise ValueError("serving cells run bf16 weights and KV")
    obs.enable()                       # a deployment serves its counters
    set_flags({"obs_trace_capacity": 200000})
    fam = family_of(model)
    cfg = fam.program_config(model, max_seq_len=sv["max_model_len"],
                             remat=False)
    t0 = time.monotonic()
    params = jax.jit(lambda k: weights.make_params(model, k, jnp.bfloat16))(
        weights.seed_key(seed))
    jax.block_until_ready(params)
    log(f"weights: {sum(a.nbytes for a in jax.tree_util.tree_leaves(params))}"
        f" bytes bf16 in {time.monotonic() - t0:.1f}s")
    eng = LLMEngine(
        params, cfg, max_slots=sv["max_slots"], block_size=sv["block_size"],
        max_model_len=sv["max_model_len"], num_blocks=sv["num_blocks"],
        prompt_buckets=list(sv["prompt_buckets"]),
        decode_steps=sv["decode_steps"], decode_kernel=sv["decode_kernel"],
        prefix_cache=sv["prefix_cache"], prefill_chunk=sv["prefill_chunk"],
        admission=AdmissionConfig(max_queue=sv["max_queue"]), seed=0,
        **fam.engine_kwargs(model))
    front = HTTPFrontDoor(ResilientEngine(eng), host="127.0.0.1", port=0)
    return eng, front, params


def warm(eng, model: Dict, plan: Dict, log) -> None:
    """Run every program the plan's requests can reach, straight on the
    engine before the front door starts: each prompt bucket once alone
    (the one-row form) and once as a pair (the padded full-width form),
    two tokens each so that the decode program runs too."""
    lens = sorted({r["prompt_len"] for r in plan["requests"]})
    bucket_for = lambda n: min(b for b in eng.buckets if b >= n)
    buckets = sorted({bucket_for(n) for n in lens})
    t0 = time.monotonic()
    for b in buckets:
        n = max(x for x in lens if bucket_for(x) == b)
        prompt = traffic.prompt_tokens(0, [9, b], n, model["vocab_size"])
        for rows in (1, 2):
            for _ in range(rows):
                eng.add_request(prompt, max_new_tokens=2)
            eng.run()
    eng.results.clear()
    log(f"warmed buckets {buckets} x (1 row, {eng.N} rows) + decode in "
        f"{time.monotonic() - t0:.1f}s")


def _spans(t0: float, t1: float) -> List[Dict]:
    from paddle_tpu.observability import get_tracer

    return [{"name": s.name, "t0": s.t0, "t1": s.t1, "attrs": dict(s.attrs)}
            for s in get_tracer().spans() if s.t1 >= t0 and s.t0 <= t1]


def drive(port: int, model: Dict, spec: Dict, plan: Dict, seed: int,
          seconds: float, trace_dir, log) -> Dict:
    """Start the client, watch the window, return what was collected."""
    import paddle_tpu.observability as obs
    from paddle_tpu.observability import profiling

    plan = dict(plan, port=port, seed=seed, vocab=model["vocab_size"],
                seconds=seconds, temperature=spec.get("temperature", 0.0),
                open_when_streaming=model["serve"]["max_slots"],
                t_open=time.monotonic() + plan["lead_in_s"] + 0.5)
    child = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "client.py")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, cwd=HERE)
    rec: Dict = {"gauges": [], "trace_dir": None}
    try:
        child.stdin.write(json.dumps(plan).encode())
        child.stdin.close()
        sampler = None
        stop = threading.Event()
        for raw in child.stdout:
            doc = json.loads(raw)
            if doc["event"] == "open":
                rec["snap_open"] = obs.snapshot()
                rec["t_open"] = doc["t"]
                if trace_dir:
                    sampler = threading.Thread(
                        target=_traced_window,
                        args=(rec, trace_dir, stop, log), daemon=True)
                    sampler.start()
            elif doc["event"] == "close":
                rec["snap_close"] = obs.snapshot()
                rec["t_close"] = doc["t"]
            elif doc["event"] == "result":
                rec["client"] = doc
        stop.set()
        if sampler is not None:
            sampler.join(120)
            profiling.get_controller().stop()
        if child.wait(60) != 0 or "client" not in rec:
            raise RuntimeError(f"the load generator failed "
                               f"(exit {child.returncode})")
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    rec["spans"] = _spans(rec["t_open"], time.monotonic())
    return rec


def _traced_window(rec: Dict, trace_dir: str, stop, log) -> None:
    """In a traced run: samples of the gauges four times a second while
    the window is open, then, once it has closed and the load goes on, a
    profiler capture of TRACE_STEPS engine steps, started through the
    program's ProfileController so that its host spans land in the trace.
    The capture's start and end are caught on this clock to a hundredth of
    a second, so that the readers know which work fell inside it."""
    import paddle_tpu.observability as obs
    from paddle_tpu.observability import profiling

    ctl = profiling.get_controller()
    armed, began, tick = False, None, 0
    while not stop.wait(0.01) and "trace_span" not in rec:
        now = time.monotonic()
        if "t_close" not in rec:
            if tick % 25 == 0:
                rec["gauges"].append((now, {
                    m["name"]: m["series"][0]["value"]
                    for m in obs.snapshot()["metrics"]
                    if m["kind"] == "gauge"
                    and m["name"].startswith("serving_") and m["series"]}))
            tick += 1
        elif not armed:
            armed = bool(ctl.request(steps=TRACE_STEPS,
                                     out_dir=trace_dir).get("ok"))
            if not armed:
                log("the profiler refused the capture")
                return
        else:
            # status() waits while the controller starts or stops the
            # profiler, so `now`, taken before it, is when that began
            active = ctl.status()["active"]
            if began is None and active:
                began = now
            elif began is not None and not active:
                rec["trace_span"] = (began, now)
                rec["trace_dir"] = trace_dir
                log(f"traced {now - began:.2f}s after the window")


def close(front) -> None:
    front.begin_drain()
    front.wait_drained(30)
    front.stop()


def sample_finished(result: Dict, seed: int, k: int) -> List[Dict]:
    """``k`` finished requests drawn from the seed, the longest among them."""
    import numpy as np

    done = [s for s in result["streams"] if clientstats.ok(s)]
    if not done:
        return []
    done.sort(key=lambda s: (s["prompt_len"] + len(s["tokens"]), s["idx"]))
    longest, rest = done[-1], done[:-1]
    rng = np.random.default_rng([int(seed), 7])
    pick = [rest[i] for i in rng.permutation(len(rest))[:max(0, k - 1)]]
    return [longest] + pick


def free(*objs) -> None:
    import jax

    for o in objs:
        for leaf in jax.tree_util.tree_leaves(o):
            if hasattr(leaf, "delete"):
                leaf.delete()
    gc.collect()
