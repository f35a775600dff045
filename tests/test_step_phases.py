"""The serving step's phases on the trace clock (PR 25).

- every phase span lies inside the span named as its parent, and
  ``Span.parent`` says so; ``serving.telemetry`` follows its step;
- ``serving_step_host_seconds`` is observed once per step observation and
  never exceeds it;
- with observability off a step records no span and observes nothing;
- a device capture starts without the Python tracer and stops off the step
  thread, ``status()`` never answering inactive before the file is there;
- the compiled programs carry names;
- the ``serving.request`` span carries the engine's own timestamps;
- the front door spans its step-thread work and times emit-to-write.
"""
import dataclasses
import json
import socket
import threading
import time

import numpy as np
import pytest

import paddle_tpu  # noqa: F401  (forces the CPU/virtual-device conftest setup)
import jax
import jax.numpy as jnp

import paddle_tpu.observability as obs
from paddle_tpu.models import llama
from paddle_tpu.observability import profiling, request_trace
from paddle_tpu.serving import HTTPFrontDoor, LLMEngine
from paddle_tpu.serving import engine as engine_mod

# span -> the span the step's layout puts it in (None: top of its thread)
PARENT = {
    "serving.housekeeping": "serving.step",
    "serving.admit": "serving.step",
    "serving.prefill_build": "serving.admit",
    "serving.prefill": "serving.admit",
    "serving.decode_prepare": "serving.step",
    "serving.decode": "serving.step",
    "serving.readback": "serving.step",
    "serving.readback_wait": "serving.readback",
    "serving.telemetry": None,
    "serving.step": None,
}


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(
        llama.tiny_llama(vocab=64, hidden=32, layers=2, heads=4, kv_heads=2,
                         seq=128, ffn=64),
        dtype=jnp.float32)
    return cfg, llama.init_params(cfg, jax.random.PRNGKey(0))


def _clear():
    obs.get_registry().reset()
    obs.get_tracer().clear()
    request_trace.get_request_tracer().clear()
    request_trace.get_exemplar_store().clear()


@pytest.fixture
def obs_on():
    _clear()
    obs.enable()
    try:
        yield
    finally:
        obs.disable()
        _clear()


def _engine(model, **kw):
    cfg, params = model
    return LLMEngine(params, cfg, max_slots=2, block_size=8,
                     max_model_len=128, prompt_buckets=[8, 32], **kw)


def _run_three(eng):
    """Three requests on two slots: a wave of two, a queued third that is
    admitted alone once a slot frees."""
    rng = np.random.default_rng(0)
    rids = [eng.add_request(rng.integers(1, 64, size=n).tolist(),
                            max_new_tokens=k)
            for n, k in ((3, 6), (7, 4), (20, 5))]
    eng.run()
    return rids


def _hist(name):
    for m in obs.snapshot()["metrics"]:
        if m["name"] == name:
            return m["series"][0] if m["series"] else None
    return None


# ---------------------------------------------------------------------------
# A. the span tree
# ---------------------------------------------------------------------------
def test_every_phase_lies_inside_its_parent_and_says_so(model, obs_on):
    _run_three(_engine(model))
    spans = [s for s in obs.get_tracer().spans() if s.name in PARENT]
    seen = {s.name for s in spans}
    assert seen == set(PARENT), set(PARENT) - seen
    for s in spans:
        assert s.parent == PARENT[s.name], (s.name, s.parent)
        if s.parent is None:
            continue
        assert any(p.name == s.parent and p.tid == s.tid
                   and p.t0 <= s.t0 and s.t1 <= p.t1 for p in spans), s.name
    steps = sorted((s for s in spans if s.name == "serving.step"),
                   key=lambda s: s.t0)
    tele = sorted((s for s in spans if s.name == "serving.telemetry"),
                  key=lambda s: s.t0)
    assert len(tele) == len(steps)
    for i, (st, te) in enumerate(zip(steps, tele)):
        # a sibling right after its step, before the next one
        assert st.t1 <= te.t0
        assert i + 1 == len(steps) or te.t1 <= steps[i + 1].t0
    # the counts the benchmark's readers lean on: one prefill and one
    # build per ROW (a wave of two is two one-row programs), one decode
    # per dispatch, one readback per record
    assert sum(s.name == "serving.prefill" for s in spans) == 3
    assert sum(s.name == "serving.prefill_build" for s in spans) == 3


def test_phase_attributes(model, obs_on):
    _run_three(_engine(model))
    by = {}
    for s in obs.get_tracer().spans():
        by.setdefault(s.name, []).append(s)
    builds, rows = by["serving.prefill_build"], by["serving.prefill"]
    for build, row in zip(builds, rows):
        for k in ("bucket", "batch", "wave"):
            assert build.attrs[k] == row.attrs[k]
    # the two-row admission is two one-row programs, each in its own
    # bucket (3 and 7 tokens: 8; 20 tokens: 32), then the third alone
    assert [(r.attrs["bucket"], r.attrs["batch"], r.attrs["wave"],
             r.attrs["tokens"], r.attrs["start"]) for r in rows] == \
        [(8, 1, 2, [3], [0]), (8, 1, 2, [7], [0]), (32, 1, 1, [20], [0])]
    assert all(len(r.attrs["request_ids"]) == 1 for r in rows)
    admits = by["serving.admit"]
    assert admits[0].attrs == {"queue": 3, "wave": 2}
    assert all(a.attrs["queue"] >= 1 for a in admits)   # none when idle
    assert sum(a.attrs["wave"] for a in admits) == 3
    assert all(p.attrs["slots"] in (1, 2)
               for p in by["serving.decode_prepare"])
    for d in by["serving.decode"]:
        # what was there, and the walk the method already computed
        assert {"slots", "steps", "prefix_bucket", "request_ids",
                "walk_blocks", "kv_bytes"} <= set(d.attrs)
        assert d.attrs["kv_bytes"] > 0 and d.attrs["walk_blocks"] > 0
    chrome = obs.get_tracer().chrome_trace()["traceEvents"]
    assert any(e["name"] == "serving.readback_wait"
               and e["args"]["parent"] == "serving.readback"
               for e in chrome)


def test_an_early_end_closes_the_span_once(obs_on):
    with obs.trace_span("outer"):
        with obs.trace_span("prep", a=1) as sp:
            sp.attrs["b"] = 2
            sp.end()
            with obs.trace_span("after"):
                pass
    got = {s.name: s for s in obs.get_tracer().spans()}
    assert len(obs.get_tracer().spans()) == 3
    assert got["prep"].attrs == {"a": 1, "b": 2}
    assert got["prep"].parent == "outer" and got["after"].parent == "outer"
    assert got["prep"].t1 <= got["after"].t0
    assert sp.seconds == pytest.approx(got["prep"].duration)


# ---------------------------------------------------------------------------
# B. host time that is host time
# ---------------------------------------------------------------------------
def test_step_host_seconds_pairs_with_step_seconds(model, obs_on,
                                                   monkeypatch):
    pairs = {"step": [], "host": []}

    class Tap:
        def __init__(self, inner, key):
            self.inner, self.key = inner, key

        def observe(self, v):
            pairs[self.key].append(v)
            self.inner.observe(v)

    monkeypatch.setattr(engine_mod, "_M_STEP_SECONDS",
                        Tap(engine_mod._M_STEP_SECONDS, "step"))
    monkeypatch.setattr(engine_mod, "_M_STEP_HOST_SECONDS",
                        Tap(engine_mod._M_STEP_HOST_SECONDS, "host"))
    _run_three(_engine(model))
    step, host = _hist("serving_step_seconds"), \
        _hist("serving_step_host_seconds")
    assert step["count"] == host["count"] == len(pairs["step"]) > 0
    assert len(pairs["host"]) == len(pairs["step"])
    for h, s in zip(pairs["host"], pairs["step"]):
        assert 0.0 <= h <= s
    # the difference is the time inside the readback_wait spans
    wait = sum(s.duration for s in obs.get_tracer().spans()
               if s.name == "serving.readback_wait")
    assert wait > 0.0
    assert step["sum"] - host["sum"] == pytest.approx(wait, rel=1e-6)


def test_with_observability_off_a_step_leaves_nothing(model):
    _clear()
    assert not obs.enabled()
    eng = _engine(model)
    _run_three(eng)
    assert obs.get_tracer().spans() == []
    for m in obs.snapshot()["metrics"]:
        for s in m["series"]:
            assert not s.get("count") and not s.get("value"), m["name"]
    assert eng._wait_s == 0.0


# ---------------------------------------------------------------------------
# C. the request span carries the engine's timestamps
# ---------------------------------------------------------------------------
def test_request_span_carries_the_tracers_summary(model, obs_on):
    rids = _run_three(_engine(model))
    spans = {s.attrs["request_id"]: s for s in obs.get_tracer().spans()
             if s.name == "serving.request"}
    assert sorted(spans) == sorted(rids)
    tracer = request_trace.get_request_tracer()
    for rid in rids:
        summ, a = tracer.get(rid)["summary"], spans[rid].attrs
        assert a["queue_ms"] == summ["queue_ms"] is not None
        assert a["ttft_ms"] == summ["ttft_ms"] is not None
        assert a["prefill_ms"] == pytest.approx(
            summ["ttft_ms"] - summ["queue_ms"])
        assert a["tokens"] == summ["tokens"]
    # the third request waited for a slot: its queue time shows
    assert spans[rids[2]].attrs["queue_ms"] > spans[rids[0]].attrs["queue_ms"]


# ---------------------------------------------------------------------------
# D. a capture that costs the run nothing it need not
# ---------------------------------------------------------------------------
def test_capture_has_no_python_tracer_and_stops_off_the_step_thread(
        obs_on, tmp_path, monkeypatch):
    started, stopped = {}, threading.Event()

    def start_trace(log_dir, **kw):
        started.update(kw, log_dir=log_dir)

    def stop_trace():
        time.sleep(0.5)
        stopped.set()

    monkeypatch.setattr(jax.profiler, "start_trace", start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", stop_trace)
    ctl = profiling.ProfileController()
    d = str(tmp_path / "cap")
    assert ctl.request(steps=1, out_dir=d)["ok"]
    ctl.step_tick()                                    # starts
    assert started["log_dir"] == d
    assert started["profiler_options"].python_tracer_level == 0
    assert started["profiler_options"].host_tracer_level == \
        jax.profiler.ProfileOptions().host_tracer_level
    t0 = time.perf_counter()
    ctl.step_tick()                                    # counts down: stops
    assert time.perf_counter() - t0 < 0.1
    assert not stopped.is_set()
    again = ctl.request(steps=1)                       # refused meanwhile
    assert not again["ok"] and again["status"]["active"]
    assert time.perf_counter() - t0 < 0.1
    ctl.step_tick()                                    # a no-op, at once
    assert time.perf_counter() - t0 < 0.2
    # whoever gets an answer before the file is there is told "active"
    st = ctl.status()
    assert st["active"] or stopped.is_set()
    assert stopped.wait(5)
    st = ctl.stop()
    assert not st["active"] and st["last_capture"]["ok"]
    cap = [s for s in obs.get_tracer().spans()
           if s.name == "serving.profile_capture"]
    assert len(cap) == 1 and cap[0].attrs == {"dir": d, "steps": 1}
    assert cap[0].t1 <= t0 + 0.1            # ends where the stop was asked
    assert ctl.request(steps=1, out_dir=d)["ok"]       # free again
    ctl.stop()


def test_an_overdue_capture_ends_with_the_whole_steps_it_has(
        obs_on, tmp_path, monkeypatch):
    """A capture is bounded in seconds as well as in steps: the boundary
    that finds it overdue stops it, the ring span says how many whole
    steps it holds, and one in time runs to its count as before."""
    stopped = threading.Event()
    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **kw: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", stopped.set)
    ctl = profiling.ProfileController()
    d = str(tmp_path / "cap")
    out = ctl.request(steps=50, out_dir=d, seconds=0.2)
    assert out["ok"] and out["max_seconds"] == 0.2
    ctl.step_tick()                                    # starts
    ctl.step_tick()                                    # in time: goes on
    ctl.step_tick()
    assert ctl.status()["active"] and ctl.status()["steps_left"] == 48
    time.sleep(0.25)
    ctl.step_tick()                                    # overdue: stops
    assert stopped.wait(5)
    st = ctl.status()
    assert not st["active"] and st["steps_left"] == 0
    assert st["last_capture"]["ok"]
    cap = [s for s in obs.get_tracer().spans()
           if s.name == "serving.profile_capture"]
    assert len(cap) == 1 and cap[0].attrs == {"dir": d, "steps": 3}
    assert not ctl._pending                            # ticks cost nothing
    # the default bound is the module's, and a bound of nothing is refused
    assert ctl.request(steps=2, out_dir=d)["max_seconds"] == \
        profiling.MAX_SECONDS == 6.0
    stopped.clear()
    ctl.step_tick(), ctl.step_tick(), ctl.step_tick()
    assert stopped.wait(5) and not ctl.status()["active"]
    assert [s.attrs["steps"] for s in obs.get_tracer().spans()
            if s.name == "serving.profile_capture"] == [3, 2]
    bad = ctl.request(steps=2, seconds=0)
    assert not bad["ok"] and bad["bad_request"]


def test_capture_anchors_the_ring_clock_in_the_trace(obs_on, tmp_path):
    """A real capture (the CPU backend has a host plane): the anchor's
    argument is a perf_counter reading inside the capture's ring span."""
    from jax.profiler import ProfileData

    from benchmark import trace as bench_trace

    ctl = profiling.ProfileController()
    assert ctl.request(steps=1, out_dir=str(tmp_path))["ok"]
    ctl.step_tick()
    with obs.trace_span("serving.step"):
        jnp.ones(4).block_until_ready()
    ctl.step_tick()
    assert ctl.stop()["last_capture"]["ok"]
    cap = [s for s in obs.get_tracer().spans()
           if s.name == "serving.profile_capture"][0]
    path = bench_trace.find(str(tmp_path))
    events = [e for p in ProfileData.from_file(path).planes
              for ln in p.lines for e in ln.events
              if e.name.startswith("serving.")]
    names = {e.name for e in events}
    assert {"serving.clock_anchor", "serving.step"} <= names
    assert "serving.profile_capture" not in names      # ring only
    anchor = [e for e in events if e.name == "serving.clock_anchor"][0]
    t = float(dict(anchor.stats)["perf_counter"])
    assert cap.t0 == pytest.approx(t, abs=1e-3)
    # the benchmark's loader keeps both, and reduces without a device
    kept = bench_trace.load(path)
    assert any(e[0] == "serving.clock_anchor" for p in kept["planes"]
               for ln in p["lines"] for e in ln["events"])


# ---------------------------------------------------------------------------
# E. programs with names
# ---------------------------------------------------------------------------
def test_compiled_programs_carry_names(model):
    eng = _engine(model)
    _run_three(eng)
    assert eng._decode_cache and eng._prefill
    assert all(f.__name__ == "paged_decode"
               for f in eng._decode_cache.values())
    assert all(f.__name__ == "paged_prefill" for f in eng._prefill.values())


def test_program_name_reaches_the_lowered_module(model):
    import functools

    named = engine_mod._named("paged_decode",
                              functools.partial(lambda x, k: x * k, k=2))
    text = jax.jit(named).lower(jnp.ones(3)).as_text()
    assert "jit_paged_decode" in text and "unknown" not in text


# ---------------------------------------------------------------------------
# the front door on the step thread, and emit-to-write
# ---------------------------------------------------------------------------
def _post(host, port, doc):
    s = socket.create_connection((host, port), timeout=120)
    body = json.dumps(doc).encode()
    s.sendall((f"POST /v1/generate HTTP/1.1\r\nHost: x\r\n"
               f"Content-Length: {len(body)}\r\n\r\n").encode() + body)
    data = b""
    while True:
        c = s.recv(65536)
        if not c:
            break
        data += c
    s.close()
    return data


def test_front_door_spans_and_emit_to_write(model, obs_on):
    eng = _engine(model)
    front = HTTPFrontDoor(eng)
    host, port = front.start()
    try:
        rng = np.random.default_rng(1)
        bodies = [_post(host, port, {
            "prompt": rng.integers(1, 64, size=n).tolist(),
            "max_new_tokens": k}) for n, k in ((5, 7), (9, 3))]
    finally:
        front.stop()
    frames = sum(b.count(b"data:") - 1 for b in bodies)  # less terminals
    assert frames == 10
    assert _hist("serving_http_emit_to_write_seconds")["count"] == frames
    spans = obs.get_tracer().spans()
    ops = [s for s in spans if s.name == "serving.http.ops"]
    route = [s for s in spans if s.name == "serving.http.route"]
    steps = [s for s in spans if s.name == "serving.step"]
    assert len(ops) >= 2                     # at least the two submissions
    assert len(route) == len(steps)
    assert all(s.parent is None and s.tid == steps[0].tid
               for s in ops + route)
    assert sum(s.attrs["tokens"] for s in route) == frames


def test_emit_to_write_is_silent_with_observability_off(model):
    _clear()
    eng = _engine(model)
    front = HTTPFrontDoor(eng)
    host, port = front.start()
    try:
        body = _post(host, port, {"prompt": [3, 4, 5], "max_new_tokens": 4})
    finally:
        front.stop()
    assert body.count(b"data:") == 5
    assert obs.get_tracer().spans() == []
    h = _hist("serving_http_emit_to_write_seconds")
    assert h is None or not h["count"]
