"""r7 per-request observability: timelines, exemplars, SLO audit, the
on-demand profiling control plane.

Contracts under test:
- a served request's timeline is COMPLETE (queued -> admitted ->
  prefill -> first_token -> decode -> finish) with monotone timestamps;
  a preempted request additionally shows preempt -> resumed and keeps
  ONE id across slots;
- the p99 TTFT exemplar names the deliberately-slowest request, and its
  id retrieves the full timeline over HTTP (/request/<id>.json on the
  reserved-port server) — the integration path;
- FLAGS_obs_enabled off => no context objects, no ring writes, no
  exemplars (the disabled-path guard);
- the profiling controller windows a jax.profiler capture to N step
  boundaries, mirrors trace_span into TraceAnnotations only while
  live, and logs the capture to the flight recorder.
"""
import dataclasses
import json
import os
import subprocess
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu  # noqa: F401  (forces the CPU/virtual-device conftest setup)
import jax
import jax.numpy as jnp

import paddle_tpu.observability as obs
from paddle_tpu.models import llama
from paddle_tpu.observability import profiling, request_trace
from paddle_tpu.serving import LLMEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(
        llama.tiny_llama(vocab=64, hidden=32, layers=2, heads=4,
                         kv_heads=2, seq=128, ffn=64),
        dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture
def obs_on():
    obs.get_registry().reset()
    obs.get_tracer().clear()
    request_trace.get_request_tracer().clear()
    request_trace.get_exemplar_store().clear()
    obs.flight_recorder.get_recorder().clear()
    obs.enable()
    try:
        yield
    finally:
        obs.disable()
        obs.get_registry().reset()
        obs.get_tracer().clear()
        request_trace.get_request_tracer().clear()
        request_trace.get_exemplar_store().clear()
        obs.flight_recorder.get_recorder().clear()


@pytest.fixture
def obs_http_server(obs_on):
    from paddle_tpu.observability.http_server import MetricsServer

    srv = MetricsServer(port=0)
    try:
        yield srv
    finally:
        srv.close()


def _get_json(srv, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}{path}", timeout=10) as r:
        return json.load(r)


# ---------------------------------------------------------------------------
# timeline contract
# ---------------------------------------------------------------------------
def test_request_timeline_complete_and_monotone(model, obs_on):
    cfg, params = model
    rng = np.random.default_rng(0)
    eng = LLMEngine(params, cfg, max_slots=2, block_size=8,
                    max_model_len=128, prompt_buckets=[8, 32])
    rids = [eng.add_request(rng.integers(1, 64, size=n).tolist(),
                            max_new_tokens=k)
            for n, k in ((3, 6), (7, 4))]
    results = eng.run()
    tracer = request_trace.get_request_tracer()
    for rid in rids:
        doc = tracer.get(rid)
        assert doc is not None and doc["finished"], rid
        kinds = [e["kind"] for e in doc["events"]]
        # complete lifecycle, in order
        for a, b in zip(("queued", "admitted", "prefill", "first_token"),
                        ("admitted", "prefill", "first_token", "finish")):
            assert kinds.index(a) < kinds.index(b), kinds
        assert "decode" in kinds
        ts = [e["t"] for e in doc["events"]]
        assert ts == sorted(ts), f"non-monotone timeline for {rid}"
        s = doc["summary"]
        assert s["tokens"] == len(results[rid])
        assert s["queue_ms"] is not None and s["queue_ms"] >= 0
        assert s["ttft_ms"] is not None and s["ttft_ms"] >= s["queue_ms"]
        assert s["preemptions"] == 0
    # summaries ride /requests.json-shaped payloads, worst TTFT first
    payload = obs.requests_payload()
    assert len(payload["requests"]) == 2
    ttfts = [r["ttft_ms"] for r in payload["requests"]]
    assert ttfts == sorted(ttfts, reverse=True)


def test_preempted_request_shows_preempt_resume_one_id(model, obs_on):
    """Pool pressure preempts the newest request: its timeline shows
    preempt -> resumed under the SAME request_id, and the summary
    counts the preemption."""
    cfg, params = model
    rng = np.random.default_rng(3)
    eng = LLMEngine(params, cfg, max_slots=2, block_size=8,
                    max_model_len=64, num_blocks=5, prompt_buckets=[8])
    id1 = eng.add_request(rng.integers(1, 64, size=8).tolist(),
                          max_new_tokens=16)
    id2 = eng.add_request(rng.integers(1, 64, size=8).tolist(),
                          max_new_tokens=16)
    eng.run()
    assert obs.get_registry().counter(
        "serving_preemptions_total").labels().value >= 1
    tracer = request_trace.get_request_tracer()
    docs = {rid: tracer.get(rid) for rid in (id1, id2)}
    preempted = [rid for rid, d in docs.items()
                 if any(e["kind"] == "preempt" for e in d["events"])]
    assert preempted, "no preempt event on either timeline"
    for rid in preempted:
        kinds = [e["kind"] for e in docs[rid]["events"]]
        i_pre = kinds.index("preempt")
        assert "resumed" in kinds[i_pre:], kinds
        # resumed -> a fresh prefill for the recompute
        assert "prefill" in kinds[kinds.index("resumed", i_pre):], kinds
        assert docs[rid]["summary"]["preemptions"] >= 1
        ts = [e["t"] for e in docs[rid]["events"]]
        assert ts == sorted(ts)


def test_disabled_no_ring_writes_no_context_minting(model):
    """FLAGS_obs_enabled off => add_request/run create no request
    contexts, no retained timelines, no exemplars, no spans."""
    assert not obs.enabled()
    tracer = request_trace.get_request_tracer()
    tracer.clear()
    request_trace.get_exemplar_store().clear()
    obs.get_tracer().clear()
    cfg, params = model
    rng = np.random.default_rng(1)
    eng = LLMEngine(params, cfg, max_slots=2, block_size=8,
                    max_model_len=128, prompt_buckets=[8])
    eng.add_request(rng.integers(1, 64, size=5).tolist(), max_new_tokens=3)
    eng.run()
    assert tracer.live_count() == 0
    assert tracer.requests() == []
    assert tracer.get(0) is None
    assert request_trace.get_exemplar_store().exemplars(
        "serving_ttft_seconds") == []
    assert obs.get_tracer().spans() == []
    # direct mutations are no-ops too (the module-level guard)
    tracer.submit(99)
    tracer.record(99, "decode", tokens=1)
    assert tracer.live_count() == 0 and tracer.finish(99) is None


# ---------------------------------------------------------------------------
# exemplars
# ---------------------------------------------------------------------------
def test_exemplar_store_bucket_semantics(obs_on):
    h = obs.get_registry().histogram("serving_ttft_seconds")
    request_trace.observe_with_exemplar(h, 0.004, "a")
    request_trace.observe_with_exemplar(h, 0.0041, "b")   # same bucket: wins
    request_trace.observe_with_exemplar(h, 3.0, "slow")
    exs = request_trace.get_exemplar_store().exemplars(h.name, h.bounds)
    assert {e["request_id"] for e in exs} == {"b", "slow"}
    ex = request_trace.exemplar_for_quantile(h, 0.99)
    assert ex["request_id"] == "slow"
    # median falls among the fast pair
    assert request_trace.exemplar_for_quantile(h, 0.25)["request_id"] == "b"
    c = obs.get_registry().counter(
        "serving_request_exemplars_total").labels().value
    assert c == 3


def test_slo_breach_audits_timeline(obs_on, tmp_path):
    """A finished request over FLAGS_obs_slo_ttft_ms lands its FULL
    timeline in the audit ring and the bounded JSONL file."""
    from paddle_tpu.framework.flags import set_flags

    set_flags({"obs_audit_dir": str(tmp_path), "obs_slo_ttft_ms": 10.0})
    try:
        tracer = request_trace.get_request_tracer()
        tracer.submit(7, prompt_tokens=4)
        tracer.admitted(7, slot=0)
        time.sleep(0.03)                       # ttft ~30ms > 10ms target
        tracer.record(7, "first_token")
        tracer.record(7, "decode", tokens=2)
        tracer.finish(7, tokens=3)
        audits = tracer.audit_entries()
        assert len(audits) == 1 and audits[0]["request_id"] == 7
        assert "ttft" in audits[0]["reasons"]
        kinds = [e["kind"] for e in audits[0]["timeline"]["events"]]
        assert kinds[0] == "queued" and kinds[-1] == "finish"
        jl = tmp_path / f"request_audit-{os.getpid()}.jsonl"
        assert jl.exists()
        line = json.loads(jl.read_text().splitlines()[0])
        assert line["request_id"] == 7
        assert obs.get_registry().counter(
            "serving_request_slo_audits_total").labels(
                reason="ttft").value == 1
    finally:
        set_flags({"obs_audit_dir": "", "obs_slo_ttft_ms": 1000.0})


def test_audit_file_budget_not_spent_while_dir_unset(obs_on, tmp_path):
    """Breaches with obs_audit_dir unset must not consume the JSONL
    line budget — setting the dir later starts capturing immediately."""
    from paddle_tpu.framework.flags import set_flags

    set_flags({"obs_slo_ttft_ms": 0.001, "obs_audit_capacity": 2})
    tracer = request_trace.get_request_tracer()
    try:
        for rid in range(3):                  # dir unset: ring only
            tracer.submit(rid)
            tracer.admitted(rid, slot=0)
            tracer.record(rid, "first_token")
            tracer.finish(rid, tokens=1)
        assert tracer._audit_written == 0
        # ring resize via set_flags is live, and keeps the newest
        set_flags({"obs_audit_capacity": 4})
        assert tracer._audit.maxlen == 4
        set_flags({"obs_audit_dir": str(tmp_path),
                   "obs_audit_capacity": 2})
        for rid in (10, 11, 12):              # budget==2 spent on writes
            tracer.submit(rid)
            tracer.admitted(rid, slot=0)
            tracer.record(rid, "first_token")
            tracer.finish(rid, tokens=1)
        jl = tmp_path / f"request_audit-{os.getpid()}.jsonl"
        lines = [json.loads(x) for x in jl.read_text().splitlines()]
        assert [x["request_id"] for x in lines] == [10, 11]
    finally:
        set_flags({"obs_audit_dir": "", "obs_slo_ttft_ms": 1000.0,
                   "obs_audit_capacity": 64})


def test_requests_limit_contract(obs_on):
    tracer = request_trace.get_request_tracer()
    for rid in range(3):
        tracer.submit(rid)
        tracer.admitted(rid, slot=0)
        tracer.finish(rid, tokens=1)
    assert len(tracer.requests(limit=2)) == 2
    # non-positive limits mean "no limit", never drop the worst rows
    assert len(tracer.requests(limit=0)) == 3
    assert len(tracer.requests(limit=-2)) == 3


def test_decode_tick_cap_drops_counted(obs_on):
    from paddle_tpu.framework.flags import set_flags

    set_flags({"obs_request_events_max": 8})
    try:
        tracer = request_trace.get_request_tracer()
        tracer.submit(1)
        tracer.admitted(1, slot=0)
        for _ in range(20):
            tracer.record(1, "decode", tokens=1)
        tracer.record(1, "preempt")            # lifecycle: always lands
        doc = tracer.get(1)
        assert doc["events_dropped"] > 0
        assert [e["kind"] for e in doc["events"]].count("preempt") == 1
        assert len(doc["events"]) <= 8 + 1     # cap + the lifecycle event
    finally:
        set_flags({"obs_request_events_max": 512})


# ---------------------------------------------------------------------------
# chrome trace / span args
# ---------------------------------------------------------------------------
def test_spans_carry_request_ids_and_survive_numpy_args(obs_on, tmp_path):
    tracer = request_trace.get_request_tracer()
    tracer.submit(5)
    tracer.admitted(5, slot=0)
    tracer.finish(5, tokens=1)
    # a numpy attr must be stringified, not abort the export; a user
    # "depth" arg must win over the synthetic nesting field
    with obs.trace_span("custom", count=np.int64(3), depth="mine"):
        pass
    path = str(tmp_path / "trace.json")
    obs.export_chrome_trace(path)
    doc = json.load(open(path))
    by_name = {}
    for ev in doc["traceEvents"]:
        by_name.setdefault(ev["name"], []).append(ev)
    assert by_name["serving.request"][0]["args"]["request_id"] == 5
    cust = by_name["custom"][0]["args"]
    assert cust["count"] == "3" and cust["depth"] == "mine"


# ---------------------------------------------------------------------------
# HTTP endpoints (reserved port)
# ---------------------------------------------------------------------------
def test_http_requests_endpoints_roundtrip(obs_http_server):
    srv = obs_http_server
    tracer = request_trace.get_request_tracer()
    tracer.submit(11, prompt_tokens=3)
    tracer.admitted(11, slot=0)
    tracer.record(11, "first_token")
    tracer.finish(11, tokens=2)
    tracer.submit(12, prompt_tokens=5)         # still live
    doc = _get_json(srv, "/requests.json?sort=ttft")
    assert doc["live"] == 1
    ids = {r["request_id"] for r in doc["requests"]}
    assert ids == {11, 12}
    one = _get_json(srv, "/request/11.json")
    assert [e["kind"] for e in one["events"]] == [
        "queued", "admitted", "first_token", "finish"]
    with pytest.raises(urllib.error.HTTPError) as ei:
        _get_json(srv, "/request/404.json")
    assert ei.value.code == 404


def test_http_profile_control_arm_and_conflict(obs_http_server):
    srv = obs_http_server
    try:
        out = _get_json(srv, "/control/profile?steps=3")
        assert out["ok"] and out["armed_steps"] == 3
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get_json(srv, "/control/profile?steps=1")
        assert ei.value.code == 409
    finally:
        profiling.get_controller().stop()
    # explicit steps=0 is the CALLER's mistake, not "use the default
    # window" and not a conflict: 400, nothing armed
    with pytest.raises(urllib.error.HTTPError) as ei:
        _get_json(srv, "/control/profile?steps=0")
    assert ei.value.code == 400
    assert profiling.get_controller().status()["steps_left"] == 0
    # ?stop=0 is NOT a stop (string truthiness trap): it arms instead
    try:
        out = _get_json(srv, "/control/profile?stop=0&steps=2")
        assert out["ok"] and out["armed_steps"] == 2
    finally:
        profiling.get_controller().stop()
    out = _get_json(srv, "/control/profile?stop=1")
    assert out["ok"] and out["status"]["steps_left"] == 0


@pytest.mark.parametrize("query, code, max_s", [
    ("steps=3&seconds=2.5", 200, 2.5),     # the caller's bound
    ("steps=3", 200, 6.0),                 # the module's
    ("steps=3&seconds=0", 400, None),      # no bound at all: refused
    ("steps=3&seconds=soon", 400, None),
])
def test_http_profile_control_takes_a_bound_in_seconds(
        obs_http_server, query, code, max_s):
    try:
        if code == 200:
            out = _get_json(obs_http_server, f"/control/profile?{query}")
            assert out["ok"] and out["max_seconds"] == max_s
        else:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get_json(obs_http_server, f"/control/profile?{query}")
            assert ei.value.code == code
            assert profiling.get_controller().status()["steps_left"] == 0
    finally:
        profiling.get_controller().stop()


def test_http_request_id_junk_is_404_not_500(obs_http_server):
    for junk in ("--5", "abc", "-"):
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get_json(obs_http_server, f"/request/{junk}.json")
        assert ei.value.code == 404, junk


def test_profile_instances_do_not_disturb_default_controller(obs_on):
    """A user-constructed controller arms/stops ITS OWN window; the
    module-level step_tick drives only the default controller."""
    ctl = profiling.get_controller()
    mine = profiling.ProfileController()
    out = ctl.request(steps=2)
    assert out["ok"]
    mine.stop()                               # must NOT disarm the default
    assert ctl.status()["steps_left"] == 2
    assert ctl._pending is True
    ctl.stop()


def test_sigusr2_defers_arming_to_step_boundary(obs_on, tmp_path):
    """The signal handler only sets flags (taking the controller lock
    in signal context can deadlock the main thread); the next step
    boundary performs the arm."""
    import signal as _signal

    ctl = profiling.get_controller()
    assert profiling.install_sigusr2()
    try:
        os.kill(os.getpid(), _signal.SIGUSR2)
        time.sleep(0.05)
        st = ctl.status()
        assert st.get("sig_armed") and st["steps_left"] == 0
        profiling.step_tick()                 # boundary arms + starts
        assert ctl.status()["active"]
    finally:
        ctl.stop()
        profiling.uninstall_sigusr2()


def test_disable_with_live_requests_does_not_pin_contexts(obs_on):
    """obs.disable() mid-flight: finish() still evicts the live
    context instead of pinning it in /requests.json forever."""
    tracer = request_trace.get_request_tracer()
    tracer.submit(21, prompt_tokens=2)
    tracer.admitted(21, slot=0)
    obs.disable()
    assert tracer.finish(21, tokens=1) is None
    assert tracer.live_count() == 0
    obs.enable()
    assert tracer.get(21) is None             # dropped, not retained


# ---------------------------------------------------------------------------
# on-demand profiling controller
# ---------------------------------------------------------------------------
def test_profile_capture_windows_to_step_boundaries(obs_on, tmp_path):
    from paddle_tpu.observability import tracing as _tracing

    ctl = profiling.get_controller()
    d = str(tmp_path / "cap")
    out = ctl.request(steps=2, out_dir=d)
    assert out["ok"], out
    f = jax.jit(lambda x: x * 2)
    profiling.step_tick()                      # boundary 1: starts
    assert ctl.status()["active"]
    # trace_span mirrors into TraceAnnotation ONLY while capturing
    assert _tracing._ANNOTATION_FACTORY is not None
    with obs.trace_span("under.capture"):
        f(jnp.ones((4,))).block_until_ready()
    profiling.step_tick()                      # windowed step 1
    assert ctl.status()["active"]
    profiling.step_tick()                      # windowed step 2: stops
    st = ctl.status()
    assert not st["active"] and st["steps_left"] == 0
    assert st["last_capture"]["ok"], st
    assert _tracing._ANNOTATION_FACTORY is None
    assert os.path.isdir(d) and os.listdir(d)
    assert obs.get_registry().counter(
        "obs_profile_captures_total").labels().value == 1
    kinds = [e["kind"] for e in obs.flight_recorder.get_recorder().events()]
    assert "profile_capture" in kinds
    # idle ticks after the window are free no-ops
    profiling.step_tick()
    assert ctl._pending is False


def test_profile_capture_via_engine_steps(model, obs_on, tmp_path):
    """The engine's step() drives the capture window end to end."""
    cfg, params = model
    rng = np.random.default_rng(2)
    eng = LLMEngine(params, cfg, max_slots=2, block_size=8,
                    max_model_len=128, prompt_buckets=[8])
    eng.add_request(rng.integers(1, 64, size=5).tolist(),
                    max_new_tokens=6)
    out = profiling.request_capture(steps=2,
                                    out_dir=str(tmp_path / "engcap"))
    assert out["ok"]
    eng.run()
    st = profiling.get_controller().status()
    assert not st["active"] and st["last_capture"]["ok"], st


# ---------------------------------------------------------------------------
# integration: exemplar -> timeline over HTTP, sentinel on real rounds
# ---------------------------------------------------------------------------
def test_integration_p99_exemplar_resolves_slow_request_over_http(
        model, obs_http_server):
    """Mixed workload with one seeded slow request: the p99 TTFT
    exemplar's request_id retrieves that request's full timeline via
    /request/<id>.json (the ISSUE acceptance path)."""
    srv = obs_http_server
    cfg, params = model
    rng = np.random.default_rng(5)
    eng = LLMEngine(params, cfg, max_slots=2, block_size=8,
                    max_model_len=128, prompt_buckets=[8, 32])
    # warm EVERY compiled variant the measured pattern will hit (the
    # 2-wide admission wave, the single re-admission, both decode
    # buckets) by running the exact same traffic shape once — otherwise
    # a first-compile lands in some fast request's TTFT and outweighs
    # the seeded queue wait
    for n, k in ((3, 4), (7, 6)):
        eng.add_request(rng.integers(1, 64, size=n).tolist(),
                        max_new_tokens=k)
    eng.step()
    eng.step()
    eng.add_request(rng.integers(1, 64, size=5).tolist(),
                    max_new_tokens=4)
    eng.run()
    request_trace.get_request_tracer().clear()
    request_trace.get_exemplar_store().clear()
    obs.get_registry().histogram("serving_ttft_seconds").reset()
    # mixed traffic: both slots busy...
    fast = [eng.add_request(rng.integers(1, 64, size=n).tolist(),
                            max_new_tokens=k)
            for n, k in ((3, 4), (7, 6))]
    eng.step()
    # the token stream lags the chip by one call: only the SECOND step
    # makes the fast requests' first tokens host-visible. Without it their
    # TTFT would hold the sleep below too (257.47 ms against the slow
    # request's 257.82 when this was found), and the ranking at the end
    # would turn on a third of a millisecond
    eng.step()
    # ...then the seeded-slow request queues behind them and waits
    slow = eng.add_request(rng.integers(1, 64, size=5).tolist(),
                           max_new_tokens=4)
    time.sleep(0.25)
    results = eng.run()
    assert set(results) >= {slow, *fast}

    hist = obs.get_registry().histogram("serving_ttft_seconds")
    ex = request_trace.exemplar_for_quantile(hist, 0.99)
    assert ex is not None and ex["request_id"] == slow, ex
    # the id from the exemplar retrieves the full timeline over HTTP
    doc = _get_json(srv, f"/request/{ex['request_id']}.json")
    kinds = [e["kind"] for e in doc["events"]]
    assert kinds[0] == "queued" and kinds[-1] == "finish"
    assert "first_token" in kinds and doc["summary"]["ttft_ms"] >= 250
    # and /requests.json ranks it worst
    listing = _get_json(srv, "/requests.json?sort=ttft")
    assert listing["requests"][0]["request_id"] == slow
    assert listing["exemplar_quantiles"][
        "serving_ttft_seconds"]["p99"]["request_id"] == slow


# ---------------------------------------------------------------------------
# obs_dump --requests (file mode)
# ---------------------------------------------------------------------------
def test_obs_dump_fetch_url_keeps_caller_query(monkeypatch):
    """A --requests URL that already carries a query string keeps it;
    /requests.json lands on the PATH, not glued onto the query."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "obs_dump_for_test", os.path.join(REPO, "tools", "obs_dump.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    seen = []

    class _Resp:
        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False

        def read(self):
            return b"{}"

    import urllib.request as _ur

    monkeypatch.setattr(_ur, "urlopen",
                        lambda url, timeout=None: seen.append(url) or _Resp())
    mod._fetch_requests("http://h:1/requests.json?limit=5", "ttft")
    mod._fetch_requests("http://h:1", "tpot")
    assert seen[0] == "http://h:1/requests.json?limit=5&sort=ttft"
    assert seen[1] == "http://h:1/requests.json?sort=tpot"


def test_obs_dump_requests_table_from_file(obs_on, tmp_path):
    tracer = request_trace.get_request_tracer()
    tracer.submit(3, prompt_tokens=4)
    tracer.admitted(3, slot=0)
    tracer.record(3, "first_token")
    tracer.finish(3, tokens=5)
    payload = obs.requests_payload()
    p = tmp_path / "reqs.json"
    p.write_text(json.dumps(payload, default=repr))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "obs_dump.py"),
         "--requests", str(p)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, timeout=120,
        cwd=REPO)
    out = proc.stdout.decode()
    assert proc.returncode == 0, out
    assert "requests: 1 traced" in out and "ttft_ms" in out
