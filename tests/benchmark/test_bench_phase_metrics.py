"""The per-layer metrics that read the serving step's phases (PR 25): each
new reader on a hand-made record, the metric files and entries, and the CPU
rehearsal of both serving cells with ``--trace 1`` reporting every new
metric that does not read the device. Nothing here is a measurement."""
import json

import jax
import pytest

import bench_tiny as tiny
from benchmark import manifest, run

STEP_METRICS = ("step_host_ms", "readback_wait_ms_per_step",
                "prefill_build_ms_per_wave", "step_telemetry_ms",
                "frontdoor_route_ms_per_step")
RETIRED = "idle_unattributed_share"     # PR 39: a share of almost nothing
OFFLINE = ["batch-offline", "longdoc-offline", "rag-offline", "repo-offline"]
CHAT_ONLY = ("frontdoor_emit_to_write_p99_ms", "engine_ttft_p95_ms")
DEVICE_WORDS = ("roofline", "device_idle", "dev_ms", "mfu", "hbm_peak",
                "collective")


def _reader(name):
    return manifest.load_reader(name)


def _span(name, t0, dur, **attrs):
    return {"name": name, "t0": t0, "t1": t0 + dur, "attrs": attrs}


def _record():
    """A window [10, 20] with three steps in it and one before it."""
    spans = [_span("serving.step", 5.0, 0.02),
             _span("serving.readback_wait", 5.001, 0.5)]
    for i, t in enumerate((11.0, 12.0, 13.0)):
        spans += [_span("serving.step", t, 0.020),
                  _span("serving.readback_wait", t + 0.003, 0.012),
                  _span("serving.telemetry", t + 0.020, 0.0003),
                  _span("serving.http.route", t + 0.021, 0.0002)]
    spans += [_span("serving.readback_wait", 13.016, 0.003),
              _span("serving.http.ops", 12.5, 0.0004),
              _span("serving.prefill_build", 11.001, 0.001, bucket=256),
              _span("serving.prefill_build", 13.001, 0.003, bucket=512),
              _span("serving.request", 10.5, 3.0, ttft_ms=100.0),
              _span("serving.request", 11.5, 3.0, ttft_ms=300.0),
              _span("serving.request", 12.5, 3.0, ttft_ms=None),
              _span("serving.request", 9.0, 3.0, ttft_ms=9000.0)]
    return {"t_open": 10.0, "t_close": 20.0, "spans": spans}


def test_span_per_divides_by_another_spans_count_or_gives_the_mean():
    read = _reader("span_per").read
    rec = _record()
    # 3 x 12 ms + 3 ms over three steps; the step before the window is out
    assert read(rec, spans=["serving.readback_wait"],
                per="serving.step") == pytest.approx(13.0)
    assert read(rec, spans=["serving.http.ops", "serving.http.route"],
                per="serving.step") == pytest.approx((0.4 + 0.6) / 3)
    assert read(rec, spans=["serving.prefill_build"]) == pytest.approx(2.0)
    assert read(rec, spans=["serving.telemetry"], per="serving.step",
                scale=1.0) == pytest.approx(0.0003)
    # a program without the span (the parent commit) reads nothing
    assert read(rec, spans=["serving.nothing"], per="serving.step") is None
    assert read({"spans": []}, spans=["serving.telemetry"]) is None
    assert read({"t_open": 0.0, "t_close": 1.0},
                spans=["serving.telemetry"]) is None


def test_span_attr_quantile_takes_the_spans_that_began_in_the_window():
    read = _reader("span_attr_quantile").read
    rec = _record()
    kw = dict(name="serving.request", attr="ttft_ms")
    assert read(rec, q=0.5, **kw) == pytest.approx(200.0)
    assert read(rec, q=0.95, **kw) == pytest.approx(290.0)
    assert read(rec, q=1.0, scale=0.001, **kw) == pytest.approx(0.3)
    # the parent's request spans carry no ttft_ms
    assert read(rec, name="serving.request", attr="queue_ms", q=0.5) is None
    assert read({}, q=0.5, **kw) is None


def test_trace_idle_named_shares_out_the_idle_time_less_the_seams():
    read = _reader("trace_idle_named").read
    names = ["serving.step", "(no host span)"]
    red = {"window_s": 4.0, "busy_s": 3.8,
           "idle_gaps": [["serving.readback_wait", 0.10],
                         ["serving.step", 0.03],
                         ["(between operations)", 0.04],
                         ["serving.decode_prepare", 0.02],
                         ["(no host span)", 0.01]]}
    assert read({"trace": red}, names=names) == pytest.approx(25.0)
    assert read({"trace": dict(red, idle_gaps=[["serving.admit", 0.2]])},
                names=names) == 0.0
    assert read({"trace": None}, names=names) is None
    assert read({}, names=names) is None
    assert read({"trace": dict(red, busy_s=4.0, idle_gaps=[])},
                names=names) is None


def test_the_new_entries_and_files():
    """By name and by membership: each phase reading is one name for the
    latency cells and one (``offline.``, PR 39) for the ``tokens_per_s``
    cells, with one reader and the same arguments."""
    man = manifest.Manifest()
    man.validate()
    by = {m["name"]: m for m in man.doc["per_layer"]}
    for name in STEP_METRICS:
        chat, off = by[name], by["offline." + name]
        assert "chat-steady" in chat["workloads"]
        # at the least: a later cell appends itself (benchmark/README.md)
        assert set(OFFLINE) <= set(off["workloads"])
        assert "batch." + name not in by
        assert off["moves"] == "tokens_per_s"
        assert (chat["layer"], chat["source"], chat["unit"]) == (
            off["layer"], off["source"], off["unit"])
        assert man.metric_spec(name) == man.metric_spec("offline." + name)
    for name in CHAT_ONLY:
        assert "chat-steady" in by[name]["workloads"]
        assert not {"batch." + name, "offline." + name} & set(by)
    assert not {RETIRED, "batch." + RETIRED, "offline." + RETIRED} & set(by)
    layers = {m["layer"] for m in man.doc["per_layer"]
              if not m["name"].split(".")[-1] in STEP_METRICS + CHAT_ONLY}
    new = [by[n] for n in by if n.split(".")[-1] in STEP_METRICS + CHAT_ONLY]
    assert {m["name"] for m in new} >= set(STEP_METRICS + CHAT_ONLY) | {
        "offline." + n for n in STEP_METRICS}
    for m in new:
        assert m["layer"] in layers                    # no new layer name
        reads_device = m["source"] == "device_trace"
        assert reads_device or not any(w in m["name"] for w in DEVICE_WORDS)
    assert man.metric_spec("step_host_ms")["args"]["name"] == \
        "serving_step_host_seconds"


@pytest.mark.parametrize("cell,prefix,only", [
    ("chat-steady", "", CHAT_ONLY), ("batch-offline", "offline.", ())])
def test_traced_rehearsal_reports_the_phase_metrics(tmp_path, cell, prefix,
                                                    only):
    man = tiny.make_root(str(tmp_path))
    out = run.measure(man, tiny.args(cell, seed=2**31 + 25, trace=1),
                      jax.devices()[:1])
    assert out["correct"] and out["failed"] == 0
    got = out["metrics"]
    want = {prefix + n for n in STEP_METRICS} | set(only)
    assert want <= set(got), want - set(got)
    assert not any(RETIRED in n for n in got)
    assert all(got[n]["value"] >= 0.0 for n in want)
    # what PR 24's rehearsal saw is still there
    fill = "prefill_row_fill" if cell == "chat-steady" \
        else "batch.prefill_row_fill"
    assert {prefix + "sched_host_ms_per_step", fill} <= set(got)
    host = got[prefix + "step_host_ms"]["value"]
    wait = got[prefix + "readback_wait_ms_per_step"]["value"]
    step = got[prefix + "sched_host_ms_per_step"]["value"]
    assert 0.0 < host <= step
    # the two halves make up the step (the window's edges cut one differently
    # from the other by at most a step or two)
    assert host + wait == pytest.approx(step, rel=0.25)
    json.dumps(out)
