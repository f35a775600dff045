"""The reduction from a trace to busy time, program time and idle gaps: on
a hand-made trace whose answers are worked out by hand, and on a small
trace recorded on the chip (``data/recorded_trace.json``: the first
operations, programs and host spans of a traced ``chat-steady`` run, as
``benchmark/trace.py`` ``load`` gives them)."""
import json
import os

import pytest

from benchmark import costs, manifest, peaks, trace
from benchmark.readers import trace_idle, trace_program, trace_roofline

MAN = manifest.Manifest()


def _args(metric):
    return MAN.metric_spec(metric)["args"]

HERE = os.path.dirname(os.path.abspath(__file__))
US = 1000


def _hand_made():
    ops0 = [["fusion.1", 0, 100 * US], ["all-gather-done.2", 100 * US, 50 * US],
            ["fusion.1", 400 * US, 100 * US]]
    ops1 = [["fusion.1", 0, 200 * US], ["fusion.7", 450 * US, 50 * US]]
    mods = [["jit__paged_decode(123)", 0, 150 * US],
            ["jit__paged_prefill(9)", 400 * US, 100 * US]]
    host = [["serving.step", 0, 500 * US], ["serving.readback", 160 * US,
                                             230 * US],
            ["unrelated.python", 0, 10 * US]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops0},
            {"name": "XLA Modules", "events": mods}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": ops1}]},
        {"name": "/host:CPU", "lines": [{"name": "step thread",
                                         "events": host[:2]}]}]}


def test_reduce_by_hand():
    red = trace.reduce(_hand_made())
    assert red["chips"] == 2
    assert red["window_s"] == pytest.approx(500e-6)
    # chip 0 ran 250 us, chip 1 ran 250 us
    assert red["busy_s"] == pytest.approx(250e-6)
    assert red["collective_s"] == pytest.approx(25e-6)
    # programs and kernels are read on the first chip
    assert trace.op_seconds(red, "fusion") == pytest.approx(200e-6)
    assert trace.module_runs(red, "paged_decode") == [(0, 150 * US)]
    assert trace.module_runs(red, "jit", contains="all-gather") == \
        [(0, 150 * US)]
    assert trace.module_runs(red, "jit", lacks="all-gather") == \
        [(400 * US, 100 * US)]
    assert trace.op_seconds(red, "fusion", within=[(400 * US, 100 * US)]) \
        == pytest.approx(100e-6)
    gaps = dict(red["idle_gaps"])
    # both long gaps lie mostly under the readback span, the innermost
    assert gaps["serving.readback"] == pytest.approx(250e-6)
    assert red["device_ops"][0] == ["fusion", pytest.approx(225e-6)]
    assert trace_idle.read({"trace": red}) == pytest.approx(50.0)
    assert trace_program.read({"trace": red}, "paged_decode") == \
        pytest.approx(0.15)


def test_roofline_share_from_a_trace_and_its_refusal():
    red = trace.reduce(_hand_made())
    m = {"family": "llama",     # whose costs the reader looks up
         "hidden_size": 4096, "intermediate_size": 14336, "head_dim": 128,
         "num_attention_heads": 32, "num_key_value_heads": 8,
         "vocab_size": 32768, "num_hidden_layers": 16}
    span = (10.0, 10.5)
    rec = {"trace": red, "model": m, "peak": peaks.peak("TPU v5 lite"),
           "trace_span": span, "t_open": 0.0,
           "spans": [{"name": "serving.decode", "t0": 10.1, "t1": 10.2,
                      "attrs": {}}],
           "client": {"streams": [{"prompt_len": 100, "request_id": 1,
                                   "t_tokens": [10.0, 10.15]}]}}
    # one decode step of 1 slot with 101 live tokens took 150 us: far less
    # than the weights' 9 ms at the peak bandwidth, so the share is refused
    prog = {"pattern": "paged_decode"}
    with pytest.raises(ValueError, match="105%"):
        trace_roofline.read(rec, "decode", program=prog)
    red["module_events"][0][2] = 18000 * US
    share = trace_roofline.read(rec, "decode", program=prog)
    flops, nbytes = costs.decode_step_cost(m, 1, 101)
    assert share == pytest.approx(100 * nbytes / 8.19e11 / 0.018)
    assert 45 < share < 55


def test_reduce_without_a_device_plane_reads_nothing():
    red = trace.reduce({"planes": [{"name": "/host:CPU", "lines": []}]})
    assert red["chips"] == 0 and red["busy_s"] == 0.0


def test_recorded_chip_trace():
    path = os.path.join(HERE, "data", "recorded_trace.json")
    doc = json.load(open(path))
    red = trace.reduce(doc["trace"])
    want = doc["expected"]
    assert red["chips"] == want["chips"]
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert red["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["device_ops"][0][0] == want["top_op"]
    assert red["idle_gaps"][0][0] == "(between operations)"
    # the programs and kernels, told apart as the metric files tell them
    dec = _args("decode_dev_ms_per_step")
    pre = _args("prefill_dev_ms_per_ktok")
    assert len(trace.module_runs(red, dec["pattern"], dec["contains"])) \
        == want["decode_runs"] == 3
    runs = trace.module_runs(red, pre["pattern"], pre["contains"],
                             pre["lacks"])
    assert len(runs) == want["prefill_runs"] == 1
    assert trace_program.read({"trace": red}, **dec) == pytest.approx(
        16.0, abs=1.5)          # ms a decode step, as the whole trace read
    walk = _args("ragged_walk_roofline")
    flash = _args("flash_roofline")
    assert trace.op_seconds(red, walk["op"]) == pytest.approx(
        want["ragged_s"]) and want["ragged_s"] > 0
    assert trace.op_seconds(red, flash["op"], flash["op_lacks"], runs) == \
        pytest.approx(want["flash_s"]) and want["flash_s"] > 0
