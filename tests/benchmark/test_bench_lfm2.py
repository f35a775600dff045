"""The ``rag-offline`` cell on ``lfm2-8b-a1b-serve`` (and ``doc-prefill`` on
the Mistral configuration, where the benchmark has it): manifest entries,
files, the cost functions by hand, the new reader on recorded spans, and
the family's rehearsal on the CPU through the harness's own path, with the
int8 control coming out not correct."""
import json
import os

import jax
import pytest

import bench_tiny as tiny
from benchmark import correct, manifest, run, serve_cell, traffic
from paddle_tpu.observability import get_tracer

CELL, CONFIG = "rag-offline", "lfm2-8b-a1b-serve"
# the cells of the other families (a later cell of this one may append itself
# to this family's names: ``test_bench_names.py`` refuses it a copy)
OTHERS = {"chat-steady", "doc-prefill", "pretrain-4k-mesh4", "batch-offline",
          "longdoc-offline", "rag-offline", "repo-offline"} - {CELL}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_cells_the_configuration_and_the_metrics():
    man = manifest.Manifest()
    man.validate()
    cell = man.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, CELL, 1)
    assert {m["name"] for m in man.metrics_for(CELL, "end_to_end")} >= {
        "tokens_per_s", "setup_s"}
    # by name and by membership (PR 39): what only this family has under
    # ``lfm.``, what every offline cell reads under ``offline.``
    mine = {m["name"]: m for m in man.metrics_for(CELL, "per_layer")}
    assert all(CELL in m["workloads"] and m["moves"] == "tokens_per_s"
               for m in mine.values())
    assert {"lfm.decode_dev_ms_per_step", "lfm.decode_hbm_roofline",
            "lfm.prefill_dev_ms_per_ktok", "lfm.prefill_flops_roofline",
            "offline.piece_row_fill", "offline.expert_gmm_roofline",
            "offline.expert_rows_per_step", "offline.experts_hit_share",
            "offline.expert_load_max_over_mean", "lfm.ragged_walk_roofline",
            "lfm.flash_roofline", "offline.kv_bytes_per_token",
            "offline.sched_host_ms_per_step", "offline.step_host_ms",
            "offline.decode_slots_mean", "offline.kv_used_peak",
            "offline.preemptions", "offline.recompiles_in_window",
            "offline.device_idle", "offline.hbm_peak_gb",
            "offline.http_non200_share", "offline.state_bytes_per_slot",
            "lfm.state_carried_share"} <= set(mine)
    # what only this family has, no other family's cell lists
    assert all(not OTHERS & set(m["workloads"]) for n, m in mine.items()
               if n.startswith("lfm."))
    # no layer name of its own under ``lfm.``: the state's metrics lie with
    # the KV manager, the experts' with the expert layer the file names
    others = {m["layer"] for m in man.doc["per_layer"]
              if CELL not in m.get("workloads", [CELL])}
    assert {m["layer"] for n, m in mine.items()
            if n.startswith("lfm.")} <= others
    assert mine["lfm.state_carried_share"]["layer"] == mine[
        "offline.state_bytes_per_slot"]["layer"] == (
        "KV manager serving/engine.py")
    if "doc-prefill" in man.workloads:
        doc = man.workload("doc-prefill")
        assert (doc["config"], doc["chips"]) == ("mistral-7b-v0.3-serve", 1)
        assert {m["name"] for m in man.metrics_for(
            "doc-prefill", "end_to_end")} >= {"itl_p50_ms", "itl_p99_ms",
                                              "setup_s"}
        assert len([m for m in man.metrics_for("doc-prefill", "per_layer")
                    if m["name"].startswith("doc.")]) <= 10


def test_the_configuration_file_states_the_cut_and_nothing_else():
    man = manifest.Manifest()
    doc = man.config(CONFIG)
    assert doc["reduced"] == ["num_hidden_layers"]
    assert doc["published"] == {"num_hidden_layers": 24}
    assert doc["num_hidden_layers"] in (16, 12)
    assert (doc["num_experts"], doc["num_experts_per_tok"], doc["vocab_size"],
            doc["hidden_size"], doc["moe_intermediate_size"],
            doc["intermediate_size"]) == (32, 4, 65536, 2048, 1792, 7168)
    if os.path.exists(CATALOG):
        cat = next(row for row in map(json.loads, open(CATALOG))
                   if row["name"] == "LFM2-8B-A1B")
        assert doc["source"] == cat["source_url"]
        for k, v in cat["config"].items():      # the list of types whole
            assert k in doc["reduced"] or doc[k] == v, k
    costs = manifest.family_of(doc).costs
    run_types = costs.layer_types(doc)
    assert len(run_types) == doc["num_hidden_layers"]
    assert run_types[:4] == ["conv", "conv", "full_attention", "conv"]
    assert run_types == run_types[:4] * (len(run_types) // 4)   # periods
    spec = man.traffic(CELL)
    assert (spec["kind"], spec["clients"], spec["epoch"],
            spec["max_requests_per_s"], spec["lead_in_s"]) == (
        "closed_backlog", 128, 128, 24, 20)
    assert spec["prompt"] == {"dist": "lognormal", "median": 1024,
                              "sigma": 0.7, "min": 128, "max": 8192}
    assert spec["output"] == {"dist": "lognormal", "median": 192,
                              "sigma": 0.5, "min": 32, "max": 768}
    assert (spec["check_requests"], spec["order_seed"],
            spec["temperature"]) == (4, 0, 0.0)
    sv = doc["serve"]
    assert (sv["max_slots"], sv["block_size"], sv["max_model_len"],
            sv["prefill_chunk"], sv["num_blocks"], sv["decode_steps"],
            sv["prefix_cache"]) == (64, 16, 9216, 1024, 12288, 1, False)
    # every piece of the traffic lands in a bucket the warm-up compiles
    assert sv["prompt_buckets"] == [1024]


def test_parameter_counts_are_the_issues_arithmetic():
    """ISSUE 32 section 2, at the published widths and 16 layers."""
    man = manifest.Manifest()
    m = dict(man.config(CONFIG), num_hidden_layers=16)
    costs = manifest.family_of(m).costs
    assert costs.conv_params(m) == 2048 * 6144 + 2048 * 2048 + 6144   # 16.8M
    assert costs.attention_params(m) == 2 * 2048 * 2048 + 2 * 2048 * 512
    assert costs.expert_params(m) == 3 * 2048 * 1792                  # 11.01M
    assert (costs.conv_layers(m), costs.attention_layers(m),
            costs.dense_layers(m), costs.expert_layers(m)) == (12, 4, 2, 14)
    held = costs.fixed_params(m) + 14 * 32 * costs.expert_params(m)
    assert held == pytest.approx(5399e6, rel=2e-3)                    # 10.8 GB
    assert costs.kv_bytes_per_token(m) == 8192
    assert costs.state_bytes_per_slot(m) == 12 * 8192
    # a cached token-layer of the walk: 8,192 FLOPs against 2,048 B
    f, b = costs.decode_attention_cost(m, 1, 1)
    assert (f, b) == (4 * 8192, 4 * 2048)
    # the whole model, 24 layers: the published 8.3B
    whole = dict(m, num_hidden_layers=24)
    total = (costs.fixed_params(whole)
             + 22 * 32 * costs.expert_params(whole))
    assert total == pytest.approx(8.34e9, rel=5e-3)


# three layers (a dense convolution, an attention and a convolution with
# experts), two query heads on one KV head: small enough to count
M = {"hidden_size": 8, "intermediate_size": 16, "moe_intermediate_size": 4,
     "num_attention_heads": 2, "num_key_value_heads": 1,
     "num_dense_layers": 1, "num_experts": 4, "n_routed_experts": 4,
     "num_experts_per_tok": 2, "num_hidden_layers": 3, "vocab_size": 32,
     "layer_types": ["conv", "full_attention", "conv", "conv"]}


def test_costs_by_hand():
    costs = manifest.load_family("lfm2_moe").costs
    conv = 8 * 24 + 24 + 8 * 8                                  # 280
    attn = 2 * 8 * 8 + 2 * 8 * 4                                # 192
    assert (costs.conv_params(M), costs.attention_params(M)) == (conv, attn)
    assert costs.layer_types(M) == ["conv", "full_attention", "conv"]
    assert costs.expert_params(M) == 3 * 8 * 4
    fixed = 2 * conv + attn + 3 * 8 * 16 + 2 * 8 * 4 + 8 * 32
    assert costs.fixed_params(M) == fixed
    # a cached token: K and V of one KV head of 4, one attention layer, bf16
    assert costs.kv_bytes_per_token(M) == 2 * 4 * 2
    assert costs.state_bytes_per_slot(M) == 2 * 2 * 8 * 2
    # the walk over 10 live tokens: 2 heads, one dot and one weighted sum
    # over 4 columns each
    assert costs.decode_attention_cost(M, 3, 10) == (4 * 2 * 4 * 10, 16 * 10)
    assert costs.expert_gmm_cost(M, 5, 2) == (2 * 96 * 5, 96 * 2 * 2)
    f, b = costs.decode_step_cost(M, 3, 10, expert_rows=5, experts_hit=2)
    assert f == 2 * fixed * 3 + 320 + 960
    assert b == fixed * 2 + 384 + 160 + (16 + 64) * 3
    # 3 queries after 5 cached positions see 15 + 6 pairs
    assert costs.attn_flops_causal(M, 3, 5) == 4 * 2 * 4 * 21
    assert costs.prefill_flops(M, 3, 5, expert_rows=4, final=False) == (
        2 * (fixed - 256) * 3 + costs.attn_flops_causal(M, 3, 5)
        + 2 * 96 * 4)
    f, b = costs.flash_cost(M, [3, 2], starts=[5, 0])
    assert f == costs.attn_flops_causal(M, 3, 5) \
        + costs.attn_flops_causal(M, 2, 0)
    assert b == 2 * 2 * 4 * 2 * 5 + 16 * (8 + 2)
    with pytest.raises(ValueError):
        costs.train_flops_per_token(M, 8)


def test_the_state_s_reader_takes_the_spans_own_attribute():
    read = manifest.load_reader("span_attr_share").read
    span = lambda t, **a: {"name": "serving.prefill", "t0": t, "t1": t + .1,
                           "attrs": a}
    rec = {"t_open": 0.0, "t_close": 10.0, "spans": [
        span(1.0, state_in=False), span(2.0, state_in=True),
        span(3.0, state_in=True), span(4.0, state_in=False),
        span(11.0, state_in=True),                 # outside the window
        {"name": "serving.decode", "t0": 5.0, "t1": 5.1,
         "attrs": {"state_in": True}}]}
    assert read(rec, "serving.prefill", "state_in") == 50.0
    # a program without the attribute (the parent): nothing, and no raise
    bare = {"t_open": 0.0, "t_close": 10.0,
            "spans": [span(1.0, bucket=16), span(2.0, bucket=16)]}
    assert read(bare, "serving.prefill", "state_in") is None
    assert read({}, "serving.prefill", "state_in") is None


def test_the_family_refuses_a_trainer_and_other_convolutions():
    fam = manifest.load_family("lfm2_moe")
    with pytest.raises(NotImplementedError):
        fam.trainer({})
    doc = manifest.Manifest().config(CONFIG)
    with pytest.raises(ValueError):
        fam.program_config(dict(doc, conv_bias=True))
    kinds = [fam.layer_kind(doc, l) for l in range(4)]
    assert kinds == ["conv-dense", "conv-dense", "attn-moe", "conv-moe"]


def _rehearsal_root(tmp_path):
    man = tiny.make_root(str(tmp_path))
    path = os.path.join(str(tmp_path), "benchmark", "configs",
                        CONFIG + ".json")
    doc = json.load(open(path))
    doc["serve"]["prefill_chunk"] = 32       # pieces under buckets 16-64
    json.dump(doc, open(path, "w"))
    return man


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearsed_on_the_cpu(tmp_path, trace):
    """Prefill in pieces, decode through the cache and the state, the
    expert layer's counts and the state's spans and counters, the
    reference's verdict: the harness's own path."""
    man = _rehearsal_root(tmp_path)
    out = run.measure(man, tiny.args(CELL, seed=2**31 + 32, trace=trace),
                      jax.devices()[:1])
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 5
    got = out["metrics"]
    if not trace:
        assert set(got) == {"tokens_per_s", "setup_s"}
        assert all(v["value"] > 0 for v in got.values())
        return
    want = {"offline.piece_row_fill", "offline.expert_rows_per_step",
            "offline.experts_hit_share", "offline.expert_load_max_over_mean",
            "offline.kv_bytes_per_token", "offline.kv_used_peak",
            "offline.preemptions", "offline.recompiles_in_window",
            "offline.sched_host_ms_per_step", "offline.step_host_ms",
            "offline.decode_slots_mean", "offline.http_non200_share",
            "offline.state_bytes_per_slot", "lfm.state_carried_share",
            "offline.readback_wait_ms_per_step",
            "offline.prefill_build_ms_per_wave", "offline.step_telemetry_ms",
            "offline.frontdoor_route_ms_per_step"}
    assert want <= set(got), want - set(got)
    assert not any("roofline" in n or "dev_ms" in n for n in got)
    # K and V of two KV heads of 64, ONE attention layer of three, bf16
    assert got["offline.kv_bytes_per_token"]["value"] == 2 * 2 * 64 * 2
    # two convolution layers: two inputs of 256 each, bf16
    assert got["offline.state_bytes_per_slot"]["value"] == 2 * 2 * 256 * 2
    # prompts of 8-64 in pieces of 32: some pieces carry a state, not all.
    # On a loaded machine the two-second window may hold none that does, so
    # the window's share is held to its range and the whole run to both kinds
    assert 0 <= got["lfm.state_carried_share"]["value"] < 100
    carried = [s.attrs["state_in"] for s in get_tracer().spans()
               if s.name == "serving.prefill" and "state_in" in s.attrs]
    assert any(carried) and not all(carried)
    assert 0 < got["offline.experts_hit_share"]["value"] <= 100


def test_the_int8_control_comes_out_not_correct_on_the_cpu(tmp_path):
    """What the cell's engine served (built as the cell builds it, driven
    directly: sixteen requests, whatever the machine's load), judged by
    the reference in float32 and by the same reference with int8 weights:
    the control's first tokens lie further below the sound reference's
    best than the served ones do."""
    man = _rehearsal_root(tmp_path)
    model = man.config(man.workload(CELL)["config"])
    seed = 2**31 + 33
    eng, _front, _params = serve_cell.build(model, seed, run.log)
    lens = [8 + 7 * i for i in range(16)]           # 8..113: pieces of 32
    ids = [eng.add_request(traffic.prompt_tokens(seed, [5, i], n,
                                                 model["vocab_size"]),
                           max_new_tokens=12) for i, n in enumerate(lens)]
    res = eng.run()
    samples = [{"tag": [5, i], "prompt_len": n, "tokens": res[rid]}
               for i, (n, rid) in enumerate(zip(lens, ids))]
    gaps = correct.served_gaps(model, seed, samples, "int8")
    assert gaps["positions"] == 16 * 12
    # sound: a bf16 engine under the float32 reference; control: int8
    assert gaps["control"]["logit_gap_mean"] > 2 * gaps["logit_gap_mean"]
    assert gaps["control"]["logit_gap_mean"] > 1e-4
