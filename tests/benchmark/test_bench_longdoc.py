"""The ``longdoc-offline`` cell on ``deepseek-v2-serve-ep8``: its manifest
entries, its files, its cost functions by hand, its reader on recorded
counts, and its rehearsal on the CPU through the harness's own path."""
import json
import os

import jax
import pytest

import bench_tiny as tiny
from benchmark import manifest, peaks, run

CELL, CONFIG = "longdoc-offline", "deepseek-v2-serve-ep8"
# the cells of the other families (a later cell of this one may append itself
# to this family's names: ``test_bench_names.py`` refuses it a copy)
OTHERS = {"chat-steady", "doc-prefill", "pretrain-4k-mesh4", "batch-offline",
          "longdoc-offline", "rag-offline", "repo-offline"} - {CELL}
NEW_READER = "moe_trace_roofline"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_cell_its_configuration_and_its_metrics():
    man = manifest.Manifest()
    man.validate()
    cell = man.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, CELL, 1)
    assert {m["name"] for m in man.metrics_for(CELL, "end_to_end")} >= {
        "tokens_per_s", "setup_s"}
    # by name and by membership (PR 39): what only this family has under
    # ``ds.``, what the cells with an expert layer share under ``offline.``
    mine = {m["name"]: m for m in man.metrics_for(CELL, "per_layer")}
    assert all(CELL in m["workloads"] and m["moves"] == "tokens_per_s"
               for m in mine.values())
    # every kernel share and every count the issue names is there
    assert {"ds.latent_walk_roofline", "ds.mla_prefill_attn_roofline",
            "offline.expert_gmm_roofline", "ds.decode_hbm_roofline",
            "ds.prefill_flops_roofline", "ds.routed_here_share",
            "offline.kv_bytes_per_token"} <= set(mine)
    # what only this family has, no other family's cell lists
    assert all(not OTHERS & set(m["workloads"]) for n, m in mine.items()
               if n.startswith("ds."))
    # one layer name that no cell without experts has, the expert layer's
    dense = {m["layer"] for m in man.doc["per_layer"]
             if set(m.get("workloads", [CELL])) <= {
                 "chat-steady", "doc-prefill", "batch-offline",
                 "pretrain-4k-mesh4"}}
    assert mine["offline.expert_gmm_roofline"]["layer"] not in dense
    assert {m["layer"] for n, m in mine.items()
            if n.startswith(("ds.", "offline."))} - dense == {
        mine["offline.expert_gmm_roofline"]["layer"]}


def test_the_configuration_file_states_the_cut():
    man = manifest.Manifest()
    doc = man.config(CONFIG)
    cat = None
    if os.path.exists(CATALOG):
        cat = next(row for row in map(json.loads, open(CATALOG))
                   if row["name"] == "DeepSeek-V2")
    assert sorted(doc["reduced"]) == ["n_routed_experts",
                                      "num_hidden_layers", "vocab_size"]
    assert doc["published"] == {"num_hidden_layers": 60,
                                "n_routed_experts": 160,
                                "vocab_size": 102400}
    assert (doc["num_hidden_layers"], doc["n_routed_experts"],
            doc["vocab_size"], doc["router_width"]) == (7, 20, 12800, 160)
    if cat:      # every published key as published, but the reduced ones
        for k, v in cat["config"].items():
            assert k in doc["reduced"] or doc[k] == v, k
    spec = man.traffic(CELL)
    assert (spec["kind"], spec["clients"], spec["epoch"],
            spec["max_requests_per_s"], spec["lead_in_s"]) == (
        "closed_backlog", 48, 64, 2.0, 30.0)
    assert spec["prompt"] == {"dist": "lognormal", "median": 3072,
                              "sigma": 0.8, "min": 256, "max": 16384}
    assert spec["output"] == {"dist": "lognormal", "median": 1024,
                              "sigma": 0.5, "min": 256, "max": 2048}


def test_parameter_counts_are_the_issues_arithmetic():
    man = manifest.Manifest()
    m = man.config(CONFIG)
    costs = manifest.family_of(m).costs
    assert costs.attention_params(m) == (
        5120 * 1536 + 1536 * 128 * 192 + 5120 * 576 + 512 * 128 * 256
        + 128 * 128 * 5120)                                   # 149.2M
    assert costs.expert_params(m) == 3 * 5120 * 1536          # 23.59M
    # all the chip holds: one dense + six expert layers + the vocabulary
    held = (costs.fixed_params(m) + 12800 * 5120
            + 6 * 20 * costs.expert_params(m))
    assert held == pytest.approx(4483.7e6, rel=1e-4)
    assert costs.latent_bytes_per_token(m) == 7 * 1152


# two layers (one dense, one of experts), two heads: small enough to count
M = {"hidden_size": 8, "intermediate_size": 16, "moe_intermediate_size": 4,
     "num_attention_heads": 2, "q_lora_rank": 6, "kv_lora_rank": 4,
     "qk_nope_head_dim": 3, "qk_rope_head_dim": 2, "v_head_dim": 3,
     "n_routed_experts": 2, "router_width": 8, "n_shared_experts": 1,
     "first_k_dense_replace": 1, "num_hidden_layers": 2, "vocab_size": 32}


def test_costs_by_hand():
    costs = manifest.load_family("deepseek_v2").costs
    attn = 8 * 6 + 6 * 2 * 5 + 8 * 6 + 4 * 2 * 6 + 2 * 3 * 8      # 252
    assert costs.attention_params(M) == attn
    assert costs.expert_params(M) == 3 * 8 * 4
    fixed = (attn + 3 * 8 * 16) + (attn + 96 + 8 * 8) + 8 * 32
    assert costs.fixed_params(M) == fixed
    # a cached token: (4 + 2) entries a layer, two layers, bf16
    assert costs.latent_bytes_per_token(M) == 2 * 6 * 2
    # the latent walk over 10 live tokens: each of 2 heads, in each of 2
    # layers, one dot over 6 columns and one weighted sum over 4
    f, b = costs.decode_attention_cost(M, 3, 10)
    assert (f, b) == (2 * 2 * 2 * (6 + 4) * 10, 24 * 10)
    # experts: 5 pairs computed, 2 experts hit
    assert costs.expert_gmm_cost(M, 5, 2) == (2 * 96 * 5, 96 * 2 * 2)
    # a step of 3 slots: fixed weights once and 2 FLOPs a weight a slot,
    # plus the walk, the experts, and 3 new rows
    f, b = costs.decode_step_cost(M, 3, 10, expert_rows=5, experts_hit=2)
    assert f == 2 * fixed * 3 + 800 + 960
    assert b == fixed * 2 + 384 + 240 + 24 * 3
    # expanded causal attention: 3 queries after 5 cached positions see
    # 15 + 6 pairs; QK at 5 wide and PV at 3 wide, 2 heads, 2 layers
    assert costs.attn_flops_causal(M, 3, 5) == 2 * 2 * 2 * (5 + 3) * 21
    assert costs.prefill_flops(M, 3, 5, expert_rows=4, final=False) == (
        2 * (fixed - 256) * 3 + costs.attn_flops_causal(M, 3, 5)
        + 2 * 96 * 4)
    f, b = costs.flash_cost(M, [3, 2], starts=[5, 0])
    assert f == costs.attn_flops_causal(M, 3, 5) \
        + costs.attn_flops_causal(M, 2, 0)
    assert b == 2 * (5 + 3) * 2 * 2 * 5 + 24 * (8 + 2)


def _record(spans, client=None):
    peak = peaks.peak("TPU v5 lite")
    return {"model": dict(M, family="deepseek_v2"), "peak": peak,
            "spans": spans, "t_open": 0.0, "t_close": 10.0,
            "trace_span": (2.0, 6.0), "client": client or {"streams": []}}


def test_the_reader_takes_the_spans_own_counts():
    read = manifest.load_reader(NEW_READER).read
    decode = lambda t, rows, hit: {
        "name": "serving.decode", "t0": t, "t1": t + 0.01,
        "attrs": {"slots": 2, "expert_rows": rows, "experts_hit": hit}}
    prefill = lambda t, toks, start: {
        "name": "serving.prefill", "t0": t, "t1": t + 0.1,
        "attrs": {"batch": 4, "bucket": 16, "tokens": toks, "start": start,
                  "expert_rows": 3, "experts_hit": 1}}
    spans = [decode(1.0, 4, 2), decode(3.0, 2, 1), decode(7.0, 6, 2),
             prefill(2.5, [16, 8], [0, 16]), prefill(8.0, [16], [0])]
    rec = _record(spans)
    assert read(rec, "expert_rows_per_step") == 4.0
    # 2 held experts x 1 expert layer: (2 + 1 + 2) of 3 x 2
    assert read(rec, "experts_hit_share") == pytest.approx(100 * 5 / 6)
    assert read(rec, "row_fill") == pytest.approx(100 * 40 / 128)
    # spans without the counts (a program before this PR): nothing, no raise
    bare = _record([{"name": "serving.decode", "t0": 1.0, "t1": 1.1,
                     "attrs": {"slots": 2}},
                    {"name": "serving.prefill", "t0": 2.5, "t1": 2.6,
                     "attrs": {"batch": 1, "bucket": 16}}])
    for what in ("expert_rows_per_step", "experts_hit_share", "row_fill"):
        assert read(bare, what) is None
    for what in ("decode", "prefill", "expert_gmm"):
        assert read(bare, what, program={"pattern": "x"}) is None  # no trace


def test_the_reader_refuses_a_share_over_105(tmp_path):
    read = manifest.load_reader(NEW_READER).read
    costs = manifest.load_family("deepseek_v2").costs
    # one grouped matmul of 1 us in which 10^9 pairs were computed
    red = {"op_events": [["%gmm.1 = bf16[8,8] custom-call(...), "
                          "custom_call_target=\"tpu_custom_call\"",
                          100, 1000]],
           "module_events": [["jit_paged_decode(1)", 0, 5000]]}
    span = {"name": "serving.decode", "t0": 3.0, "t1": 3.1,
            "attrs": {"slots": 1, "expert_rows": 1e9, "experts_hit": 1}}
    rec = dict(_record([span]), trace=red)
    with pytest.raises(ValueError, match="105%"):
        read(rec, "expert_gmm", op="^%?gmm[.0-9]* = ")
    # and a plausible one is a share
    span["attrs"]["expert_rows"] = 1
    flops, nbytes = costs.expert_gmm_cost(M, 1, 1)
    peak = rec["peak"]
    assert read(rec, "expert_gmm", op="^%?gmm[.0-9]* = ") == pytest.approx(
        100 * max(flops / peak.flops, nbytes / peak.hbm_bw) / 1e-6)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearsed_on_the_cpu(tmp_path, trace):
    """Chunked prefill (a chunk of 32 under buckets of 16-64), decode
    through the latent cache, the expert share's counts on the spans and
    in the counters, the reference's verdict: the harness's own path."""
    man = tiny.make_root(str(tmp_path))
    path = os.path.join(str(tmp_path), "benchmark", "configs",
                        CONFIG + ".json")
    doc = json.load(open(path))
    doc["serve"]["prefill_chunk"] = 32
    json.dump(doc, open(path, "w"))
    out = run.measure(man, tiny.args(CELL, seed=2**31 + 28, trace=trace),
                      jax.devices()[:1])
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 5
    got = out["metrics"]
    if not trace:
        assert set(got) == {"tokens_per_s", "setup_s"}
        assert all(v["value"] > 0 for v in got.values())
        return
    want = {"offline.piece_row_fill", "offline.expert_rows_per_step",
            "offline.experts_hit_share", "ds.routed_here_share",
            "offline.expert_load_max_over_mean", "offline.kv_bytes_per_token",
            "offline.kv_used_peak", "offline.preemptions",
            "offline.recompiles_in_window", "offline.sched_host_ms_per_step",
            "offline.decode_slots_mean", "offline.http_non200_share",
            "offline.step_host_ms", "offline.readback_wait_ms_per_step",
            "offline.prefill_build_ms_per_wave", "offline.step_telemetry_ms",
            "offline.frontdoor_route_ms_per_step"}
    assert want <= set(got), want - set(got)
    assert not any("roofline" in n or "dev_ms" in n for n in got)
    # a share of 8 of 32 experts, one group of four with two kept: a pair
    # lands here about one time in four
    assert 0.1 < got["ds.routed_here_share"]["value"] < 0.5
    assert 0 < got["offline.experts_hit_share"]["value"] <= 100
    assert 0 < got["offline.piece_row_fill"]["value"] <= 100
    # one padded latent row a layer: (128 + 16 -> 256) x 3 layers x 4 B
    assert got["offline.kv_bytes_per_token"]["value"] == 256 * 3 * 2
