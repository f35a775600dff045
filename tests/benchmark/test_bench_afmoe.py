"""The ``agent-offline`` cell on ``trinity-large-preview-serve-ep8``: manifest
entries, files, the configuration's arithmetic (ISSUE 42), the cost
functions by hand (the gate's projection, the dense layer, the shared expert
and the router's whole width are fixed work; a routed expert counts where it
is HELD and had a row; a window layer's work is what its mask lets a query
SEE), and the family's rehearsal on the CPU through the harness's own path,
with the int8 control coming out not correct."""
import json
import os

import jax
import pytest

import bench_tiny as tiny
from benchmark import correct, manifest, run, serve_cell, traffic
from paddle_tpu import observability

CELL, CONFIG = "agent-offline", "trinity-large-preview-serve-ep8"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
KV = "KV manager serving/engine.py"
KERNELS = "kernels paged_attention.py/pallas_attention.py"
PROGRAMS = "compiled programs _paged_prefill/_paged_decode"
AFM = {"afm.decode_dev_ms_per_step", "afm.prefill_dev_ms_per_ktok",
       "afm.decode_hbm_roofline", "afm.prefill_flops_roofline",
       "afm.walk_full_roofline", "afm.walk_window_roofline",
       "afm.flash_roofline", "afm.past_window_token_share"}
SHARED = {"offline.device_idle", "offline.hbm_peak_gb",
          "offline.http_non200_share", "offline.sched_host_ms_per_step",
          "offline.step_host_ms", "offline.readback_wait_ms_per_step",
          "offline.prefill_build_ms_per_wave", "offline.step_telemetry_ms",
          "offline.frontdoor_route_ms_per_step", "offline.decode_slots_mean",
          "offline.kv_used_peak", "offline.preemptions",
          "offline.recompiles_in_window", "device_starved_ms_per_step",
          "pipeline_drains_per_step", "offline.expert_gmm_roofline",
          "offline.expert_rows_per_step", "offline.experts_hit_share",
          "offline.expert_load_max_over_mean", "offline.kv_bytes_per_token",
          "offline.piece_row_fill", "piece_lone_share",
          "mel.window_walk_share", "mel.window_blocks_recycled_per_step",
          "ds.routed_here_share"}


def test_the_cell_the_configuration_and_the_metrics():
    """By name and by membership: nothing here counts the file's entries or
    says where in a list they lie."""
    man = manifest.Manifest()
    man.validate()
    cell = man.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, CELL, 1)
    assert {m["name"] for m in man.metrics_for(CELL, "end_to_end")} >= {
        "tokens_per_s", "setup_s"}
    mine = {m["name"]: m for m in man.metrics_for(CELL, "per_layer")}
    assert AFM | SHARED <= set(mine), (AFM | SHARED) - set(mine)
    assert all(CELL in m["workloads"] and m["moves"] == "tokens_per_s"
               for m in mine.values())
    # what only this family has, no other cell lists
    assert all(m["workloads"] == [CELL] for n, m in mine.items()
               if n.startswith("afm."))
    # one reading, one name: the window's and the share's readings join the
    # names that Mellum2's and DeepSeek-V2's cells brought
    assert "repo-offline" in mine["mel.window_walk_share"]["workloads"]
    assert "longdoc-offline" in mine["ds.routed_here_share"]["workloads"]
    # repo-offline is not given the new counter's name here (a standing
    # cell's set waits for a benchmark issue)
    assert mine["afm.past_window_token_share"]["layer"] == KV
    assert {mine[n]["layer"] for n in (
        "afm.walk_full_roofline", "afm.walk_window_roofline",
        "afm.flash_roofline")} == {KERNELS}
    assert {mine[n]["layer"] for n in (
        "afm.decode_dev_ms_per_step", "afm.prefill_dev_ms_per_ktok",
        "afm.decode_hbm_roofline", "afm.prefill_flops_roofline")} == {
        PROGRAMS}
    # no layer name of its own, no reader of its own: data files over the
    # readers that stand
    others = {m["layer"] for m in man.doc["per_layer"]
              if not m["name"].startswith("afm.")}
    assert {m["layer"] for m in mine.values()} <= others
    readers = {json.load(open(os.path.join(
        man.data_dir, "metrics", n + ".json")))["reader"] for n in AFM}
    assert readers == {"trace_program", "moe_trace_roofline", "window_walk",
                       "counter"}
    # every kernel of this model under its own name in some data file
    text = "".join(open(os.path.join(man.data_dir, "metrics", n + ".json")
                        ).read() for n in AFM)
    for kernel in ("afmoe_walk_full", "afmoe_walk_window",
                   "afmoe_prefill_chunk", "history_full", "history_window"):
        assert kernel in text


def test_the_entries_this_pr_added_keep_the_contracts_form():
    """What `Manifest.validate` does not hold and the driver refuses before
    any run: a `why`, a `layer` and a `source` are 1 to 200 printable
    characters on one line (the configuration's `why` was 204 once)."""
    man = manifest.Manifest()
    config = next(c for c in man.doc["configs"] if c["name"] == CONFIG)
    texts = [config["why"], config["source"], man.workload(CELL)["why"]]
    texts += [m["layer"] for m in man.metrics_for(CELL, "per_layer")]
    for text in texts:
        assert 1 <= len(text) <= 200, (len(text), text)
        assert text.isascii() and text.isprintable(), text
    assert len(config["reduced"]) <= 16
    assert os.path.getsize(os.path.join(
        man.root, "BENCHMARK.json")) <= 64 * 1024


def test_the_configuration_file_states_the_cut_and_nothing_else():
    man = manifest.Manifest()
    doc = man.config(CONFIG)
    assert doc["reduced"] == ["num_hidden_layers", "num_dense_layers",
                              "num_experts", "vocab_size"]
    assert doc["published"] == {"num_hidden_layers": 60,
                                "num_dense_layers": 6, "num_experts": 256,
                                "vocab_size": 200192}
    assert (doc["num_hidden_layers"], doc["num_dense_layers"],
            doc["num_experts"], doc["n_routed_experts"], doc["router_width"],
            doc["held_first"], doc["vocab_size"]) == (
        5, 1, 32, 32, 256, 0, 25024)
    assert doc["vocab_size"] * 8 == 200192 and doc["num_experts"] * 8 == 256
    # every width as published
    assert (doc["hidden_size"], doc["intermediate_size"],
            doc["moe_intermediate_size"], doc["head_dim"],
            doc["num_attention_heads"], doc["num_key_value_heads"],
            doc["num_experts_per_tok"], doc["sliding_window"],
            doc["route_scale"]) == (3072, 12288, 3072, 128, 48, 8, 4, 4096,
                                    2.448)
    assert "deployment" in doc and doc["family"] == "afmoe"
    assert any("gate on the attention's output" in a
               for a in doc["assumed"])
    if os.path.exists(CATALOG):
        cat = next(row for row in map(json.loads, open(CATALOG))
                   if row["name"] == "Trinity-Large-Preview")
        assert doc["source"] == cat["source_url"]
        for k, v in cat["config"].items():   # nested groups and lists whole
            assert k in doc["reduced"] or doc[k] == v, k
    # the floors of a model_config cut: a whole period and four layers after
    # the dense ones, 8 routed experts, an eighth of the vocabulary
    costs = manifest.family_of(doc).costs
    assert doc["layers_run"] == [0, 8, 9, 10, 11]
    assert costs.layer_types(doc) == ["sliding_attention"] * 4 + [
        "full_attention"]
    assert len(doc["layer_types"]) == 60
    fam = manifest.load_family("afmoe")
    assert [fam.layer_kind(doc, l) for l in range(5)] == [
        "sliding-dense", "sliding-moe", "sliding-moe", "sliding-moe",
        "full-moe"]
    spec = man.traffic(CELL)
    assert (spec["kind"], spec["clients"], spec["epoch"], spec["lead_in_s"],
            spec["max_requests_per_s"]) == ("closed_backlog", 64, 128, 30,
                                            8.0)
    assert spec["prompt"] == {"dist": "lognormal", "median": 6144,
                              "sigma": 0.8, "min": 512, "max": 32768}
    assert spec["output"] == {"dist": "lognormal", "median": 512,
                              "sigma": 0.5, "min": 128, "max": 2048}
    assert (spec["order_seed"], spec["temperature"],
            spec["check_requests"]) == (0, 0.0, 16)
    sv = doc["serve"]
    assert (sv["max_slots"], sv["block_size"], sv["max_model_len"],
            sv["num_blocks"], sv["prefill_chunk"], sv["decode_steps"],
            sv["prefix_cache"], sv["prompt_buckets"]) == (
        32, 16, 34816, 32768, 1024, 1, False, [1024])
    # the longest prompt and the longest answer fit a slot
    assert spec["prompt"]["max"] + spec["output"]["max"] == sv[
        "max_model_len"]
    # about seven contexts in ten lie past the window before they decode
    lens = traffic.quantile_lengths(spec["prompt"], 128)
    assert 0.65 < sum(n > 4096 for n in lens) / 128 < 0.73


def test_parameter_counts_are_the_issues_arithmetic():
    """ISSUE 42 section 4, at the published widths and the cut."""
    man = manifest.Manifest()
    m = man.config(CONFIG)
    costs = manifest.family_of(m).costs
    h = 3072
    assert costs.attention_params(m) == 3 * h * 6144 + 2 * h * 1024  # 62.9M
    assert costs.expert_params(m) == 3 * h * 3072                    # 28.3M
    assert (costs.full_layers(m), costs.window_layers(m),
            costs.expert_layers(m)) == (1, 4, 4)
    fixed_layer = costs.attention_params(m) + costs.expert_params(m) \
        + h * 256
    assert fixed_layer == pytest.approx(92.0e6, rel=2e-3)
    dense = 3 * h * 12288
    assert costs.fixed_params(m) == 5 * costs.attention_params(m) + dense \
        + 4 * (costs.expert_params(m) + h * 256) + h * 25024
    held = costs.fixed_params(m) + 25024 * h \
        + 4 * 32 * costs.expert_params(m)           # + the embedding
    assert 2 * held == pytest.approx(8.64e9, rel=5e-3)               # bf16
    assert costs.row_bytes(m) == 4096
    assert costs.kv_bytes_per_token(m) == 4096        # the ONE full layer's
    assert costs.window_bytes_per_slot(m, 16) == 4 * 257 * 16 * 4096
    assert costs.window_bytes_per_slot(m, 16) == pytest.approx(67.4e6,
                                                               rel=1e-3)
    assert 32768 * 16 * 4096 == pytest.approx(2.15e9, rel=2e-3)
    # a cached token-layer of a walk: 48 heads x 4 x 128 FLOPs, 4,096 B
    assert costs.walk_cost(m, "full", 1) == (24576, 4096)
    assert costs.walk_cost(m, "window", 1) == (4 * 24576, 4 * 4096)
    # the whole model: the published 400B, 13B of it active a token
    whole = dict(m, num_hidden_layers=60, num_dense_layers=6,
                 layers_run=list(range(60)), vocab_size=200192)
    total = costs.fixed_params(whole) + 200192 * h \
        + 54 * 256 * costs.expert_params(whole)
    assert total == pytest.approx(398e9, rel=2e-2)
    active = costs.fixed_params(whole) + 54 * 4 * costs.expert_params(whole)
    assert active == pytest.approx(13e9, rel=5e-2)


# a dense window layer, a window expert layer and a full expert layer; two
# query heads on one KV head of 4, a window of 4 tokens, a share of 2 of the
# router's 8: small enough to count
M = {"hidden_size": 8, "intermediate_size": 16, "moe_intermediate_size": 4,
     "head_dim": 4, "num_attention_heads": 2, "num_key_value_heads": 1,
     "num_experts": 2, "n_routed_experts": 2, "router_width": 8,
     "num_shared_experts": 1, "num_experts_per_tok": 2,
     "num_hidden_layers": 3, "num_dense_layers": 1, "vocab_size": 32,
     "sliding_window": 4,
     "layer_types": ["sliding_attention", "full_attention",
                     "sliding_attention", "full_attention"],
     "layers_run": [0, 2, 3]}


def test_costs_by_hand():
    costs = manifest.load_family("afmoe").costs
    attn = 3 * 8 * 8 + 2 * 8 * 4                 # q, g, o and k, v: 256
    assert costs.attention_params(M) == attn
    assert costs.layer_types(M) == ["sliding_attention", "sliding_attention",
                                    "full_attention"]
    assert (costs.full_layers(M), costs.window_layers(M),
            costs.expert_layers(M)) == (1, 2, 2)
    assert costs.expert_params(M) == 3 * 8 * 4
    # the dense layer's FFN, and a router 8 wide and a shared expert in
    # each of the two expert layers, and the head
    fixed = 3 * attn + 3 * 8 * 16 + 2 * (8 * 8 + 96) + 8 * 32
    assert costs.fixed_params(M) == fixed
    ew = costs.elementwise_flops(M)
    assert ew == 3 * (2 * 2 * 4 + 16 * 8)
    assert (costs.row_bytes(M), costs.kv_bytes_per_token(M)) == (16, 16)
    assert costs.window_bytes_per_slot(M, 2) == 2 * 3 * 2 * 16
    assert costs.visible_pairs(M, "full", 3, 5) == 21
    assert costs.visible_pairs(M, "window", 3, 5) == 12
    per_pair = 4 * 2 * 4
    assert costs.attn_flops_causal(M, 3, 5) == per_pair * (21 + 2 * 12)
    assert costs.decode_attention_cost(M, 3, 10, window_tokens=8) == (
        per_pair * (10 + 2 * 8), 16 * (10 + 2 * 8))
    # a routed expert counts where it had a row HERE
    assert costs.expert_gmm_cost(M, 5, 2) == (2 * 96 * 5, 96 * 2 * 2)
    f, b = costs.decode_step_cost(M, 3, 10, expert_rows=5, experts_hit=2,
                                  window_tokens=8)
    assert f == (2 * fixed + ew) * 3 + per_pair * 26 + 960
    assert b == fixed * 2 + 384 + 16 * 26 + 3 * 16 * 3
    assert costs.prefill_flops(M, 3, 5, expert_rows=4, final=False) == (
        (2 * (fixed - 256) + ew) * 3 + per_pair * 45 + 2 * 96 * 4)
    f, b = costs.flash_cost(M, [3, 2], starts=[5, 0])
    assert f == per_pair * (45 + 3 + 2 * 3)
    # q and o of every piece token a layer; K and V rows: the full layer
    # [cached ; piece], a window layer the piece and the last 3 cached
    assert b == 3 * (2 * 2 * 4 * 2) * 5 + 16 * (8 + 2) + 2 * 16 * (6 + 2)
    with pytest.raises(ValueError):
        costs.train_flops_per_token(M, 8)
    with pytest.raises(ValueError):
        costs.flash_cost(M, [3], backward=True)


def test_the_family_refuses_a_trainer_and_other_layouts():
    fam = manifest.load_family("afmoe")
    with pytest.raises(NotImplementedError):
        fam.trainer({})
    doc = manifest.Manifest().config(CONFIG)
    for key, bad in (("score_func", "softmax"), ("tie_word_embeddings", True),
                     ("rope_scaling", {"type": "yarn"}), ("n_group", 2),
                     ("mup_enabled", False), ("hidden_act", "gelu")):
        with pytest.raises(ValueError):
            fam.program_config(dict(doc, **{key: bad}))
    cfg = fam.program_config(doc)
    assert (cfg.num_layers, cfg.num_dense_layers, cfg.sliding_window,
            cfg.num_experts, cfg.held_first, cfg.held_experts,
            cfg.route_scale, cfg.vocab_size) == (
        5, 1, 4096, 256, 0, 32, 2.448, 25024)
    served = cfg.served_model()
    assert served.window == 4096 and len(served.window_entries) == 4
    # the seeded tree: experts for the held share only, the router and its
    # bias over the whole width, the bias in float32
    tree = jax.eval_shape(lambda: fam.make_params(
        doc, jax.random.PRNGKey(0), jax.numpy.bfloat16))
    moe = tree["layers"][1]
    assert moe["e_gu"].shape == (32, 3072, 6144)
    assert moe["e_down"].shape == (32, 3072, 3072)
    assert moe["router"].shape == (3072, 256)
    assert (moe["expert_bias"].shape, str(moe["expert_bias"].dtype)) == (
        (256,), "float32")
    assert moe["wg"].shape == (3072, 6144)
    assert tree["layers"][0]["w_gate"].shape == (3072, 12288)
    total = sum(a.size * a.dtype.itemsize
                for a in jax.tree_util.tree_leaves(tree))
    assert total == pytest.approx(8.64e9, rel=5e-3)


def _rehearsal_root(tmp_path):
    man = tiny.make_root(str(tmp_path))
    path = os.path.join(str(tmp_path), "benchmark", "configs",
                        CONFIG + ".json")
    doc = json.load(open(path))
    doc["serve"]["prefill_chunk"] = 32       # pieces under buckets 16-64
    json.dump(doc, open(path, "w"))
    # every prompt reaches past the ring of 16 / 8 + 1 blocks of 8 tokens
    path = os.path.join(str(tmp_path), "benchmark", "traffic",
                        man.workload(CELL)["traffic"] + ".json")
    doc = json.load(open(path))
    doc["prompt"]["min"] = 26
    json.dump(doc, open(path, "w"))
    return man


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearsed_on_the_cpu(tmp_path, trace):
    """Prefill in pieces, decode through both kinds of cache across a
    window of 16 tokens, the share's counts, the window's spans and both
    window counters, the reference's verdict: the harness's own path."""
    man = _rehearsal_root(tmp_path)
    # four seconds, not the rehearsals' two: under six workers a two-second
    # window once finished no request, and nothing finished is not correct
    out = run.measure(man, tiny.args(CELL, seed=2**31 + 42, seconds=4.0,
                                     trace=trace), jax.devices()[:1])
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 5
    got = out["metrics"]
    if not trace:
        assert set(got) == {"tokens_per_s", "setup_s"}
        assert all(v["value"] > 0 for v in got.values())
        return
    # every shared name whose reader needs neither a device trace nor the
    # chip's memory counter, and this family's counter (off a TPU ``auto``
    # takes the bucketed path, whose pieces carry no rows: nothing counts
    # ``piece_lone_share``)
    want = (SHARED | {"afm.past_window_token_share"}) - {
        "offline.device_idle", "offline.expert_gmm_roofline",
        "offline.hbm_peak_gb", "piece_lone_share"}
    assert want <= set(got), want - set(got)
    assert not any("roofline" in n or "dev_ms" in n for n in got)
    # K and V of two KV heads of 64, the ONE full layer, bf16
    assert got["offline.kv_bytes_per_token"]["value"] == 2 * 2 * 64 * 2
    registry = {m["name"]: sum(s["value"] for s in m["series"])
                for m in observability.snapshot()["metrics"]
                if m["name"].startswith("serving_window_")}
    # four window layers, a ring of 16 / 8 + 1 blocks of 8 tokens
    assert registry["serving_window_bytes_per_slot"] == 4 * 3 * 8 * 512
    assert registry["serving_window_bounded_tokens_total"] > 0
    # every prompt of this root is longer than the window of 16
    assert got["afm.past_window_token_share"]["value"] == 100.0
    assert 0 < got["mel.window_walk_share"]["value"] < 100
    assert got["mel.window_blocks_recycled_per_step"]["value"] > 0
    # a share of 8 of the router's 32 under even routing
    assert 0.1 < got["ds.routed_here_share"]["value"] < 0.45
    assert 0 < got["offline.piece_row_fill"]["value"] <= 100
    assert 0 < got["offline.experts_hit_share"]["value"] <= 100


def test_the_int8_control_comes_out_not_correct_on_the_cpu(tmp_path):
    """What the cell's engine served (built as the cell builds it, driven
    directly), judged by the reference in float32 and by the same
    reference with int8 weights."""
    man = _rehearsal_root(tmp_path)
    model = man.config(man.workload(CELL)["config"])
    seed = 2**31 + 43
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
