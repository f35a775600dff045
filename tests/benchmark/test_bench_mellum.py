"""The ``repo-offline`` cell on ``mellum2-12b-a2.5b-serve`` and
``doc-prefill`` on the Mistral configuration: manifest entries, files, the
cost functions by hand (a window layer's work is what its mask lets a query
SEE), the new reader on recorded spans, and the family's rehearsal on the
CPU through the harness's own path, with the int8 control coming out not
correct."""
import json
import os

import jax
import pytest

import bench_tiny as tiny
from benchmark import correct, manifest, peaks, run, serve_cell, traffic
from paddle_tpu import observability

CELL, CONFIG = "repo-offline", "mellum2-12b-a2.5b-serve"
# the cells of the other families (a later cell of this one may append itself
# to this family's names: ``test_bench_names.py`` refuses it a copy)
OTHERS = {"chat-steady", "doc-prefill", "pretrain-4k-mesh4", "batch-offline",
          "longdoc-offline", "rag-offline", "repo-offline"} - {CELL}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
KV = "KV manager serving/engine.py"
KERNELS = "kernels paged_attention.py/pallas_attention.py"


def test_the_cells_the_configuration_and_the_metrics():
    """By name and by membership: nothing here counts the file's entries
    or says where in a list they lie, so that a later PR that adds a cell
    or a metric leaves this test alone."""
    man = manifest.Manifest()
    man.validate()
    cell = man.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, CELL, 1)
    assert {m["name"] for m in man.metrics_for(CELL, "end_to_end")} >= {
        "tokens_per_s", "setup_s"}
    mine = {m["name"]: m for m in man.metrics_for(CELL, "per_layer")}
    # what every offline cell reads and what the cells with an expert
    # layer read (``offline.``, PR 39), and under ``mel.`` what only this
    # family has: its programs, its kernels, the window's spans and counters
    assert {"offline.http_non200_share", "offline.sched_host_ms_per_step",
            "offline.step_host_ms", "offline.decode_slots_mean",
            "offline.piece_row_fill", "offline.kv_used_peak",
            "offline.preemptions", "offline.kv_bytes_per_token",
            "offline.recompiles_in_window",
            "mel.decode_dev_ms_per_step", "mel.prefill_dev_ms_per_ktok",
            "mel.decode_hbm_roofline", "mel.prefill_flops_roofline",
            "offline.expert_gmm_roofline", "offline.expert_rows_per_step",
            "offline.experts_hit_share", "offline.expert_load_max_over_mean",
            "offline.device_idle", "offline.hbm_peak_gb",
            "mel.walk_full_roofline", "mel.walk_window_roofline",
            "mel.flash_roofline", "mel.window_walk_share",
            "mel.window_blocks_recycled_per_step"} <= set(mine)
    # retired in PR 39: the engine's rule of a ring of blocks a slot, which
    # tests/test_mellum.py holds as a gauge
    assert "mel.window_bytes_per_slot" not in mine
    assert all(CELL in m["workloads"] and m["moves"] == "tokens_per_s"
               for m in mine.values())
    # what only this family has, no other family's cell lists
    assert all(not OTHERS & set(m["workloads"]) for n, m in mine.items()
               if n.startswith("mel."))
    assert mine["mel.window_walk_share"]["layer"] == mine[
        "mel.window_blocks_recycled_per_step"]["layer"] == KV
    assert mine["mel.walk_window_roofline"]["layer"] == mine[
        "mel.walk_full_roofline"]["layer"] == mine[
        "mel.flash_roofline"]["layer"] == KERNELS
    # no layer name of its own: each is one that an older cell's metric has
    others = {m["layer"] for m in man.doc["per_layer"]
              if not m["name"].startswith("mel.")}
    assert {m["layer"] for m in mine.values()} <= others
    # ONE entry and one file with rag-offline, wherever both read the same
    by = {m["name"]: m for m in man.doc["per_layer"]}
    for name in ("http_non200_share", "step_host_ms", "piece_row_fill",
                 "experts_hit_share", "expert_load_max_over_mean",
                 "expert_rows_per_step", "kv_used_peak"):
        assert "rag-offline" in mine["offline." + name]["workloads"]
        assert not {"mel." + name, "lfm." + name} & set(by)
    # the other new cell: data files only, on the dense configuration; it
    # reports chat-steady's own metrics (the cell appended to their lists)
    doc = man.workload("doc-prefill")
    assert (doc["config"], doc["traffic"], doc["chips"]) == (
        "mistral-7b-v0.3-serve", "doc-prefill", 1)
    assert {m["name"] for m in man.metrics_for("doc-prefill", "end_to_end")
            } >= {"itl_p50_ms", "itl_p99_ms", "setup_s"}
    docs = {m["name"] for m in man.metrics_for("doc-prefill", "per_layer")}
    assert {"decode_slots_mean", "decode_dev_ms_per_step",
            "prefill_dev_ms_per_ktok", "prefill_row_fill",
            "queue_wait_p90_ms", "engine_ttft_p95_ms", "shed_share",
            "device_idle", "hbm_peak_gb", "recompiles_in_window",
            "decode_hbm_roofline", "prefill_flops_roofline",
            "ragged_walk_roofline", "flash_roofline", "kv_used_peak",
            "step_host_ms", "sched_host_ms_per_step"} <= docs
    assert all("chat-steady" in by[n]["workloads"] for n in docs)
    # the two whose NAME says chat-steady stay that cell's
    assert not {"chat-steady.ttft_p50_ms", "chat.ttft_p95_ms"} & docs
    assert doc["chips"] == cell["chips"] == 1


def test_doc_prefill_s_traffic_and_limits():
    man = manifest.Manifest()
    spec = man.traffic("doc-prefill")
    assert (spec["kind"], spec["rate_per_s"], spec["lead_in_s"]) == (
        "open_poisson", 3.0, 6.0)
    assert spec["prompt"] == {"dist": "lognormal", "median": 1792,
                              "sigma": 0.1, "min": 1536, "max": 2048}
    assert spec["output"] == {"dist": "lognormal", "median": 96,
                              "sigma": 0.2, "min": 64, "max": 128}
    # every prompt in the configuration's 2048 bucket
    plan = traffic.make_plan(spec, 1, 45.0)
    buckets = man.config("mistral-7b-v0.3-serve")["serve"]["prompt_buckets"]
    assert {min(b for b in buckets if b >= r["prompt_len"])
            for r in plan["requests"]} == {2048}
    assert correct.load_limits(man.data_dir, "doc-prefill") == \
        correct.load_limits(man.data_dir, "chat-steady")


def test_the_configuration_file_states_the_cut_and_nothing_else():
    man = manifest.Manifest()
    doc = man.config(CONFIG)
    assert doc["reduced"] == ["num_hidden_layers"]
    assert doc["published"] == {"num_hidden_layers": 28}
    assert doc["num_hidden_layers"] in (12, 8)
    assert (doc["num_experts"], doc["num_experts_per_tok"], doc["vocab_size"],
            doc["hidden_size"], doc["moe_intermediate_size"],
            doc["head_dim"], doc["sliding_window"]) == (
        64, 8, 98304, 2304, 896, 128, 1024)
    assert doc["assumed"][0].startswith("a per-head RMS norm of q and k")
    assert "deployment" in doc and doc["family"] == "mellum"
    if os.path.exists(CATALOG):
        cat = next(row for row in map(json.loads, open(CATALOG))
                   if row["name"] == "Mellum2-12B-A2.5B-Instruct")
        assert doc["source"] == cat["source_url"]
        for k, v in cat["config"].items():   # nested groups and lists whole
            assert k in doc["reduced"] or doc[k] == v, k
    costs = manifest.family_of(doc).costs
    run_types = costs.layer_types(doc)
    assert len(run_types) == doc["num_hidden_layers"]
    assert run_types[:4] == ["sliding_attention"] * 3 + ["full_attention"]
    assert run_types == run_types[:4] * (len(run_types) // 4)   # periods
    assert len(doc["layer_types"]) == len(doc["mlp_layer_types"]) == 28
    spec = man.traffic(CELL)
    assert (spec["kind"], spec["clients"], spec["epoch"],
            spec["lead_in_s"]) == ("closed_backlog", 64, 128, 30)
    assert spec["prompt"] == {"dist": "lognormal", "median": 4096,
                              "sigma": 1.0, "min": 256, "max": 32768}
    assert spec["output"] == {"dist": "lognormal", "median": 384,
                              "sigma": 0.5, "min": 64, "max": 1024}
    assert (spec["order_seed"], spec["temperature"]) == (0, 0.0)
    # enough served positions that the int8 control reads apart from the
    # sound path on every seed (limits/repo-offline.json says how many)
    assert spec["check_requests"] >= 8
    sv = doc["serve"]
    assert (sv["max_slots"], sv["block_size"], sv["max_model_len"],
            sv["prefill_chunk"], sv["decode_steps"], sv["prefix_cache"]) == (
        32, 16, 33792, 1024, 1, False)
    assert sv["num_blocks"] in (24576, 20480)
    assert sv["prompt_buckets"] == [1024]
    # the longest prompt and the longest answer fit a slot
    assert spec["prompt"]["max"] + spec["output"]["max"] <= sv[
        "max_model_len"]


def test_parameter_counts_are_the_issues_arithmetic():
    """ISSUE 35 section 2, at the published widths and 12 layers."""
    man = manifest.Manifest()
    m = dict(man.config(CONFIG), num_hidden_layers=12)
    costs = manifest.family_of(m).costs
    assert costs.attention_params(m) == 2 * 2304 * 4096 + 2 * 2304 * 512
    assert costs.expert_params(m) == 3 * 2304 * 896                # 6.193M
    assert (costs.full_layers(m), costs.window_layers(m),
            costs.expert_layers(m)) == (3, 9, 12)
    held = costs.fixed_params(m) + 98304 * 2304 \
        + 12 * 64 * costs.expert_params(m)          # + the embedding
    assert held == pytest.approx(5466e6, rel=2e-3)                 # 10.93 GB
    assert costs.row_bytes(m) == 2048
    assert costs.kv_bytes_per_token(m) == 6144
    assert costs.window_bytes_per_slot(m, 16) == 9 * 65 * 16 * 2048
    # a cached token-layer of a walk: 16,384 FLOPs against 2,048 B
    assert costs.walk_cost(m, "full", 1) == (3 * 16384, 3 * 2048)
    assert costs.walk_cost(m, "window", 1) == (9 * 16384, 9 * 2048)
    # the whole model, 28 layers: the published 12B
    whole = dict(m, num_hidden_layers=28)
    total = costs.fixed_params(whole) + 98304 * 2304 \
        + 28 * 64 * costs.expert_params(whole)
    assert total == pytest.approx(12.15e9, rel=5e-3)


# two layers (a window layer and a full one), two query heads on one KV
# head of 4, a window of 4 tokens: small enough to count
M = {"hidden_size": 8, "moe_intermediate_size": 4, "head_dim": 4,
     "num_attention_heads": 2, "num_key_value_heads": 1, "num_experts": 4,
     "n_routed_experts": 4, "num_experts_per_tok": 2, "num_hidden_layers": 2,
     "vocab_size": 32, "sliding_window": 4,
     "layer_types": ["sliding_attention", "full_attention",
                     "sliding_attention"]}


def test_costs_by_hand():
    costs = manifest.load_family("mellum").costs
    attn = 2 * 8 * 8 + 2 * 8 * 4                                   # 192
    assert costs.attention_params(M) == attn
    assert costs.layer_types(M) == ["sliding_attention", "full_attention"]
    assert costs.expert_params(M) == 3 * 8 * 4
    fixed = 2 * attn + 2 * 8 * 4 + 8 * 32
    assert costs.fixed_params(M) == fixed
    assert (costs.row_bytes(M), costs.kv_bytes_per_token(M)) == (16, 16)
    # a ring of ceil(4 / 2) + 1 blocks of 2 tokens, one window layer
    assert costs.window_bytes_per_slot(M, 2) == 3 * 2 * 16
    # 3 queries after 5 cached positions: a full layer sees 6 + 7 + 8
    # pairs, a window layer 4 each
    assert costs.visible_pairs(M, "full", 3, 5) == 21
    assert costs.visible_pairs(M, "window", 3, 5) == 12
    # 5 queries from the start: 1 + 2 + 3 + 4 + 4 under the window
    assert costs.visible_pairs(M, "window", 5, 0) == 14
    assert costs.visible_pairs(M, "full", 5, 0) == 15
    assert costs.visible_pairs(M, "window", 2, 2) == 3 + 4
    per_pair = 4 * 2 * 4
    assert costs.attn_flops_causal(M, 3, 5) == per_pair * (21 + 12)
    # the walks over 10 live tokens of 3 slots, of which the window layers
    # may see 8
    assert costs.decode_attention_cost(M, 3, 10, window_tokens=8) == (
        per_pair * (10 + 8), 16 * (10 + 8))
    # with the mean alone, the bound: 3 slots x min(10 / 3, 4)
    assert costs.decode_attention_cost(M, 3, 10)[1] == 16 * (10 + 10)
    assert costs.expert_gmm_cost(M, 5, 2) == (2 * 96 * 5, 96 * 2 * 2)
    f, b = costs.decode_step_cost(M, 3, 10, expert_rows=5, experts_hit=2,
                                  window_tokens=8)
    assert f == 2 * fixed * 3 + per_pair * 18 + 960
    assert b == fixed * 2 + 384 + 16 * 18 + 2 * 16 * 3
    assert costs.prefill_flops(M, 3, 5, expert_rows=4, final=False) == (
        2 * (fixed - 256) * 3 + per_pair * 33 + 2 * 96 * 4)
    f, b = costs.flash_cost(M, [3, 2], starts=[5, 0])
    assert f == per_pair * (33 + 3 + 3)
    # q and o of every piece token a layer; K and V rows: a full layer
    # [cached ; piece], a window layer the piece and the last 3 cached
    assert b == 2 * (2 * 2 * 4 * 2) * 5 + 16 * (8 + 2) + 16 * (6 + 2)
    with pytest.raises(ValueError):
        costs.train_flops_per_token(M, 8)
    with pytest.raises(ValueError):
        costs.flash_cost(M, [3], backward=True)


def _decode_span(t, **a):
    return {"name": "serving.decode", "t0": t, "t1": t + .01, "attrs": a}


def test_the_window_s_reader_on_recorded_spans():
    read = manifest.load_reader("window_walk").read
    rec = {"t_open": 0.0, "t_close": 10.0, "spans": [
        _decode_span(1.0, kv_bytes=1000, window_bytes=300),
        _decode_span(2.0, kv_bytes=3000, window_bytes=500),
        _decode_span(11.0, kv_bytes=10, window_bytes=10),  # past the window
        {"name": "serving.prefill", "t0": 3.0, "t1": 3.1,
         "attrs": {"kv_bytes": 5, "window_bytes": 5}}]}
    assert read(rec, "share") == 100.0 * 800 / 4000
    # a program without the attribute (the parent), and no spans: nothing
    assert read({"t_open": 0.0, "t_close": 10.0, "spans": [
        _decode_span(1.0, kv_bytes=1000)]}, "share") is None
    assert read({}, "share") is None
    # no trace: the trace's metrics read nothing and do not raise
    for what in ("walk", "decode"):
        assert read(rec, what, kind="full", op="^%?mellum_walk_full") is None


def test_the_walks_rooflines_count_what_a_layer_may_see(monkeypatch):
    """Two decode steps traced, two slots of contexts 6 and 100 under a
    window of 4: a full layer's walk is charged 6 + 7 + 100 + 101 tokens, a
    window layer's 4 each, against the kernel's seconds in the trace."""
    reader = manifest.load_reader("window_walk")
    m = dict(M, family="mellum")
    stream = lambda n, ts: {"prompt_len": n, "t_tokens": ts}
    rec = {"model": m, "peak": peaks.Peak(1e5, 16e9, 1e4), "t_open": 100.0,
           "trace": {"any": 1}, "trace_span": (105.0, 106.0),
           "spans": [_decode_span(105.1, expert_rows=4, experts_hit=3),
                     _decode_span(105.6, expert_rows=4, experts_hit=3)],
           "client": {"streams": [stream(5, [4.0, 5.2, 5.7]),
                                  stream(99, [4.5, 5.3, 5.8, 7.0])]}}
    monkeypatch.setattr(reader.trace, "op_seconds",
                        lambda red, op, lacks, runs: 2.0)
    monkeypatch.setattr(reader.trace, "module_runs",
                        lambda red, **kw: [(0, 1.5e9), (0, 1.5e9)])
    costs = manifest.load_family("mellum").costs
    full = reader.read(rec, "walk", kind="full", op="x")
    want_f, want_b = costs.walk_cost(m, "full", 6 + 7 + 100 + 101)
    assert full == pytest.approx(100 * max(want_f / 1e5, want_b / 1e4) / 2.0)
    win = reader.read(rec, "walk", kind="window", op="x")
    want_f, want_b = costs.walk_cost(m, "window", 4 * 4)
    assert win == pytest.approx(100 * max(want_f / 1e5, want_b / 1e4) / 2.0)
    dec = reader.read(rec, "decode", program={"pattern": "paged_decode"})
    f, b = costs.decode_step_cost(m, 2, 107, expert_rows=4, experts_hit=3,
                                  window_tokens=8)
    assert dec == pytest.approx(100 * max(2 * f / 1e5, 2 * b / 1e4) / 3.0)
    # a share over 105% is refused, not clipped
    monkeypatch.setattr(reader.trace, "op_seconds",
                        lambda red, op, lacks, runs: 1e-3)
    with pytest.raises(ValueError, match="roofline share"):
        reader.read(rec, "walk", kind="full", op="x")
    # a configuration without a window reads nothing
    assert reader.read(dict(rec, model={"family": "mellum"}), "walk",
                       kind="full", op="x") is None


def test_the_family_refuses_a_trainer_and_other_layouts():
    fam = manifest.load_family("mellum")
    with pytest.raises(NotImplementedError):
        fam.trainer({})
    doc = manifest.Manifest().config(CONFIG)
    for key, bad in (("attention_bias", True), ("tie_word_embeddings", True),
                     ("use_sliding_window", False),
                     ("mlp_layer_types", ["dense"] * 28)):
        with pytest.raises(ValueError):
            fam.program_config(dict(doc, **{key: bad}))
    assert [fam.layer_kind(doc, l) for l in range(4)] == [
        "sliding_attention"] * 3 + ["full_attention"]
    cfg = fam.program_config(doc)
    assert (cfg.num_layers, cfg.sliding_window, cfg.rope_factor,
            cfg.rope_original_max, cfg.rope_attention_factor) == (
        12, 1024, 16.0, 8192, 1.2772588722239782)


def _rehearsal_root(tmp_path):
    man = tiny.make_root(str(tmp_path))
    path = os.path.join(str(tmp_path), "benchmark", "configs",
                        CONFIG + ".json")
    doc = json.load(open(path))
    doc["serve"]["prefill_chunk"] = 32       # pieces under buckets 16-64
    json.dump(doc, open(path, "w"))
    # every prompt reaches past the ring of 16 / 8 + 1 blocks of 8 tokens,
    # so each admission in a window writes a block again, however few
    # steps a loaded machine gets through in two seconds
    path = os.path.join(str(tmp_path), "benchmark", "traffic",
                        man.workload(CELL)["traffic"] + ".json")
    doc = json.load(open(path))
    doc["prompt"]["min"] = 26
    json.dump(doc, open(path, "w"))
    return man


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearsed_on_the_cpu(tmp_path, trace):
    """Prefill in pieces, decode through both kinds of cache across a
    window of 16 tokens, the spans and counters of the window, the
    reference's verdict: the harness's own path."""
    man = _rehearsal_root(tmp_path)
    out = run.measure(man, tiny.args(CELL, seed=2**31 + 35, trace=trace),
                      jax.devices()[:1])
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 5
    got = out["metrics"]
    if not trace:
        assert set(got) == {"tokens_per_s", "setup_s"}
        assert all(v["value"] > 0 for v in got.values())
        return
    want = {"offline.expert_rows_per_step", "offline.kv_bytes_per_token",
            "offline.kv_used_peak", "offline.preemptions",
            "offline.recompiles_in_window", "offline.sched_host_ms_per_step",
            "offline.decode_slots_mean", "mel.window_walk_share",
            "mel.window_blocks_recycled_per_step",
            "offline.http_non200_share", "offline.step_host_ms",
            "offline.piece_row_fill", "offline.experts_hit_share",
            "offline.expert_load_max_over_mean",
            "offline.readback_wait_ms_per_step",
            "offline.prefill_build_ms_per_wave", "offline.step_telemetry_ms",
            "offline.frontdoor_route_ms_per_step"}
    assert want <= set(got), want - set(got)
    assert not any("roofline" in n or "dev_ms" in n for n in got)
    # K and V of two KV heads of 64, ONE full layer of four, bf16
    assert got["offline.kv_bytes_per_token"]["value"] == 2 * 2 * 64 * 2
    # three window layers, a ring of 16 / 8 + 1 blocks of 8 tokens: the
    # gauge stands (tests/test_mellum.py), its name on the line is retired
    assert "mel.window_bytes_per_slot" not in got
    registry = {m["name"]: sum(s["value"] for s in m["series"])
                for m in observability.snapshot()["metrics"]
                if m["name"].startswith("serving_window_")}
    assert registry["serving_window_bytes_per_slot"] == 3 * 3 * 8 * 512
    assert 0 < got["mel.window_walk_share"]["value"] < 100
    assert 0 < got["offline.piece_row_fill"]["value"] <= 100
    assert 0 < got["offline.experts_hit_share"]["value"] <= 100
    assert 0 < got["offline.step_host_ms"]["value"] \
        <= got["offline.sched_host_ms_per_step"]["value"]
    # every prompt of this root passes the ring's end (``_rehearsal_root``)
    assert got["mel.window_blocks_recycled_per_step"]["value"] > 0
    assert registry["serving_window_blocks_recycled_total"] > 0


@pytest.mark.parametrize("trace", [0, 1])
def test_doc_prefill_rehearsed_on_the_cpu(tmp_path, trace):
    man = tiny.make_root(str(tmp_path))
    out = run.measure(man, tiny.args("doc-prefill", seed=2**31 + 36,
                                     trace=trace), jax.devices()[:1])
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 5
    got = set(out["metrics"])
    if not trace:
        assert got == {"itl_p50_ms", "itl_p99_ms", "setup_s"}
        return
    # chat-steady's own metrics, read here by the same readers: whatever
    # does not need a device trace shows on the CPU
    assert {"decode_slots_mean", "prefill_row_fill", "queue_wait_p90_ms",
            "engine_ttft_p95_ms", "shed_share", "kv_used_peak",
            "preemptions", "recompiles_in_window", "step_host_ms",
            "sched_host_ms_per_step", "http_non200_share"} <= got
    assert not any(n.startswith(("doc.", "chat")) for n in got)


def test_the_int8_control_comes_out_not_correct_on_the_cpu(tmp_path):
    """What the cell's engine served (built as the cell builds it, driven
    directly), judged by the reference in float32 and by the same
    reference with int8 weights."""
    man = _rehearsal_root(tmp_path)
    model = man.config(man.workload(CELL)["config"])
    seed = 2**31 + 37
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
