"""The ``reason-offline`` cell on ``ling-3.0-flash-serve-ep4``: manifest
entries and files, the configuration against the catalog, the cost
functions by hand, the plain reference's KDA against a second literal
recurrence in NumPy, the new reader on recorded spans, and the family's
rehearsal on the CPU through the harness's own path. Nothing here holds a
list of cells, a name's ``workloads`` or a cell's names closed."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny as tiny
from benchmark import manifest, peaks, run
from benchmark.reference import ling_hybrid_f32 as ref
from paddle_tpu.observability import get_tracer

CELL, CONFIG = "reason-offline", "ling-3.0-flash-serve-ep4"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
OWN = {"ling.decode_dev_ms_per_step", "ling.prefill_dev_ms_per_ktok",
       "ling.decode_hbm_roofline", "ling.prefill_flops_roofline",
       "ling.kda_step_roofline", "ling.kda_chunk_roofline",
       "ling.state_walk_share"}
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
          "offline.state_bytes_per_slot",
          # one name for one reading: the MLA layer runs DeepSeek-V2's
          # kernels under their own names, and the share of routed pairs
          # computed here and the share of pieces that began from a
          # carried state have a name each already; a copy is refused
          "ds.latent_walk_roofline", "ds.mla_prefill_attn_roofline",
          "ds.routed_here_share", "lfm.state_carried_share"}


def _model():
    return manifest.Manifest().config(CONFIG)


def test_the_cell_the_configuration_and_the_metrics():
    man = manifest.Manifest()
    man.validate()
    cell = man.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, CELL, 1)
    assert {m["name"] for m in man.metrics_for(CELL, "end_to_end")} >= {
        "tokens_per_s", "setup_s"}
    mine = {m["name"]: m for m in man.metrics_for(CELL, "per_layer")}
    assert OWN | SHARED <= set(mine)
    assert all(CELL in m["workloads"] and m["moves"] == "tokens_per_s"
               for m in mine.values())
    # what only this family has, no other cell lists; and it makes no
    # layer name that no file knows: the kernels' own file aside
    for n in OWN:
        assert all(man.workload(w)["config"] == CONFIG
                   for w in mine[n]["workloads"])
    assert mine["ling.state_walk_share"]["layer"] == mine[
        "offline.state_bytes_per_slot"]["layer"]
    assert mine["ling.kda_step_roofline"]["layer"] == mine[
        "ling.kda_chunk_roofline"]["layer"] == "kernels kda.py"
    t = man.traffic(CELL)
    assert (t["kind"], t["clients"], t["epoch"], t["lead_in_s"],
            t["check_requests"], t["order_seed"]) == (
        "closed_backlog", 128, 128, 30, 4, 0)
    assert t["max_requests_per_s"] == man.traffic("rag-offline")[
        "max_requests_per_s"]
    assert t["prompt"] == {"dist": "lognormal", "median": 1024, "sigma": 0.8,
                           "min": 128, "max": 16384}
    assert t["output"] == {"dist": "lognormal", "median": 2048, "sigma": 0.6,
                           "min": 512, "max": 8192}
    assert t["temperature"] == 0.0


def test_the_configuration_is_the_catalog_s_but_for_what_it_says():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog in this installation")
    row = next(d for d in map(json.loads, open(CATALOG))
               if d["name"] == "Ling-3.0-flash")
    doc = _model()
    assert doc["source"] == row["source_url"]
    assert sorted(doc["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    for k, v in row["config"].items():
        if k in doc["reduced"]:
            assert doc["published"][k] == v and doc[k] != v
        else:
            assert doc[k] == v, k
    assert (doc["num_hidden_layers"], doc["num_experts"], doc["vocab_size"],
            doc["first_layer"], doc["router_width"], doc["held_first"],
            doc["n_routed_experts"]) == (7, 128, 39296, 1, 512, 0, 128)
    sv = doc["serve"]
    assert (sv["max_slots"], sv["block_size"], sv["max_model_len"],
            sv["num_blocks"], sv["prompt_buckets"], sv["prefill_chunk"],
            sv["decode_steps"], sv["prefix_cache"]) == (
        64, 16, 25600, 32768, [1024], 1024, 1, False)
    assert len(doc["assumed"]) >= 8 and doc["deployment"] and doc["why"]


def test_the_family_s_layers_and_what_it_refuses():
    fam = manifest.load_family("ling_hybrid")
    doc = _model()
    kinds = [fam.layer_kind(doc, l) for l in range(7)]
    assert kinds == ["kda-dense", "kda-moe", "kda-moe", "kda-moe", "mla-moe",
                     "kda-moe", "kda-moe"]
    with pytest.raises(NotImplementedError):
        fam.trainer({})
    for bad in (dict(score_function="softmax"), dict(q_lora_rank=1536),
                dict(kda_safe_gate=False), dict(topk_method="greedy")):
        with pytest.raises(ValueError):
            fam.program_config(dict(doc, **bad))
    shapes = fam.layer_shapes(doc, 1)
    assert shapes["router"] == (2560, 512) and shapes["e_gate"] == (
        128, 2560, 768) and shapes["w_f"] == (2560, 4096)
    assert fam.layer_shapes(doc, 4)["w_q"] == (2560, 32 * 192)
    cfg = fam.program_config(doc)
    assert (cfg.held_first, cfg.held_experts, cfg.num_experts) == (0, 128,
                                                                   512)


def test_a_state_in_another_precision_is_refused_where_the_cell_is_built(
        monkeypatch):
    """The served tokens cannot tell a matrix state kept in bf16 from one
    in float32 (``limits/reason-offline.json`` has the readings), so the
    family holds the program to the precision the configuration states,
    where the harness builds it: the bf16-state control does not run."""
    from paddle_tpu.models import ling_hybrid

    fam = manifest.load_family("ling_hybrid")
    doc = _model()
    assert doc["serve"]["state_dtype"] == "float32"
    real = ling_hybrid.LingHybridServed.make_state
    monkeypatch.setattr(
        ling_hybrid.LingHybridServed, "make_state",
        lambda self, slots: {
            n: a.astype(jnp.bfloat16) if n in self.state_in_place else a
            for n, a in real(self, slots).items()})
    with pytest.raises(ValueError, match="serve.state_dtype"):
        fam.program_config(doc)
    with pytest.raises(ValueError, match="serve.state_dtype"):
        fam.program_config(dict(doc, **fam.tiny(doc)))


def test_the_costs_by_hand():
    m = _model()
    costs = manifest.family_of(m).costs
    assert (costs.kda_layers(m), costs.mla_layers(m), costs.dense_layers(m),
            costs.expert_layers(m)) == (6, 1, 1, 6)
    # 32 heads of a 128 x 128 float32 matrix, and three inputs of 3 x 4096
    # channels in bf16, over six KDA layers: 13.0 MB a slot
    assert costs.matrix_bytes_per_slot(m) == 2097152
    assert costs.conv_bytes_per_slot(m) == 73728
    assert costs.state_bytes_per_slot(m) == 6 * (2097152 + 73728) == 13025280
    # the one MLA layer's latent row: 512 + 64 entries in bf16 (the pool's
    # row is padded to 640: 1,280 B, which the program's gauge reads)
    assert costs.kv_bytes_per_token(m) == 1152
    assert costs.kda_params(m) == 5 * 2560 * 4096 + 2 * 2560 * 32
    assert costs.mla_params(m) == (2560 * 32 * 192 + 2560 * 576
                                   + 512 * 32 * 256 + 4096 * 2560 + 2560 * 32)
    assert costs.expert_params(m) == 3 * 2560 * 768 == 5898240
    fixed = (6 * costs.kda_params(m) + costs.mla_params(m)
             + 3 * 2560 * 6144 + 6 * (5898240 + 2560 * 512) + 2560 * 39296)
    assert costs.fixed_params(m) == fixed
    # the hit experts' bytes: 11.8 MB each
    flops, nbytes = costs.expert_gmm_cost(m, 100.0, 80.0)
    assert (flops, nbytes) == (2.0 * 5898240 * 100, 5898240 * 2 * 80.0)
    # the recurrence a token a head: d^2 + 3 x 2 d^2 = 7 x 16,384
    rec = 7.0 * 128 * 128 * 32 * 6
    assert costs.recurrence_flops_per_token(m) == rec
    # two worked pieces, of 1,000 real tokens and of 24, as their spans
    # count them: tokens x the six layers the scan advanced
    f, b = costs.kda_chunk_cost(m, [6000, 144])
    assert f == rec * 1024
    per_tok = 32 * (128 * (4 * 2 + 4) + 4) * 6
    assert b == per_tok * 1024 + 2.0 * 6 * 2097152 * 2
    f, b = costs.kda_step_cost(m, 64.0)
    assert f == rec * 64
    assert b == 6 * (2 * 2097152 + (5 * 128 * 4 + 4) * 32) * 64.0
    # a decode step: weights, hit experts, live rows, a new row and the
    # state read and written a slot
    f, b = costs.decode_step_cost(m, 64.0, 200000.0, expert_rows=768.0,
                                  experts_hit=480.0)
    assert b == (fixed * 2 + 480.0 * 11796480 + 1152 * 200000.0
                 + (1152 + 2 * 13025280) * 64.0)
    assert f == (2.0 * fixed * 64 + 2.0 * 32 * (576 + 512) * 200000.0
                 + 2.0 * 5898240 * 768 + rec * 64)
    # prefill attention of the one MLA layer: the causal pairs at 192 + 128
    assert costs.attn_flops_causal(m, 4, 10) == 2.0 * 32 * 320 * (40 + 10)
    assert costs.prefill_flops(m, 10, 0, final=False) == (
        (2.0 * (fixed - 2560 * 39296) + rec) * 10
        + costs.attn_flops_causal(m, 10, 0))
    with pytest.raises(ValueError):
        costs.train_flops_per_token(m, 4096)


def _kda_numpy(q, k, v, g, beta):
    """The recurrence once more, literally, in float64 NumPy."""
    S_, H, d = q.shape
    state = np.zeros((H, d, d))
    out = np.zeros((S_, H, d))
    for t in range(S_):
        for h in range(H):
            s = np.diag(np.exp(g[t, h])) @ state[h]
            s = s + beta[t, h] * np.outer(k[t, h], v[t, h] - s.T @ k[t, h])
            state[h] = s
            out[t, h] = s.T @ q[t, h]
    return out


def test_the_reference_s_kda_is_the_literal_recurrence():
    rng = np.random.default_rng(0)
    S_, H, d = 9, 2, 8
    q, k, v = (rng.standard_normal((S_, H, d)) for _ in range(3))
    g = -5.0 * rng.uniform(size=(S_, H, d))
    beta = rng.uniform(size=(S_, H))
    f32 = lambda a: jnp.asarray(a[None], jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = ref.kda_recurrence(f32(q), f32(k), f32(v), f32(g), f32(beta))
    np.testing.assert_allclose(np.asarray(got[0]), _kda_numpy(q, k, v, g,
                                                              beta),
                               rtol=2e-5, atol=2e-5)
    # the convolution: four taps, causal, zero before the sequence
    x = rng.standard_normal((1, 6, 3)).astype(np.float32)
    w = rng.standard_normal((3, 4)).astype(np.float32)
    want = np.zeros((6, 3), np.float32)
    for t in range(6):
        for j in range(4):
            if t - 3 + j >= 0:
                want[t] += w[:, j] * x[0, t - 3 + j]
    np.testing.assert_allclose(np.asarray(ref.short_conv(
        jnp.asarray(x), jnp.asarray(w)))[0], want, rtol=1e-5, atol=1e-6)


def test_the_state_reader_on_recorded_spans():
    read = manifest.load_reader("state_trace_roofline").read
    span = lambda t0, **attrs: {"name": "serving.decode", "t0": t0,
                                "t1": t0 + 0.01, "attrs": attrs}
    rec = {"t_open": 0.0, "t_close": 10.0, "spans": [
        span(1.0, state_bytes=300, kv_bytes=100),
        span(2.0, state_bytes=100, kv_bytes=300),
        span(11.0, state_bytes=900, kv_bytes=0),       # outside the window
        # a lone piece is no step that decoded; one that carried the
        # decode rows is, with its own row's state and theirs
        {"name": "serving.prefill", "t0": 3.0, "t1": 3.1,
         "attrs": {"state_bytes": 50, "kv_bytes": 0, "decode_slots": 0}},
        {"name": "serving.prefill", "t0": 4.0, "t1": 4.1,
         "attrs": {"state_bytes": 500, "kv_bytes": 100,
                   "decode_slots": 4}}]}
    assert read(rec, "state_walk_share") == 100.0 * 900 / 1400
    # a program whose spans lack the attribute, or no spans: nothing
    assert read({"t_open": 0.0, "t_close": 10.0, "spans": [
        span(1.0, kv_bytes=5)]}, "state_walk_share") is None
    assert read({}, "state_walk_share") is None
    # no trace: the kernels' shares read nothing, and do not raise
    assert read(rec, "kda_step", op="^%?ling_kda_step") is None
    assert read(rec, "kda_chunk", op="^%?ling_kda_chunk") is None
    # a traced stretch: 2 pieces of 1000 and 24 tokens, the scan's
    # operations 2 ms, against the costs' own count
    m = _model()
    costs = manifest.family_of(m).costs
    peak = peaks.PEAKS["v5e"]
    traced = {"model": m, "peak": peak, "trace_span": (0.0, 5.0),
              "trace": {"op_events": [("%ling_kda_chunk.1 = x", 0, 1000000),
                                      ("%ling_kda_chunk.2 = x", 5, 1000000),
                                      ("%gmm = x", 9, 77)],
                        "module_events": [], "chips": 1},
              "spans": [{"name": "serving.prefill", "t0": 1.0, "t1": 1.1,
                         "attrs": {"tokens": [1000], "start": [0],
                                   "scan_tokens": 6000}},
                        {"name": "serving.prefill", "t0": 2.0, "t1": 2.1,
                         "attrs": {"tokens": [24], "start": [1000],
                                   "scan_tokens": 144}}]}
    f, b = costs.kda_chunk_cost(m, [6000, 144])
    want = 100.0 * max(f / peak.flops, b / peak.hbm_bw) / 2e-3
    assert read(traced, "kda_chunk", op="^%?ling_kda_chunk") == \
        pytest.approx(want)
    # pieces of a program that counts no scan (the parent's): nothing
    for sp in traced["spans"]:
        del sp["attrs"]["scan_tokens"]
    assert read(traced, "kda_chunk", op="^%?ling_kda_chunk") is None


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
    """Prefill in pieces, decode through the latent cache and the state
    advanced in place, the expert share's counts, the state's spans and
    counters, the reference's verdict: the harness's own path."""
    man = _rehearsal_root(tmp_path)
    # a window of four seconds and no count of requests held: what a
    # loaded machine finishes in two is too few to hold anything on
    out = run.measure(man, tiny.args(CELL, seed=2**31 + 40, seconds=4.0,
                                     trace=trace), jax.devices()[:1])
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
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
            "ds.routed_here_share", "ling.state_walk_share",
            "offline.readback_wait_ms_per_step",
            "offline.prefill_build_ms_per_wave", "offline.step_telemetry_ms",
            "offline.frontdoor_route_ms_per_step"}
    assert want <= set(got), want - set(got)
    assert not any("roofline" in n or "dev_ms" in n for n in got)
    # one MLA layer's row: 128 + 16 entries padded to 256 lanes, bf16
    assert got["offline.kv_bytes_per_token"]["value"] == 256 * 2
    # two KDA layers: 4 heads of a 32 x 32 float32 matrix, and three
    # inputs of 3 x 128 channels in bf16
    assert got["offline.state_bytes_per_slot"]["value"] == 2 * (
        4 * 32 * 32 * 4 + 3 * 3 * 128 * 2)
    # a share of two groups of four: about half of the routed pairs
    assert 0.2 < got["ds.routed_here_share"]["value"] < 0.8
    assert 0 < got["ling.state_walk_share"]["value"] < 100
    assert 0 < got["offline.experts_hit_share"]["value"] <= 100
    spans = [s for s in get_tracer().spans() if s.name == "serving.prefill"]
    carried = [s.attrs["state_in"] for s in spans if "state_in" in s.attrs]
    assert any(carried) and not all(carried)
    # real tokens x the two KDA layers; the row's state and the rows' that
    # decode with it
    assert all(s.attrs["scan_tokens"] == 2 * s.attrs["tokens"][0]
               for s in spans if "scan_tokens" in s.attrs)
    per_slot = int(got["offline.state_bytes_per_slot"]["value"])
    assert all(s.attrs["state_bytes"] == per_slot * (
        1 + s.attrs.get("decode_slots", 0)) for s in spans
        if "state_bytes" in s.attrs)
