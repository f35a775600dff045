"""One name for one reading (PR 39): what each cell reports, by name; no
two names of one end-to-end metric with the same reader and arguments; a
file for every name and none besides; a new served family that brings four
names of its own and appends its cell to the shared ones; and the decode
load of a traced stretch in which pieces carried the decode rows. Nothing
here counts the file's entries or says where in a list they lie, and
nothing holds a list of cells or a cell's names CLOSED: a later PR appends
its cell to the shared names and gives a cell here a name of its own, and
every check of the entries holds on that file too: one test below makes
such a file and asks them all of it, the other files' checks too."""
import json
import os
import shutil

import jax
import pytest

import bench_tiny as tiny
from benchmark import manifest, peaks, readers_util, run
from test_bench_family import HERE, RENAMED
from test_bench_longdoc import M as DS_M
from test_bench_mellum import M as MEL_M

OFF = ["batch-offline", "longdoc-offline", "rag-offline", "repo-offline"]
MOE = OFF[1:]
# what the two latency cells share, and what only chat-steady has
LATENCY = {
    "gen_late_p99_ms", "http_non200_share", "queue_wait_p90_ms", "shed_share",
    "sched_host_ms_per_step", "decode_slots_mean", "prefill_row_fill",
    "kv_used_peak", "preemptions", "recompiles_in_window",
    "decode_dev_ms_per_step", "prefill_dev_ms_per_ktok",
    "decode_hbm_roofline", "prefill_flops_roofline", "ragged_walk_roofline",
    "flash_roofline", "device_idle", "hbm_peak_gb", "step_host_ms",
    "readback_wait_ms_per_step", "prefill_build_ms_per_wave",
    "step_telemetry_ms", "frontdoor_route_ms_per_step",
    "frontdoor_emit_to_write_p99_ms", "engine_ttft_p95_ms"}
CHAT_ONLY = {"chat-steady.ttft_p50_ms", "chat.ttft_p95_ms"}
# every tokens_per_s serving cell: the same reader, the same arguments.
# ALL_FOUR had a copy a cell; HOST only batch-offline's, which the other
# three gain; step_host_ms all but longdoc-offline's
ALL_FOUR = ("device_idle", "hbm_peak_gb", "http_non200_share",
            "sched_host_ms_per_step", "decode_slots_mean", "kv_used_peak",
            "preemptions", "recompiles_in_window")
HOST = ("readback_wait_ms_per_step", "prefill_build_ms_per_wave",
        "step_telemetry_ms", "frontdoor_route_ms_per_step")
SHARED = {"offline." + n for n in ALL_FOUR + HOST + ("step_host_ms",)} | {
    "device_starved_ms_per_step", "pipeline_drains_per_step"}
# those with an expert layer and prompts in pieces
EXPERT = ("expert_gmm_roofline", "expert_rows_per_step", "experts_hit_share",
          "expert_load_max_over_mean", "kv_bytes_per_token")
EXPERTS = {"offline." + n for n in EXPERT + ("piece_row_fill",)} | {
    "piece_lone_share"}
PROGRAM = ("decode_dev_ms_per_step", "prefill_dev_ms_per_ktok",
           "decode_hbm_roofline", "prefill_flops_roofline")


def _own(prefix, *more):
    return {prefix + n for n in PROGRAM + more}


CELLS = {
    "chat-steady": LATENCY | CHAT_ONLY,
    "doc-prefill": LATENCY,
    "pretrain-4k-mesh4": {"train." + n for n in (
        "step_ms_p50", "mfu", "input_wait_ms_per_step",
        "collective_exposed_share", "flash_roofline", "device_idle",
        "hbm_peak_gb")},
    "batch-offline": SHARED | _own(
        "batch.", "ragged_walk_roofline", "flash_roofline",
        "prefill_row_fill"),
    "longdoc-offline": SHARED | EXPERTS | _own(
        "ds.", "latent_walk_roofline", "mla_prefill_attn_roofline",
        "routed_here_share"),
    "rag-offline": SHARED | EXPERTS | {"offline.state_bytes_per_slot"} | _own(
        "lfm.", "ragged_walk_roofline", "flash_roofline",
        "state_carried_share"),
    "repo-offline": SHARED | EXPERTS | _own(
        "mel.", "walk_full_roofline", "walk_window_roofline",
        "flash_roofline", "window_walk_share",
        "window_blocks_recycled_per_step"),
}
# the ledger's older lines keep these names: new name -> its old names
MERGED = {"offline." + n: [p + n for p in ("batch.", "ds.", "lfm.", "mel.")]
          for n in ALL_FOUR}
MERGED.update({"offline." + n: ["batch." + n] for n in HOST})
MERGED.update({"offline." + n: [p + n for p in ("ds.", "lfm.", "mel.")]
               for n in EXPERT})
MERGED.update({
    "offline.step_host_ms": [p + "step_host_ms"
                             for p in ("batch.", "lfm.", "mel.")],
    "offline.piece_row_fill": [p + "prefill_row_fill"
                               for p in ("ds.", "lfm.", "mel.")],
    "offline.state_bytes_per_slot": ["lfm.state_bytes_per_slot"]})
RETIRED = ("mel.window_bytes_per_slot", "idle_unattributed_share",
           "batch.idle_unattributed_share")
# every name PR 39 leaves in the file: a cell is held to these, a name
# that a later PR brings is that PR's to hold
KNOWN = set().union(*CELLS.values())


def _group(new):
    """The cells a merged name lists at the least."""
    if new == "offline.state_bytes_per_slot":
        return ["rag-offline"]
    return MOE if new in EXPERTS else OFF


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_cell_reports_exactly_its_names(cell):
    """Of the names PR 39 knows, ``cell`` reports exactly its own, and
    whatever it reports moves a metric that it reports."""
    man = manifest.Manifest()
    assert set(CELLS) <= set(man.workloads)
    mine = man.metrics_for(cell, "per_layer")
    assert {m["name"] for m in mine} & KNOWN == CELLS[cell]
    reports = {m["name"] for m in man.metrics_for(cell, "end_to_end")}
    assert all(m["moves"] in reports for m in mine)


def test_no_name_is_a_copy_of_another():
    """Two names that move the same end-to-end metric and read the same
    thing are one reading: the cells belong in one name's ``workloads``."""
    man = manifest.Manifest()
    man.validate()
    seen = {}
    for m in man.doc["per_layer"]:
        spec = man.metric_spec(m["name"])
        key = (m["moves"], spec["reader"],
               json.dumps(spec.get("args", {}), sort_keys=True))
        assert key not in seen, (m["name"], seen[key])
        seen[key] = m["name"]


def test_a_file_for_every_name_and_no_other():
    man = manifest.Manifest()
    names = {m["name"] for m in man.doc["end_to_end"] + man.doc["per_layer"]}
    files = os.listdir(os.path.join(man.data_dir, "metrics"))
    assert sorted(files) == sorted(n + ".json" for n in names)


def test_the_merged_names_cover_their_copies_cells():
    """Every cell that read a copy reads the merged name, the copies and
    the retired names are gone, and a merged name lists every cell of its
    group at the least: all four, or the three with an expert layer."""
    man = manifest.Manifest()
    by = {m["name"]: m for m in man.doc["per_layer"]}
    cell_of = {"batch": "batch-offline", "ds": "longdoc-offline",
               "lfm": "rag-offline", "mel": "repo-offline"}
    for new, olds in MERGED.items():
        assert not set(olds) & set(by)
        assert {cell_of[o.split(".")[0]] for o in olds} <= set(
            _group(new)) <= set(by[new]["workloads"])
        assert by[new]["moves"] == "tokens_per_s"
    assert not set(RETIRED) & set(by)
    # the reader that the retired shares took stays, with its test
    assert manifest.load_reader("trace_idle_named").read({}, names=[]) is None


def _add_renamed_offline(tmp):
    """A root in which a later PR has brought a ``tokens_per_s`` cell of a
    new family: files, its cell appended to the shared names' lists, and
    four names of its own (the program readings, whose arguments name the
    family's kernels)."""
    data = os.path.join(tmp, "benchmark")
    shutil.copytree(os.path.join(HERE, "data", "renamed"), data)
    real = manifest.Manifest()
    serve = real.config("mistral-7b-v0.3-serve")["serve"]
    os.makedirs(os.path.join(data, "configs"))
    json.dump(dict(RENAMED, serve=serve),
              open(os.path.join(data, "configs", "renamed-serve.json"), "w"))
    os.makedirs(os.path.join(data, "limits"))
    shutil.copy(os.path.join(manifest.HERE, "limits", "batch-offline.json"),
                os.path.join(data, "limits", "renamed-batch.json"))
    man = tiny.make_root(tmp)
    doc, cell = man.doc, "renamed-batch"
    doc["configs"].append({
        "name": "renamed-serve", "source": "https://example.org/renamed",
        "file": "benchmark/configs/renamed-serve.json",
        "reduced": ["num_hidden_layers"], "why": "shown by a test"})
    doc["workloads"].append({
        "name": cell, "config": "renamed-serve", "traffic": "batch-offline",
        "chips": 1, "why": "shown by a test"})
    appended = []
    for m in doc["end_to_end"] + doc["per_layer"]:
        # whatever lists all four today: by membership, so that this
        # also finds them once a later PR has appended a cell of its own
        if set(OFF) <= set(m.get("workloads", [])):
            m["workloads"] = m["workloads"] + [cell]
            appended.append(m["name"])
    by = {m["name"]: m for m in doc["per_layer"]}
    for n in PROGRAM:
        doc["per_layer"].append(dict(by["batch." + n], name="rn." + n,
                                     workloads=[cell]))
        spec = man.metric_spec("batch." + n)
        args = json.loads(json.dumps(spec["args"]).replace(
            "|jit__unknown", ""))
        json.dump(dict(spec, args=args), open(os.path.join(
            data, "metrics", "rn." + n + ".json"), "w"))
    json.dump(doc, open(os.path.join(tmp, "BENCHMARK.json"), "w"))
    return manifest.Manifest(root=tmp), appended


def test_a_new_offline_cell_appends_itself_and_brings_four_names(tmp_path):
    man, appended = _add_renamed_offline(str(tmp_path))
    man.validate()
    assert {"tokens_per_s"} | SHARED <= set(appended)
    own = {"rn." + n for n in PROGRAM}
    mine = {m["name"] for m in man.metrics_for("renamed-batch", "per_layer")}
    assert mine & (KNOWN | own) == SHARED | own
    assert {m["name"] for m in man.doc["per_layer"]} - own == {
        m["name"] for m in manifest.Manifest().doc["per_layer"]}
    # and the copies rule still holds with the four beside batch-offline's
    specs = [json.dumps(man.metric_spec("rn." + n), sort_keys=True)
             for n in PROGRAM]
    assert not set(specs) & {
        json.dumps(man.metric_spec("batch." + n), sort_keys=True)
        for n in PROGRAM}
    out = run.measure(man, tiny.args("renamed-batch", seed=2**31 + 39,
                                     trace=1), jax.devices()[:1])
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 5
    # every shared name that does not read the device, from the new
    # family's engine: nothing in them belongs to a family
    device = {"offline.device_idle", "offline.hbm_peak_gb"}
    assert SHARED - device <= set(out["metrics"]) <= mine


def test_every_file_s_checks_of_the_entries_hold_once_a_cell_is_added(
        tmp_path, monkeypatch):
    """What the other files under ``tests/benchmark/`` ask of the entries,
    asked of the root with the added cell: ``Manifest()`` opens that root
    here. The PR that adds a cell as ``benchmark/README.md`` says may not
    mend a test under the benchmark's paths, so none may need it."""
    import test_bench_lfm2
    import test_bench_longdoc
    import test_bench_manifest
    import test_bench_mellum
    import test_bench_phase_metrics
    import test_bench_starved
    man, _ = _add_renamed_offline(str(tmp_path))
    # and further than that cell goes: as a second cell of rag-offline's
    # family would, it joins every name that rag-offline lists, and a
    # later PR's counter takes a name in the four cells here
    doc, cell = man.doc, "renamed-batch"
    for m in doc["per_layer"]:
        if "rag-offline" in m["workloads"] and cell not in m["workloads"]:
            m["workloads"] = m["workloads"] + [cell]
    doc["per_layer"].append(dict(
        next(m for m in doc["per_layer"]
             if m["name"] == "pipeline_drains_per_step"),
        name="later.counted_ends_per_step", workloads=OFF + [cell]))
    json.dump({"reader": "counter", "args": {
        "name": "serving_counted_finishes_total",
        "per": {"name": "serving_step_seconds", "part": "count"}}},
        open(os.path.join(man.data_dir, "metrics",
                          "later.counted_ends_per_step.json"), "w"))
    json.dump(doc, open(os.path.join(man.root, "BENCHMARK.json"), "w"))
    real = manifest.Manifest
    monkeypatch.setattr(manifest, "Manifest",
                        lambda root=man.root, **kw: real(root, **kw))
    assert "later.counted_ends_per_step" in {
        m["name"] for m in manifest.Manifest().metrics_for(
            "rag-offline", "per_layer")}
    assert cell in next(m for m in manifest.Manifest().doc["per_layer"]
                        if m["name"] == "lfm.state_carried_share")["workloads"]
    test_bench_manifest.test_manifest_and_data_files_validate()
    test_bench_manifest.test_every_moves_is_reported_by_the_same_cells()
    test_bench_manifest.test_layer_names_are_few_and_on_one_line()
    test_bench_starved.test_the_three_entries_by_name_and_membership()
    test_bench_phase_metrics.test_the_new_entries_and_files()
    test_bench_lfm2.test_the_cells_the_configuration_and_the_metrics()
    test_bench_longdoc.test_the_cell_its_configuration_and_its_metrics()
    test_bench_mellum.test_the_cells_the_configuration_and_the_metrics()
    for known in CELLS:
        test_a_cell_reports_exactly_its_names(known)
    test_no_name_is_a_copy_of_another()
    test_a_file_for_every_name_and_no_other()
    test_the_merged_names_cover_their_copies_cells()


def _span(name, t, **attrs):
    return {"name": name, "t0": t, "t1": t + 0.01, "attrs": attrs}


def _mixed_record(model, pieces=True):
    """A traced stretch [105, 106] with two pure decode steps and, with
    ``pieces``, two steps whose decode rows rode a piece, one lone piece
    and a step past the stretch; two slots, a token each a step."""
    spans = [_span("serving.decode", 105.1, expert_rows=4, experts_hit=3),
             _span("serving.decode", 105.6, expert_rows=4, experts_hit=3),
             _span("serving.decode", 106.5, expert_rows=9, experts_hit=9)]
    times = [5.15, 5.65]
    if pieces:
        spans += [
            _span("serving.prefill", 105.3, decode_slots=2, expert_rows=40,
                  experts_hit=3, batch=1, bucket=32),
            _span("serving.prefill", 105.8, decode_slots=2, expert_rows=40,
                  experts_hit=3, batch=1, bucket=32),
            _span("serving.prefill", 105.9, decode_slots=0, batch=1,
                  bucket=32),
            _span("serving.prefill", 105.95, batch=1, bucket=32)]
        times = [5.15, 5.35, 5.65, 5.85]
    stream = lambda n: {"prompt_len": n, "t_tokens": [4.0] + times + [7.0]}
    return {"model": model, "peak": peaks.Peak(1e8, 16e9, 1e7),
            "t_open": 100.0, "t_close": 110.0, "trace": {"any": 1},
            "trace_span": (105.0, 106.0), "spans": spans,
            "client": {"streams": [stream(5), stream(99)]}}


def test_the_decode_load_is_a_mean_over_every_step_that_decoded(monkeypatch):
    """The client's tokens come from every step that decoded; the decode
    program ran in the pure steps only. A step's slots and cache rows are
    the tokens over ALL those steps (before PR 39: over the pure ones, so
    two slots read as four here), the program's work that load times the
    pure steps, a walk kernel's the load times all of them."""
    llama = tiny.TINY_MODEL
    rec = _mixed_record(llama)
    assert readers_util.decoding_steps(rec, 105.0, 106.0) == (2, 4)
    # contexts 6..9 and 100..103 over four steps of two slots
    live = (6 + 7 + 8 + 9 + 100 + 101 + 102 + 103) / 4
    assert readers_util.traced_decode_load(rec) == (2, 2.0, live, 4)
    alone = _mixed_record(llama, pieces=False)
    assert readers_util.decoding_steps(alone, 105.0, 106.0) == (2, 2)
    assert readers_util.traced_decode_load(alone) == (
        2, 2.0, (6 + 7 + 100 + 101) / 2, 2)

    monkeypatch.setattr("benchmark.trace.op_seconds",
                        lambda red, op, lacks, runs: 2.0)
    monkeypatch.setattr("benchmark.trace.module_runs",
                        lambda red, **kw: [(0, 1.5e9), (0, 1.5e9)])
    share = lambda f, b, s: 100 * max(f / 1e8, b / 1e7) / s
    prog = {"pattern": "paged_decode"}

    # the dense family: the program's work over 2 steps, the walk's over 4
    read = manifest.load_reader("trace_roofline").read
    costs = manifest.load_family("llama").costs
    f, b = costs.decode_step_cost(llama, 2.0, live)
    assert read(rec, "decode", program=prog) == pytest.approx(
        share(2 * f, 2 * b, 3.0))
    f, b = costs.decode_attention_cost(llama, 2.0, live)
    assert read(rec, "ragged_walk", op="x") == pytest.approx(
        share(4 * f, 4 * b, 2.0))
    # without such pieces: as before, to the digit
    f, b = costs.decode_step_cost(llama, 4 / 2, (6 + 7 + 100 + 101) / 2)
    assert read(alone, "decode", program=prog) == share(2 * f, 2 * b, 3.0)

    # sparse experts: the pure steps' own expert counts, a step's load
    read = manifest.load_reader("moe_trace_roofline").read
    m = dict(DS_M, family="deepseek_v2")
    costs = manifest.load_family("deepseek_v2").costs
    f, b = costs.decode_step_cost(m, 2.0, live, expert_rows=4, experts_hit=3)
    assert read(dict(rec, model=m), "decode", program=prog) == pytest.approx(
        share(2 * f, 2 * b, 3.0))
    f, b = costs.decode_attention_cost(m, 2.0, live)
    assert read(dict(rec, model=m), "latent_walk", op="x") == pytest.approx(
        share(4 * f, 4 * b, 2.0))

    # window layers beside full ones: each kind's visible tokens a step
    read = manifest.load_reader("window_walk").read
    m = dict(MEL_M, family="mellum")
    costs = manifest.load_family("mellum").costs
    f, b = costs.decode_step_cost(m, 2.0, live, expert_rows=4, experts_hit=3,
                                  window_tokens=8 * m["sliding_window"] / 4)
    assert read(dict(rec, model=m), "decode", program=prog) == pytest.approx(
        share(2 * f, 2 * b, 3.0))
    # a walk is charged every token's visible rows, whatever program
    f, b = costs.walk_cost(m, "full", live * 4)
    assert read(dict(rec, model=m), "walk", kind="full",
                op="x") == pytest.approx(share(f, b, 2.0))
