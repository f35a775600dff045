"""The manifest and its data files: the contract's checks, and that a
cell, a configuration and a per-layer metric are added as files and
entries alone."""
import json
import os

import pytest

import bench_tiny as tiny
from benchmark import manifest


def test_manifest_and_data_files_validate():
    man = manifest.Manifest()
    man.validate()
    doc = man.doc
    assert os.path.getsize(os.path.join(man.root, "BENCHMARK.json")) < 65536
    assert doc["command"][:2] == ["python3", "benchmark/run.py"]
    # the contract's rule, not a count of today's cells: a quarter of the
    # cells, rounded down, may take four chips, and one always may
    assert man.workload("pretrain-4k-mesh4")["chips"] == 4
    assert sum(w["chips"] == 4 for w in doc["workloads"]) <= max(
        1, len(doc["workloads"]) // 4)
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert len(m["unit"]) <= 16 and " " not in m["unit"]
    for m in doc["end_to_end"]:
        if m["name"] == "setup_s":
            assert m["bound"] <= 0.1


def test_every_moves_is_reported_by_the_same_cells():
    man = manifest.Manifest()
    for cell in man.workloads:
        e2e = {m["name"] for m in man.metrics_for(cell, "end_to_end")}
        for m in man.metrics_for(cell, "per_layer"):
            assert m["moves"] in e2e, (cell, m["name"])


def test_layer_names_are_few_and_on_one_line():
    """By name, not by count: a layer is a module of the program or a part
    of the benchmark, and a configuration that brings a module may bring
    its layer. ``PERF.md`` section 3 lists them."""
    layers = {m["layer"] for m in manifest.Manifest().doc["per_layer"]}
    assert {"scheduler step serving/engine.py",
            "KV manager serving/engine.py", "device",
            "trainer models/llama.py"} <= layers
    assert all("\n" not in x and len(x) <= 200 for x in layers)


@pytest.mark.parametrize("breakage", [
    lambda d: d["end_to_end"][0].update(bound=0.2),
    lambda d: d["per_layer"][0].update(moves="no_such_metric"),
    lambda d: d["workloads"].append(dict(d["workloads"][0], name="again")),
    lambda d: d["per_layer"][0].update(why="a key the contract lacks"),
    lambda d: d.update(run_seconds=52),
])
def test_validate_refuses(tmp_path, breakage):
    man = tiny.make_root(str(tmp_path))
    doc = man.doc
    breakage(doc)
    json.dump(doc, open(tmp_path / "BENCHMARK.json", "w"))
    with pytest.raises(ValueError):
        manifest.Manifest(root=str(tmp_path)).validate()


def test_cell_config_metric_and_reader_added_as_data_only(tmp_path):
    """A later PR's cell: new files and new entries, no file edited."""
    man = tiny.make_root(str(tmp_path))
    before = {}
    for dirpath, _d, files in os.walk(tmp_path / "benchmark"):
        for f in files:
            p = os.path.join(dirpath, f)
            before[p] = open(p, "rb").read()
    data = tmp_path / "benchmark"
    cfg = json.load(open(data / "configs" / "mistral-7b-v0.3-serve.json"))
    cfg["num_hidden_layers"] = 1
    json.dump(cfg, open(data / "configs" / "later-model.json", "w"))
    tr = json.load(open(data / "traffic" / "chat-steady.json"))
    tr["rate_per_s"] = 3.0
    json.dump(tr, open(data / "traffic" / "later-mix.json", "w"))
    json.dump({"reader": "later_reader", "args": {"times": 2}},
              open(data / "metrics" / "later.metric.json", "w"))
    os.makedirs(data / "readers")
    (data / "readers" / "later_reader.py").write_text(
        "def read(rec, times):\n    return times * rec['attempted']\n")
    json.dump(json.load(open(data / "limits" / "chat-steady.json")),
              open(data / "limits" / "later-cell.json", "w"))
    doc = man.doc
    doc["configs"].append({
        "name": "later-model", "source": "https://example.org/paper",
        "file": "benchmark/configs/later-model.json",
        "reduced": ["num_hidden_layers"], "why": "shown by a test"})
    doc["workloads"] += [
        {"name": f"later-cell{i}", "config": "later-model",
         "traffic": "later-mix" if i == "" else "chat-steady", "chips": 1,
         "why": "shown by a test"} for i in ("",)]
    doc["per_layer"].append({
        "name": "later.metric", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "device",
        "moves": "setup_s", "workloads": ["later-cell"]})
    # the new cell reports what every cell reports, and one metric more
    for m in doc["end_to_end"]:
        if m["name"] == "itl_p50_ms":
            m["workloads"] = m["workloads"] + ["later-cell"]
    json.dump(doc, open(tmp_path / "BENCHMARK.json", "w"))
    man2 = manifest.Manifest(root=str(tmp_path))
    man2.validate()
    rec = {"attempted": 21}
    got = manifest.read_metrics(man2, "later-cell", "per_layer", rec)
    assert got == {"later.metric": {"value": 42.0, "unit": "count"}}
    for p, content in before.items():
        assert open(p, "rb").read() == content, f"{p} was edited"
