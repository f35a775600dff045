"""The three per-layer metrics that read the engine's starved-time ledger,
its drains and its lone pieces (PR 37): the manifest's entries by NAME and
membership, never by where they lie in a list or how many there are, and
each data file's arguments resolved against snapshots of the program's own
registry, taken around increments of the engine's own instruments."""
import pytest

import paddle_tpu.observability as obs
from benchmark import manifest
from paddle_tpu.observability.catalog import CATALOG
from paddle_tpu.serving import engine as engine_mod

LAYER = "scheduler step serving/engine.py"
FOUR = {"batch-offline", "longdoc-offline", "rag-offline", "repo-offline"}
ENTRIES = {
    # name: (unit, the cells that report it)
    "device_starved_ms_per_step": ("ms", FOUR),
    "pipeline_drains_per_step": ("ratio", FOUR),
    # the dense family's engine does not piggyback: it never counts a piece
    "piece_lone_share": ("%", FOUR - {"batch-offline"}),
}


def test_the_three_entries_by_name_and_membership():
    man = manifest.Manifest()
    man.validate()
    by = {m["name"]: m for m in man.doc["per_layer"]}
    for name, (unit, cells) in ENTRIES.items():
        m = by[name]
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == (unit, "lower", "program_counter", LAYER,
                                "tokens_per_s"), m
        # at the least: a later cell appends itself (benchmark/README.md)
        assert cells <= set(m["workloads"])
        assert not (FOUR - cells) & set(m["workloads"])
        # every cell that lists it reports the metric it moves, and lists
        # it among its per-layer metrics
        for cell in cells:
            assert "tokens_per_s" in {
                e["name"] for e in man.metrics_for(cell, "end_to_end")}
            assert name in {
                e["name"] for e in man.metrics_for(cell, "per_layer")}
        assert man.metric_spec(name)["reader"] == "counter"
    # an existing layer's name, letter for letter
    assert LAYER in {m["layer"] for m in man.doc["per_layer"]
                     if m["name"] not in ENTRIES}
    # the latency cells get no name: their readings stand in PERF.md
    for cell in ("chat-steady", "doc-prefill"):
        assert not set(ENTRIES) & {
            e["name"] for e in man.metrics_for(cell, "per_layer")}


def _counters(spec):
    picks = [spec["args"]] + ([spec["args"]["per"]]
                              if "per" in spec["args"] else [])
    return [(p["name"], p.get("labels") or {}, p.get("part", "value"))
            for p in picks]


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_the_arguments_name_what_the_catalogue_holds(name):
    for counter, labels, part in _counters(
            manifest.Manifest().metric_spec(name)):
        kind, label_names, _help = CATALOG[counter]
        assert set(labels) <= set(label_names), (counter, labels)
        assert part == ("value" if kind == "counter" else part)
        assert part in ("value", "sum", "count")


def test_the_readers_resolve_against_the_registry_s_snapshots():
    obs.get_registry().reset()
    obs.enable()
    try:
        E = engine_mod
        # what the window already held when it opened
        E._M_STEP_SECONDS.observe(0.02)
        E._M_STARVED.inc(0.5, phase="serving.admit")
        E._M_DRAINS.inc(3, reason="may_finish")
        E._M_PREFILL_PROGRAMS.inc(5, carried="rows")
        rec = {"snap_open": obs.snapshot()}
        # the window: 8 steps, 6 ms starved in two phases, 2 drains for two
        # reasons, 10 pieces of which 3 carried nothing
        for _ in range(8):
            E._M_STEP_SECONDS.observe(0.025)
        E._M_STARVED.inc(0.004, phase="serving.admit")
        E._M_STARVED.inc(0.002, phase="serving.prefill_build")
        E._M_NO_WORK.inc(7.0)              # spare capacity: in no metric
        E._M_DRAINS.inc(reason="may_finish")
        E._M_DRAINS.inc(reason="no_active")
        E._M_PREFILL_PROGRAMS.inc(7, carried="rows")
        E._M_PREFILL_PROGRAMS.inc(3, carried="none")
        rec["snap_close"] = obs.snapshot()
    finally:
        obs.disable()
        obs.get_registry().reset()
    man = manifest.Manifest()
    got = {}
    for name in ENTRIES:
        spec = man.metric_spec(name)
        got[name] = manifest.load_reader(spec["reader"]).read(
            rec, **spec["args"])
    assert got["device_starved_ms_per_step"] == pytest.approx(6.0 / 8)
    assert got["pipeline_drains_per_step"] == pytest.approx(2 / 8)
    assert got["piece_lone_share"] == pytest.approx(30.0)
    # a program without the ledger (the parent's): the reader finds
    # nothing and says so, it does not raise
    bare = {"metrics": [m for m in rec["snap_close"]["metrics"]
                        if m["name"] == "serving_step_seconds"]}
    for name in ENTRIES:
        spec = man.metric_spec(name)
        assert manifest.load_reader(spec["reader"]).read(
            {"snap_open": bare, "snap_close": bare}, **spec["args"]) is None
