"""BENCHMARK.json and the data files it names, loaded and checked.

Everything that belongs to one configuration, one traffic mix or one metric
sits in a file of its own, found by the name in ``BENCHMARK.json``:
``configs/<config>.json`` (the path is the manifest's ``file``),
``traffic/<traffic>.json`` and ``metrics/<metric>.json`` under the
benchmark's directory. What belongs to one family of models (its weights,
its program config, its reference, its costs) sits in
``families/<family>.py``, found by the configuration's ``family`` as a
reader is found by a metric's ``reader``. A later PR adds a cell, a
configuration, a metric or a family by adding files and entries; nothing
here is edited for it.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os
import re
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
# what a family module gives the harness (families/llama.py states each)
FAMILY_MEMBERS = ("program_config", "engine_kwargs", "trainer", "layer_kind",
                  "make_layer", "make_top", "make_params", "reference",
                  "costs", "tiny")
DATA_DIR = "_data_dir"      # where a loaded configuration's data files lie


def _load(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


class Manifest:
    def __init__(self, root: str = ROOT, data_dir: Optional[str] = None):
        self.root = root
        self.doc = _load(os.path.join(root, "BENCHMARK.json"))
        self.data_dir = data_dir or os.path.join(root, self.doc["paths"][0])
        self.workloads = {w["name"]: w for w in self.doc["workloads"]}
        self.configs = {c["name"]: c for c in self.doc["configs"]}

    # -- files found by name ------------------------------------------------
    def workload(self, name: str) -> Dict:
        if name not in self.workloads:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                           f"{sorted(self.workloads)}")
        return self.workloads[name]

    def config(self, name: str) -> Dict:
        """The configuration's file, and (in memory only) where its data
        files lie, for ``family_of``."""
        doc = _load(os.path.join(self.root, self.configs[name]["file"]))
        doc[DATA_DIR] = self.data_dir
        return doc

    def traffic(self, name: str) -> Dict:
        return _load(os.path.join(self.data_dir, "traffic", name + ".json"))

    def metric_spec(self, name: str) -> Dict:
        return _load(os.path.join(self.data_dir, "metrics", name + ".json"))

    def metrics_for(self, cell: str, kind: str) -> List[Dict]:
        """The ``end_to_end`` or ``per_layer`` metrics that ``cell`` reports:
        those that list it, and those that list no cells."""
        return [m for m in self.doc[kind]
                if cell in m.get("workloads", [cell])]

    # -- checks, as far as the contract states them -------------------------
    def validate(self) -> None:
        d = self.doc
        keys = {"command", "paths", "run_seconds", "configs", "workloads",
                "end_to_end", "per_layer"}
        if set(d) != keys:
            raise ValueError(f"BENCHMARK.json keys {sorted(d)} != "
                             f"{sorted(keys)}")
        if not (isinstance(d["run_seconds"], int)
                and 1 <= d["run_seconds"] <= 51):
            raise ValueError("run_seconds must be a whole number in 1..51")
        for group in ("configs", "workloads", "end_to_end", "per_layer"):
            names = [e["name"] for e in d[group]]
            if len(set(names)) != len(names):
                raise ValueError(f"{group}: a name appears twice")
            for n in names:
                if not NAME.match(n):
                    raise ValueError(f"{group}: bad name {n!r}")
        for c in d["configs"]:
            if set(c) != {"name", "source", "file", "reduced", "why"}:
                raise ValueError(f"config {c['name']}: keys {sorted(c)}")
            if not any(c["file"].startswith(p + "/") for p in d["paths"]):
                raise ValueError(f"config {c['name']}: file outside paths")
            doc = self.config(c["name"])
            if sorted(doc.get("reduced", [])) != sorted(c["reduced"]):
                raise ValueError(f"config {c['name']}: 'reduced' differs "
                                 "between BENCHMARK.json and its file")
            if "family" not in doc:
                raise ValueError(f"config {c['name']}: its file names no "
                                 "'family'")
            family_of(doc)
        pairs = set()
        for w in d["workloads"]:
            if set(w) != {"name", "config", "traffic", "chips", "why"}:
                raise ValueError(f"workload {w['name']}: keys {sorted(w)}")
            if w["config"] not in self.configs or w["chips"] not in (1, 4):
                raise ValueError(f"workload {w['name']}: config or chips")
            if len(w["why"]) > 200 or (w["config"], w["traffic"]) in pairs:
                raise ValueError(f"workload {w['name']}: why too long, or "
                                 "the pair appears twice")
            pairs.add((w["config"], w["traffic"]))
            self.traffic(w["traffic"])
        if sum(w["chips"] == 4 for w in d["workloads"]) > max(
                1, len(d["workloads"]) // 4):
            raise ValueError("too many four-chip cells")
        e2e = {m["name"]: m for m in d["end_to_end"]}
        if "setup_s" not in e2e:
            raise ValueError("end_to_end lacks setup_s")
        for m in d["end_to_end"]:
            allowed = {"name", "unit", "better", "bound", "source",
                       "workloads"}
            if not (set(m) <= allowed and allowed - {"workloads"} <= set(m)):
                raise ValueError(f"metric {m['name']}: keys {sorted(m)}")
            if not (0 < m["bound"] <= 0.1) or m["source"] not in (
                    "host_clock", "device_trace"):
                raise ValueError(f"metric {m['name']}: bound or source")
        for m in d["per_layer"]:
            allowed = {"name", "unit", "better", "source", "layer", "moves",
                       "workloads"}
            if not (set(m) <= allowed and allowed - {"workloads"} <= set(m)):
                raise ValueError(f"metric {m['name']}: keys {sorted(m)}")
            if m["moves"] not in e2e or m["source"] not in SOURCES:
                raise ValueError(f"metric {m['name']}: moves or source")
        for m in d["end_to_end"] + d["per_layer"]:
            if not UNIT.match(m["unit"]) or m["better"] not in (
                    "lower", "higher"):
                raise ValueError(f"metric {m['name']}: unit or better")
            for w in m.get("workloads", []):
                if w not in self.workloads:
                    raise ValueError(f"metric {m['name']}: no cell {w!r}")
            spec = self.metric_spec(m["name"])
            load_reader(spec["reader"], self.data_dir)
        for w in self.workloads:
            mine = [m["name"] for m in self.metrics_for(w, "end_to_end")]
            if "setup_s" not in mine or len(mine) < 2:
                raise ValueError(f"cell {w}: needs setup_s and one more "
                                 "end-to-end metric")
            if not self.metrics_for(w, "per_layer"):
                raise ValueError(f"cell {w}: no per-layer metric")
            for m in self.metrics_for(w, "per_layer"):
                if m["moves"] not in mine:
                    raise ValueError(
                        f"{m['name']} moves {m['moves']}, which cell {w} "
                        "does not report")


def load_file(path: str):
    """The module in the file at ``path``, which need not lie on
    ``sys.path``: a family beside the data files loads its reference and
    its costs so."""
    if not os.path.exists(path):
        raise ValueError(f"no module at {path}")
    sub, stem = os.path.split(os.path.splitext(os.path.abspath(path))[0])
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{os.path.basename(sub)}_{stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _find(sub: str, name: str, data_dir: Optional[str]):
    """``<sub>/<name>.py``: the benchmark's own, or one beside the data
    files that a later PR added."""
    if not NAME.match(name):
        raise ValueError(f"bad name {name!r} for a module of {sub}/")
    own = os.path.join(HERE, sub, name + ".py")
    return load_file(own if os.path.exists(own) or not data_dir
                     else os.path.join(data_dir, sub, name + ".py"))


@functools.lru_cache(maxsize=None)
def load_reader(name: str, data_dir: str = None):
    """The reader module ``readers/<name>.py``."""
    return _find("readers", name, data_dir)


@functools.lru_cache(maxsize=None)
def load_family(name: str, data_dir: str = None):
    """The family module ``families/<name>.py``, with every member of the
    interface."""
    mod = _find("families", name, data_dir)
    lacks = [m for m in FAMILY_MEMBERS if not hasattr(mod, m)]
    if lacks:
        raise ValueError(f"family {name!r} ({mod.__file__}) lacks {lacks}")
    return mod


def family_of(model: Dict):
    """The family module of a configuration, as ``Manifest.config`` gave it
    or as a plain dict that names one of the benchmark's own families."""
    return load_family(model["family"], model.get(DATA_DIR))


def read_metrics(man: Manifest, cell: str, kind: str, record: Dict) -> Dict:
    """Every metric of ``kind`` that ``cell`` reports and whose reader finds
    something to read, as ``{name: {"value": v, "unit": u}}``."""
    out = {}
    for m in man.metrics_for(cell, kind):
        spec = man.metric_spec(m["name"])
        value = load_reader(spec["reader"], man.data_dir).read(
            record, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
