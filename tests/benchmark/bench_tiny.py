"""A throw-away copy of the benchmark's data with tiny cells, for the CPU
rehearsals: the same harness, generators, readers and references, at sizes
a test can hold. Nothing here is measured."""
from __future__ import annotations

import copy
import json
import os
import shutil
import types

from benchmark import manifest

TINY_SERVE = {"max_slots": 4, "block_size": 8, "max_model_len": 128,
              "prompt_buckets": [16, 32, 64], "max_queue": 64}
TINY_LEN = {"prompt": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                       "min": 8, "max": 64},
            "output": {"dist": "lognormal", "median": 8, "sigma": 0.4,
                       "min": 4, "max": 16}}
LOOSE = 1e9     # the rehearsals check the plumbing, not the precision


def __getattr__(name):
    """``TINY_MODEL``: the benchmark's first configuration at the sizes its
    family shrinks it to, with the family's name."""
    if name != "TINY_MODEL":
        raise AttributeError(name)
    man = manifest.Manifest()
    doc = man.config(man.doc["configs"][0]["name"])
    return {"family": doc["family"], **manifest.family_of(doc).tiny(doc)}


def make_root(tmp: str, limits=None) -> manifest.Manifest:
    """``tmp`` becomes a checkout's worth of benchmark data: the real files
    copied, every configuration shrunk in place as its family says, every
    traffic mix as below. Files that ``tmp`` already holds under
    ``benchmark/`` (a family, a configuration of it) stay."""
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), tmp)
    data = os.path.join(tmp, "benchmark")
    for sub in ("configs", "traffic", "metrics", "limits"):
        shutil.copytree(os.path.join(manifest.HERE, sub),
                        os.path.join(data, sub), dirs_exist_ok=True)
    for name in os.listdir(os.path.join(data, "configs")):
        path = os.path.join(data, "configs", name)
        doc = json.load(open(path))
        doc.update(manifest.load_family(doc["family"], data).tiny(doc))
        if "serve" in doc:
            doc["serve"].update(TINY_SERVE)
        json.dump(doc, open(path, "w"))
    for name in os.listdir(os.path.join(data, "traffic")):
        path = os.path.join(data, "traffic", name)
        doc = json.load(open(path))
        if "prompt" in doc:
            doc.update(copy.deepcopy(TINY_LEN))
            doc.update(rate_per_s=6.0, lead_in_s=0.5, clients=8, epoch=16)
        else:
            doc.update(batch=4, seq=64)
        json.dump(doc, open(path, "w"))
    for name in os.listdir(os.path.join(data, "limits")):
        path = os.path.join(data, "limits", name)
        doc = json.load(open(path))
        for k, v in doc.items():
            if k != "stream_faults":
                v["limit"] = (limits or {}).get(k, LOOSE)
        json.dump(doc, open(path, "w"))
    return manifest.Manifest(root=tmp)


def args(workload: str, seed: int = 3, seconds: float = 2.0, trace: int = 0):
    return types.SimpleNamespace(workload=workload, seed=seed,
                                 seconds=seconds, trace=trace)
