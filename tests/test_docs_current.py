"""The front page against the tree and the benchmark: a document that names
a file names one the checkout holds, README's table lists the cells
``BENCHMARK.json`` declares, and ``docs/served_models.md`` names every family
the benchmark serves. Plain file reads."""
import glob
import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "*.md")))

_DIRS = ("paddle_tpu/", "benchmark/", "tests/", "tests_tpu/", "tools/",
         "examples/", "docs/", "csrc/")
_RECORD = re.compile(r"[A-Z][A-Z0-9_]*(_r\d+)?\.(md|json|jsonl)$")


def _read(rel):
    with open(os.path.join(REPO, rel)) as f:
        return f.read()


def _named_paths(text):
    """Backticked tokens that name a path under a top-level directory or an
    upper-case record in the root, without a ``:line`` / ``::test`` suffix."""
    for token in re.findall(r"`([^`\n]+)`", text):
        token = token.split()[0].split(":")[0]
        if any(c in token for c in "*<>{"):
            continue
        if token.startswith(_DIRS) or _RECORD.match(token):
            yield token


@pytest.mark.parametrize("doc", DOCS)
def test_named_paths_exist(doc):
    missing = sorted({p for p in _named_paths(_read(doc))
                      if not os.path.exists(os.path.join(REPO, p))})
    assert not missing, f"{doc} names what is not in the checkout: {missing}"


def test_readme_lists_every_cell():
    section = _read("README.md").split("## What is measured")[1]
    rows = re.findall(r"^\| `([^`]+)` \|", section.split("\n## ")[0],
                      flags=re.M)
    cells = [w["name"]
             for w in json.loads(_read("BENCHMARK.json"))["workloads"]]
    assert sorted(rows) == sorted(cells)


def test_served_models_doc_names_every_family():
    doc = _read("docs/served_models.md")
    families = [os.path.basename(p)[:-3] for p in
                glob.glob(os.path.join(REPO, "benchmark", "families", "*.py"))
                if not p.endswith("_costs.py")]
    assert families and not [f for f in families if f not in doc]
