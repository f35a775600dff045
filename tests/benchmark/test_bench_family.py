"""A model family is data: a second family added to a temporary root by
new files alone (``data/renamed/``) is served by the same engine and comes
out ``correct``, and not with one term of its reference broken; a family
with two kinds of layer goes through the weights and the reference's
layer-by-layer pass at a static index; the llama family's weights are the
parent's bit for bit; ``validate`` refuses a configuration whose family is
missing, unknown or short of a member."""
import hashlib
import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny as tiny
from benchmark import correct, manifest, peaks, run, traffic, weights

HERE = os.path.dirname(os.path.abspath(__file__))
RENAMED = {"family": "renamed", "d_model": 4096, "d_ff": 14336,
           "n_heads": 32, "n_kv_heads": 8, "d_head": 128,
           "vocab_size": 32768, "rope_base": 1000000.0, "norm_eps": 1e-05,
           "num_hidden_layers": 16, "reduced": ["num_hidden_layers"],
           "kind": "serve"}


def _add_renamed(tmp, reference_edit=None):
    """A root whose ``benchmark/`` holds the ``renamed`` family, a
    configuration of it, a cell and its limits, as a later PR would bring
    them: new files, and new entries in ``BENCHMARK.json``."""
    data = os.path.join(tmp, "benchmark")
    shutil.copytree(os.path.join(HERE, "data", "renamed"), data)
    if reference_edit:
        path = os.path.join(data, "reference", "renamed_f32.py")
        text = open(path).read()
        assert reference_edit[0] in text
        open(path, "w").write(text.replace(*reference_edit))
    real = manifest.Manifest()
    serve = real.config("mistral-7b-v0.3-serve")["serve"]
    os.makedirs(os.path.join(data, "configs"))
    json.dump(dict(RENAMED, serve=serve),
              open(os.path.join(data, "configs", "renamed-serve.json"), "w"))
    os.makedirs(os.path.join(data, "limits"))
    shutil.copy(os.path.join(manifest.HERE, "limits", "chat-steady.json"),
                os.path.join(data, "limits", "renamed-chat.json"))
    before = {p: open(p, "rb").read() for p in _files(manifest.HERE)}
    man = tiny.make_root(tmp, limits={"logit_gap_max": 0.5,
                                      "logit_gap_mean": 0.1})
    assert {p: open(p, "rb").read() for p in _files(manifest.HERE)} == before
    doc = man.doc
    doc["configs"].append({
        "name": "renamed-serve", "source": "https://example.org/renamed",
        "file": "benchmark/configs/renamed-serve.json",
        "reduced": ["num_hidden_layers"], "why": "shown by a test"})
    doc["workloads"].append({
        "name": "renamed-chat", "config": "renamed-serve",
        "traffic": "chat-steady", "chips": 1, "why": "shown by a test"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "chat-steady" in m.get("workloads", []):
            m["workloads"] = m["workloads"] + ["renamed-chat"]
    json.dump(doc, open(os.path.join(tmp, "BENCHMARK.json"), "w"))
    man = manifest.Manifest(root=tmp)
    man.validate()
    return man


def _files(top):
    return [os.path.join(d, f) for d, _s, fs in os.walk(top) for f in fs
            if "__pycache__" not in d]


@pytest.mark.parametrize("reference_edit,want", [
    (None, True),
    (("rope((hn @ _f32(p[\"wq\"])).reshape(B, S, nq, d), m[\"rope_base\"])",
      "(hn @ _f32(p[\"wq\"])).reshape(B, S, nq, d)"), False),
], ids=["sound", "reference-without-rope-on-q"])
def test_a_family_added_by_files_alone_is_served_and_checked(
        tmp_path, reference_edit, want):
    man = _add_renamed(str(tmp_path), reference_edit)
    model = man.config("renamed-serve")
    assert model["d_model"] == 64 and "hidden_size" not in model
    out = run.measure(man, tiny.args("renamed-chat", seed=2**31 + 13),
                      jax.devices()[:1])
    assert out["failed"] == 0 and out["attempted"] > 5
    assert out["correct"] is want
    assert set(out["compared"]) == {"stream_faults", "logit_gap_max",
                                    "logit_gap_mean"}
    assert out["compared"]["logit_gap_mean"]["ok"] is want
    assert set(out["metrics"]) == {"itl_p50_ms", "itl_p99_ms", "setup_s"}


def test_the_readers_take_their_costs_from_the_family(tmp_path):
    man = _add_renamed(str(tmp_path))
    fam = manifest.family_of(man.config("renamed-serve"))
    assert fam.__file__.startswith(str(tmp_path))
    rec = {"model": man.config("renamed-serve"), "kind": "train", "chips": 1,
           "scalars": {"tokens_per_s": 1.0e6}, "plan": {"seq": 64},
           "peak": peaks.peak("TPU v5 lite")}
    got = manifest.load_reader("mfu", man.data_dir).read(rec)
    want = 100.0 * fam.costs.train_flops_per_token(rec["model"], 64) \
        * 1.0e6 / 197e12
    assert got == pytest.approx(want) and 0 < got < 100


def test_a_family_with_two_kinds_of_layer(tmp_path):
    """Layer 0 of another FFN width: ``make_params`` makes the layers one
    by one, and the reference's pass makes each again at a static index and
    compiles one layer a kind."""
    shutil.copytree(os.path.join(HERE, "data", "renamed"),
                    tmp_path / "benchmark")
    fam = manifest.load_family("renamed", str(tmp_path / "benchmark"))
    model = {**RENAMED, **fam.tiny(RENAMED), "num_hidden_layers": 3,
             "d_ff_first": 160, manifest.DATA_DIR: str(tmp_path / "benchmark")}
    assert weights.layer_kinds(model) == ["first", "rest", "rest"]
    key = weights.seed_key(2**31 + 21)
    whole = jax.jit(lambda k: weights.make_params(model, k, jnp.bfloat16))(key)
    assert [p["w_up"].shape for p in whole["layers"]] == \
        [(64, 160), (64, 96), (64, 96)]
    make = weights.layer_maker(model, jnp.bfloat16)
    for l, p in enumerate(whole["layers"]):
        again = make(key, l)
        assert all((again[n] == p[n]).all() for n in p), l
    assert weights.top_names(model) == ["embed", "final_norm", "lm_head"]

    rng = np.random.default_rng(1)
    samples = [{"tag": [0, i], "prompt_len": 10 + i,
                "tokens": rng.integers(0, 256, 5).tolist()} for i in range(2)]
    del fam.reference.TRACED_AT[:]
    got = correct.served_gaps(model, 2**31 + 21, samples)
    # both samples pad to 16 positions: one trace a kind, at its first layer
    assert sorted(fam.reference.TRACED_AT) == [0, 1]
    # the same gaps from the whole tree in one plain pass
    ref, gaps = fam.reference, []
    top = {n: whole[n] for n in ("embed", "lm_head", "final_norm")}
    with jax.default_matmul_precision("highest"):
        for s in samples:
            ids = traffic.prompt_tokens(2**31 + 21, s["tag"], s["prompt_len"],
                                        256) + s["tokens"]
            x = ref.embed(jnp.asarray([ids]), top)
            for l, p in enumerate(whole["layers"]):
                x = ref.layer(x, p, model, None, l)
            logits = np.asarray(ref.head_logits(x[0], top, model))
            lo = s["prompt_len"] - 1
            for i, t in enumerate(s["tokens"]):
                gaps.append(logits[lo + i].max() - logits[lo + i][t])
    assert got["positions"] == 10
    assert got["logit_gap_max"] == pytest.approx(max(gaps), abs=1e-4)
    assert got["logit_gap_mean"] == pytest.approx(np.mean(gaps), abs=1e-4)
    assert got["logit_gap_max"] > 0.5      # random tokens are not the best


def _digest(tree) -> str:
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in sorted(leaves,
                             key=lambda kv: jax.tree_util.keystr(kv[0])):
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


# taken from the parent's weights.make_params / make_layer (commit e475133,
# before the weights moved behind the family), by this same function
PARENT_DIGESTS = {
    (3, "bfloat16"): (
        "57b416188bf8cd370ac64608964cc66b3fc7771b867411ddb134ba0b6a76c841",
        "56fba0e3199bfdd0e0cbc5dad10fba54053db8aa5056ef4100e8447ddd84d813"),
    (3, "float32"): (
        "69a575c6c1ba42fe694f13472428d6a8c9eb835e81ce639bd1694f765657c464",
        "e84c91fe4ea73ef1681e2acc6658dfbfc43fc002e34789539009068a20fa0e99"),
    (2**31 + 5, "bfloat16"): (
        "ac59f372c5675f6849b5951c1288170317e5d813d5724821d63093a9961aeb35",
        "e70ec65e085a02b88323dbf8e29c836e6513748409e553decd6742c3b236df30"),
    (2**31 + 5, "float32"): (
        "52f8b4a1ffc3c551237cd55ad43f503ff9b90bfad2770bf1ff445cdd3366d428",
        "622023ac243fa5a3ce511d938140a25609770a254790fd8b96abebbe77f90fd4"),
}


@pytest.mark.parametrize("seed,dtype", sorted(PARENT_DIGESTS))
def test_llama_weights_are_the_parents_bit_for_bit(seed, dtype):
    model = dict(tiny.TINY_MODEL, tie_word_embeddings=False)
    assert model["family"] == "llama" and model["hidden_size"] == 64
    key, dt = weights.seed_key(seed), jnp.dtype(dtype)
    whole = jax.jit(lambda k: weights.make_params(model, k, dt))(key)
    one = jax.jit(lambda k: weights.make_layer(model, k, 1, dt))(key)
    assert (_digest(whole), _digest(one)) == PARENT_DIGESTS[seed, dtype]
    again = weights.layer_maker(model, dt)(key, 1)     # l traced: one kind
    assert _digest(again) == PARENT_DIGESTS[seed, dtype][1]


PARTIAL_FAMILY = "def program_config(model, **over):\n    return None\n"


@pytest.mark.parametrize("family,why", [
    (None, "names no 'family'"), ("nosuch", "no module at"),
    ("partial", "lacks")])
def test_validate_refuses_a_configuration_without_a_whole_family(
        tmp_path, family, why):
    man = tiny.make_root(str(tmp_path))
    os.makedirs(tmp_path / "benchmark" / "families")
    (tmp_path / "benchmark" / "families" / "partial.py").write_text(
        PARTIAL_FAMILY)
    path = tmp_path / "benchmark" / "configs" / "mistral-7b-v0.3-serve.json"
    doc = json.load(open(path))
    assert doc.pop("family") == "llama"
    if family:
        doc["family"] = family
    json.dump(doc, open(path, "w"))
    with pytest.raises(ValueError, match=why):
        man.validate()
