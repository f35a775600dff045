"""Every cell rehearsed tiny on the CPU through the harness's own path
(``run.measure``): the same generators, client process, readers and
references as on the chip, at sizes a test can hold. Nothing here is a
measurement. Also: the measuring path refuses a machine without a TPU, a
broken timed path comes out as not correct, and the control (the reference
in int8) lies beyond what the sound path gives."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import bench_tiny as tiny
from benchmark import correct, manifest, run, traffic, weights

DEVICE_METRICS = ("roofline", "device_idle", "dev_ms", "mfu", "hbm_peak",
                  "collective")


def _no_device_numbers(out):
    for name in out["metrics"]:
        assert not any(k in name for k in DEVICE_METRICS), name
    assert out["device"]["platform"] == "cpu"
    assert "busy_s" not in out["device"]


@pytest.mark.parametrize("cell,trace", [("chat-steady", 0),
                                        ("chat-steady", 1),
                                        ("batch-offline", 0)])
def test_serving_cells_rehearsed_on_the_cpu(tmp_path, cell, trace):
    man = tiny.make_root(str(tmp_path))
    out = run.measure(man, tiny.args(cell, seed=2**31 + 11, trace=trace),
                      jax.devices()[:1])
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 5
    want = {m["name"] for m in man.metrics_for(cell, "end_to_end")}
    if trace:
        assert {"gen_late_p99_ms", "prefill_row_fill", "shed_share",
                "sched_host_ms_per_step"} <= set(out["metrics"])
    else:
        assert set(out["metrics"]) == want
        assert all(v["value"] > 0 for v in out["metrics"].values())
    _no_device_numbers(out)


@pytest.mark.parametrize("trace", [0, 1])
def test_training_cell_rehearsed_on_four_virtual_devices(tmp_path, trace):
    man = tiny.make_root(str(tmp_path), limits={
        "loss_gap": 1e-3, "grad_norm_gap": 2e-2, "delta_norm_gap": 2e-2})
    out = run.measure(man, tiny.args("pretrain-4k-mesh4", seed=5,
                                     trace=trace), jax.devices()[:4])
    assert out["correct"] and out["device"]["count"] == 4
    if not trace:
        assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    else:
        assert "train.step_ms_p50" in out["metrics"]
    _no_device_numbers(out)


def test_the_measuring_path_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(manifest.HERE, "run.py"), "--workload",
         "chat-steady", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=120)
    assert done.returncode != 0
    assert "no TPU" in done.stderr
    assert not any(line.startswith("{") for line in done.stdout.splitlines())


def test_a_token_altered_where_it_is_produced_is_not_correct(tmp_path,
                                                             monkeypatch):
    """The timed path broken underneath: the engine's sampling returns
    another token than the one its logits put first."""
    from paddle_tpu.serving import engine as engine_mod

    sample = engine_mod._sample_rows

    def off_by_one(logits, *a, **kw):
        return (sample(logits, *a, **kw) + 1) % logits.shape[-1]

    monkeypatch.setattr(engine_mod, "_sample_rows", off_by_one)
    man = tiny.make_root(str(tmp_path), limits={"logit_gap_max": 0.5,
                                                "logit_gap_mean": 0.1})
    out = run.measure(man, tiny.args("chat-steady", seed=9),
                      jax.devices()[:1])
    assert out["correct"] is False


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        tmp_path, monkeypatch):
    from paddle_tpu.models import llama

    step = llama.train_step

    def frozen(state, tokens, config, **kw):
        _new, loss = step(state, tokens, config, **kw)
        return state, loss

    monkeypatch.setattr(llama, "train_step", frozen)
    man = tiny.make_root(str(tmp_path), limits={
        "loss_gap": 1e-3, "grad_norm_gap": 2e-2, "delta_norm_gap": 2e-2})
    out = run.measure(man, tiny.args("pretrain-4k-mesh4", seed=5),
                      jax.devices()[:4])
    assert out["correct"] is False


def _tiny_model(tmp_path, name):
    man = tiny.make_root(str(tmp_path))
    return man.config(name)


def test_serving_control_int8_lies_beyond_the_sound_path(tmp_path):
    """The reference in int8 put in the program's place: over seeded
    sequences, the token it puts first lies below the float32 reference's
    best at some positions; the reference put in its own place lies below
    at none. (On the chip, at the cell's size: PERF.md, limits/.)"""
    model = _tiny_model(tmp_path, "mistral-7b-v0.3-serve")
    model = dict(model, vocab_size=4096)     # close logits, as at 32768
    rng = np.random.default_rng(0)
    samples = [{"tag": [0, i], "prompt_len": 40,
                "tokens": rng.integers(0, 4096, 24).tolist()}
               for i in range(3)]
    out = correct.served_gaps(model, 4, samples, control="int8")
    assert out["positions"] == 72
    assert out["control"]["logit_gap_max"] > 0.01
    assert out["control"]["logit_gap_mean"] > 0.0
    same = correct.served_gaps(model, 4, samples, control="float32")
    assert same["control"]["logit_gap_max"] == 0.0


def test_training_control_int8_lies_beyond_the_sound_path(tmp_path):
    from benchmark.reference import train_ref
    from benchmark.train_cell import hyper

    model = _tiny_model(tmp_path, "mistral-7b-v0.3-train-mesh4")
    hp = hyper(model)
    batches = [traffic.train_batch(3, i, 4, 64, model["vocab_size"])
               for i in range(2)]
    want = train_ref.follow(model, hp, 3, batches, jax.devices()[:1])
    four = train_ref.follow(model, hp, 3, batches, jax.devices()[:4])
    ctl = train_ref.follow(model, hp, 3, batches, jax.devices()[:1], "int8")
    sound = correct.train_numbers(dict(four, losses=four["losses"] + [0.0]),
                                  want, [0.0])
    control = correct.train_numbers(dict(ctl, losses=ctl["losses"] + [0.0]),
                                    want, [0.0])
    assert sound["grad_norm_gap"] < 1e-4 and sound["loss_gap"] < 1e-5
    assert control["grad_norm_gap"] > 30 * max(sound["grad_norm_gap"], 1e-5)


def test_reference_makes_one_layer_again_from_the_seed():
    model = dict(tiny.TINY_MODEL, tie_word_embeddings=False)
    key = weights.seed_key(2**31 + 77)
    import jax.numpy as jnp
    whole = jax.jit(lambda k: weights.make_params(model, k, jnp.float32))(key)
    one = jax.jit(lambda k: weights.make_layer(model, k, 1, jnp.float32))(key)
    for name, w in one.items():
        assert (whole["layers"][name][1] == w).all(), name
    other = jax.jit(lambda k: weights.make_params(model, k, jnp.float32))(
        weights.seed_key(2**31 + 78))
    assert (other["embed"] != whole["embed"]).any()
