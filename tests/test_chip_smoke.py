"""What running on the chip asks of the program, checked without one.

A chip belongs to one process, so importing the package must not take it;
the compile cache must be placeable from outside and otherwise sit at one
fixed path inside the checkout; and ``chip_smoke.py`` must never pass
without a chip. The last tests rehearse the smoke's phases tiny on the
virtual CPU devices — wrong paths, arguments and control flow cost no chip
time that way.
"""
import dataclasses
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _python(code, **env_changes):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for k, v in env_changes.items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    return subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=120)


def test_import_initialises_no_backend():
    """A parent that imports the package — the launcher, a tool that
    spawns — must leave the chip to its children."""
    proc = _python(
        "from jax._src import xla_bridge as xb\n"
        "import paddle_tpu\n"
        "assert not xb.backends_are_initialized(), 'import paddle_tpu'\n"
        "import paddle_tpu.distributed.launch\n"
        "assert not xb.backends_are_initialized(), 'import launch'\n"
        "import paddle_tpu as paddle\n"
        "paddle.seed(7)\n"
        "assert not xb.backends_are_initialized(), 'paddle.seed'\n"
        "paddle.framework.random.next_key()\n"
        "assert xb.backends_are_initialized()\n")
    assert proc.returncode == 0, proc.stderr[-2000:]


@pytest.mark.parametrize("placed", [True, False])
def test_compile_cache_placement(placed, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the code sets nothing (JAX reads
    the variable); without it the cache is at <checkout>/.jax_cache."""
    outside = str(tmp_path / "cache")
    proc = _python(
        "import jax\n"
        "updates = []\n"
        "real = jax.config.update\n"
        "jax.config.update = lambda k, v: (updates.append(k), real(k, v))\n"
        "import paddle_tpu\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "print('jax_compilation_cache_dir' in updates)\n",
        JAX_COMPILATION_CACHE_DIR=outside if placed else None)
    assert proc.returncode == 0, proc.stderr[-2000:]
    where, set_in_code = proc.stdout.split()[-2:]
    if placed:
        assert (where, set_in_code) == (outside, "False")
    else:
        assert (where, set_in_code) == (
            os.path.join(REPO, ".jax_cache"), "True")


def test_smoke_does_not_pass_without_a_chip():
    """It looks at the device before it builds anything: seconds."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"


def _tiny_run():
    sys.path.insert(0, REPO)
    import chip_smoke
    from paddle_tpu.models import llama

    sizes = chip_smoke.Sizes(
        model=llama.tiny_llama(vocab=256, hidden=64, layers=2, heads=4,
                               kv_heads=2, seq=128, ffn=128),
        seq=64, batch=4, loss_chunks=2, train_layers=2, train_steps=4,
        serve_layers=2, max_len=128, block=8, wide=(6, 96), narrow=(2, 32),
        short_prompts=(4, 12), long_prompts=(40, 70), n_requests=8,
        max_new=(3, 6), probe_prompt=20, tp_layers=2, tp_requests=4)
    return chip_smoke, chip_smoke.Run(sizes, seed=0, on_chip=False)


def test_one_chip_phases_rehearse_on_cpu():
    smoke, run = _tiny_run()
    smoke.train_phase(run)
    smoke.serve_phase(run)


def test_four_chip_phases_rehearse_on_cpu():
    smoke, run = _tiny_run()
    smoke.sharded_train_phase(run)
    smoke.tp_serve_phase(run)


def test_full_sizes_are_the_published_widths():
    """Depth is the only cut: the smoke's model is LlamaConfig()'s
    defaults — Llama-3-8B — at every width."""
    smoke, _run = _tiny_run()
    from paddle_tpu.models import llama

    sizes = smoke.full_sizes()
    assert sizes.model == llama.llama3_8b()
    assert dataclasses.asdict(sizes.model) | {"num_layers": 0} == \
        dataclasses.asdict(llama.LlamaConfig()) | {"num_layers": 0}
    assert (sizes.model.hidden_size, sizes.model.intermediate_size,
            sizes.model.num_heads, sizes.model.num_kv_heads,
            sizes.model.head_dim, sizes.model.vocab_size) == (
                4096, 14336, 32, 8, 128, 128256)
    assert sizes.wide[0] > 4 >= sizes.narrow[0]
    assert sizes.seq == 2048 and sizes.long_prompts[0] >= 1024
