"""Real-chip lane for the r18 persistent fused decode megakernel.

The CPU tier-1 lane (tests/test_mega_decode.py) only ever exercises the
Pallas INTERPRETER; this lane proves the compiled Mosaic program — the
whole-layer-stack grid, the double-buffered weight-tile streaming, the
in-call ring DMA append, the fused draft multi-step epilogue — against
the XLA/ragged oracle on the chip, then the acceptance perf claim:
decode-step wall-clock beats the ragged path at batch <= 4 (one launch
per step vs one per layer).

    PADDLE_TPU_DEVICE_TESTS=1 python -m pytest tests_tpu/test_mega_decode_tpu.py -q
"""
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.kernels.mega_decode import MEGA_TPU_REFUSAL

# PR 21 withdrew the kernel from selection on a TPU: Mosaic refuses it
# (tests/test_aot_chip_compile.py keeps the compile), so asking for it by
# name raises and auto no longer picks it. Strict: the day the kernel
# compiles and is selected again, these fail until the marks go.
pytestmark = [
    pytest.mark.skipif(
        os.environ.get("PADDLE_TPU_DEVICE_TESTS") != "1",
        reason="real-device lane: set PADDLE_TPU_DEVICE_TESTS=1"),
    pytest.mark.xfail(strict=True, reason=MEGA_TPU_REFUSAL,
                      raises=(NotImplementedError, AssertionError)),
]


@pytest.fixture(scope="module")
def model():
    from paddle_tpu.models import llama
    cfg = llama.LlamaConfig(
        vocab_size=32768, hidden_size=1536, intermediate_size=6144,
        num_layers=12, num_heads=12, num_kv_heads=4, head_dim=128,
        max_seq_len=2048, remat=False, dtype=jnp.bfloat16)
    params = jax.jit(lambda k: jax.tree_util.tree_map(
        lambda p: p.astype(jnp.bfloat16),
        llama.init_params(cfg, k)))(jax.random.PRNGKey(0))
    return params, cfg


def _run(params, cfg, kernel, reqs, *, slots, steps=16, kv="int8",
         **kw):
    from paddle_tpu.serving import LLMEngine
    eng = LLMEngine(params, cfg, max_slots=slots, block_size=64,
                    max_model_len=1024, prompt_buckets=[128, 512, 1024],
                    decode_steps=steps, kv_dtype=kv,
                    decode_kernel=kernel, **kw)
    t0 = time.perf_counter()
    rids = [eng.add_request(p, max_new_tokens=32, temperature=0.0)
            for p in reqs]
    out = eng.run()
    dt = time.perf_counter() - t0
    return [out[r] for r in rids], eng, dt


def test_mega_stream_parity_vs_ragged_on_chip(model):
    """Compiled-Mosaic acceptance: greedy streams through the fused
    megakernel are bit-identical to the ragged path's (bf16 + int8-KV,
    mixed lengths) and the compile cache holds exactly one ("mega",
    flags) variant."""
    params, cfg = model
    rng = np.random.default_rng(0)
    lens = [int(x) for x in np.concatenate(
        [rng.integers(64, 160, size=2), rng.integers(600, 900, size=2)])]
    reqs = [rng.integers(1, 32768, size=ln).tolist() for ln in lens]
    toks_m, eng_m, _ = _run(params, cfg, "mega", reqs, slots=4)
    assert len(eng_m._decode_cache) == 1, sorted(eng_m._decode_cache)
    assert all(k[0] == "mega" for k in eng_m._decode_cache)
    toks_r, _, _ = _run(params, cfg, "ragged", reqs, slots=4)
    assert toks_m == toks_r


def test_mega_auto_small_batch_on_chip(model):
    """auto on TPU at batch <= 4 picks the megakernel; at batch 8 it
    stays on the ragged walk (the small-batch launch-bound regime is
    where the fusion pays)."""
    from paddle_tpu.serving import LLMEngine
    params, cfg = model
    small = LLMEngine(params, cfg, max_slots=4, block_size=64,
                      max_model_len=1024, prompt_buckets=[128])
    assert small._decode_path() == "mega"
    big = LLMEngine(params, cfg, max_slots=8, block_size=64,
                    max_model_len=1024, prompt_buckets=[128])
    assert big._decode_path() == "ragged"


@pytest.mark.parametrize("slots", [1, 4])
def test_mega_decode_beats_ragged_wall_clock_on_chip(model, slots):
    """The acceptance perf claim: decode-step wall-clock through ONE
    persistent launch beats the ragged path's launch-per-layer at
    batch <= 4 (bench row llama-2.6b_serving_megadecode carries the
    regression gate; this is the in-tree ordering check)."""
    params, cfg = model
    rng = np.random.default_rng(1)
    reqs = [rng.integers(1, 32768, size=96).tolist()
            for _ in range(slots)]
    # warm both compile caches before timing
    _run(params, cfg, "mega", reqs, slots=slots)
    _run(params, cfg, "ragged", reqs, slots=slots)
    toks_m, _, dt_m = _run(params, cfg, "mega", reqs, slots=slots)
    toks_r, _, dt_r = _run(params, cfg, "ragged", reqs, slots=slots)
    assert toks_m == toks_r
    n_tok = sum(len(t) for t in toks_m)
    print(f"[batch {slots}] mega {n_tok / dt_m:.1f} tok/s vs ragged "
          f"{n_tok / dt_r:.1f} tok/s")
    assert dt_m < dt_r, (dt_m, dt_r)


def test_mega_spec_draft_fused_on_chip(model):
    """The second fusion target on silicon: draft waves run as one
    persistent multi-step launch and the committed streams match the
    ragged wave's."""
    params, cfg = model
    rng = np.random.default_rng(2)
    reqs = [rng.integers(1, 32768, size=80).tolist() for _ in range(2)]
    toks_m, eng_m, _ = _run(params, cfg, "mega", reqs, slots=2, kv=None,
                            draft_params=params, draft_config=cfg,
                            spec_tokens=4)
    assert eng_m.spec_waves >= 1
    assert "mega" in eng_m._spec_draft_cache
    toks_r, _, _ = _run(params, cfg, "ragged", reqs, slots=2, kv=None,
                        draft_params=params, draft_config=cfg,
                        spec_tokens=4)
    assert toks_m == toks_r
