"""Real-chip lane for r13 draft-model speculative decoding.

The CPU tier-1 lane (tests/test_spec_decode.py) proves the mechanism on
the bucketed draft path; this lane proves the chip composition: the
DRAFT proposal loop rides the compiled-Mosaic ragged block-walk kernel
(decode_kernel auto picks ragged on TPU), the verify's bucketed gather
runs at real scale, and the headline numbers hold — exact greedy
parity vs the plain engine, > 1 committed token per verify with the
int8-quantized-target draft (the bench row's pairing), and a wall-clock
ordering sanity check.

    PADDLE_TPU_DEVICE_TESTS=1 python -m pytest tests_tpu/test_spec_decode_tpu.py -q
"""
import os
import time

import numpy as np
import pytest

pytestmark = pytest.mark.skipif(
    os.environ.get("PADDLE_TPU_DEVICE_TESTS") != "1",
    reason="real-device lane: set PADDLE_TPU_DEVICE_TESTS=1")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def _model(dtype=jnp.bfloat16):
    from paddle_tpu.models import llama
    cfg = llama.LlamaConfig(
        vocab_size=2048, hidden_size=512, intermediate_size=1024,
        # head_dim 128: what the ragged walk compiles for (at 64, as
        # this lane first had it, Mosaic refuses the walk and auto takes
        # the bucketed path)
        num_layers=4, num_heads=4, num_kv_heads=4, head_dim=128,
        max_seq_len=1024, remat=False, dtype=dtype, use_flash=False)
    params = jax.jit(lambda k: jax.tree_util.tree_map(
        lambda p: p.astype(dtype),
        llama.init_params(cfg, k)))(jax.random.PRNGKey(0))
    return cfg, params


def _run(params, cfg, prompts, n_new, **kw):
    from paddle_tpu.serving import LLMEngine
    eng = LLMEngine(params, cfg, max_slots=4, block_size=32,
                    max_model_len=512, prompt_buckets=[64, 256], **kw)
    rids = [eng.add_request(p, max_new_tokens=n)
            for p, n in zip(prompts, n_new)]
    out = eng.run()
    return [out[r] for r in rids], eng


def test_spec_parity_and_mechanism_on_chip():
    """int8-draft/float32-target: exact greedy stream parity vs the
    plain engine, acceptance high enough that the engine commits > 1
    token per verify call, and the draft proposal dispatches rode the
    ragged kernel (decode_kernel auto on TPU). Float32 at the highest
    matmul precision: the verify program scores [N, S, h] where decode
    runs [N, 1, h] through another attention, and at the chip's default
    precision (one bf16 pass), as in bf16, their low bits differ enough
    that near-tie argmaxes of these random weights flip — most streams
    part within 48 tokens. At float32-highest 4 seeds of 8 streams were
    all identical (PR 21's chip sweep): the mechanism is exact."""
    from paddle_tpu.models import llama
    cfg, params = _model(jnp.float32)
    draft = jax.jit(llama.quantize_params)(params)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 2048, size=int(n)).tolist()
               for n in rng.integers(40, 250, size=8)]
    n_new = [48] * len(prompts)
    with jax.default_matmul_precision("highest"):
        base, _ = _run(params, cfg, prompts, n_new)
        spec, eng = _run(params, cfg, prompts, n_new, draft_params=draft,
                         draft_config=cfg, spec_tokens=4)
    assert base == spec
    assert eng.spec_waves > 0
    assert eng.spec_committed / eng.spec_verify_calls > 1.0
    assert "ragged" in eng._spec_draft_cache
    assert len(eng._decode_cache) == 0       # every wave was speculative


def test_spec_variants_stay_bounded_on_chip():
    """The spec compile family: one draft variant per kernel path, one
    verify variant per power-of-two history bucket — the chunked-
    prefill axis, no new family."""
    cfg, params = _model()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 2048, size=int(n)).tolist()
               for n in (33, 180, 300, 64)]
    _, eng = _run(params, cfg, prompts, [40] * 4, draft_params=params,
                  draft_config=cfg, spec_tokens=4)
    assert set(eng._spec_draft_cache) == {"ragged"}
    assert all(nbk & (nbk - 1) == 0 for nbk in eng._spec_verify_cache)
    assert len(eng._spec_verify_cache) <= eng.mb.bit_length() + 1


def test_spec_throughput_ordering_on_chip():
    """Wall-clock sanity at acceptance ~1 (draft == quantized target):
    the speculative engine must not be SLOWER than the plain engine on
    the same greedy workload (the >= 1.5x acceptance number lands with
    the bench row on the serving-sized model; this guards the sign)."""
    from paddle_tpu.models import llama
    cfg, params = _model()
    draft = jax.jit(llama.quantize_params)(params)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, 2048, size=int(n)).tolist()
               for n in rng.integers(64, 200, size=8)]
    n_new = [64] * len(prompts)

    def timed(**kw):
        _run(params, cfg, prompts, n_new, **kw)      # warm/compile
        t0 = time.perf_counter()
        _run(params, cfg, prompts, n_new, **kw)
        return time.perf_counter() - t0

    t_plain = timed()
    t_spec = timed(draft_params=draft, draft_config=cfg, spec_tokens=4)
    assert t_spec <= 1.15 * t_plain, (t_spec, t_plain)
