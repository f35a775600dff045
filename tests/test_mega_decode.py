"""Persistent fused decode megakernel (r18, kernels/mega_decode).

Interpret-mode legs of the acceptance contract: greedy token streams
through ``decode_kernel="mega"`` are bit-identical to the ragged path —
plain and int8-KV and int8-weights, and composed with prefix-cache hits,
chunked prefill, swap-in restores and spec-decode draft waves (where the
draft's k steps run as ONE persistent multi-step launch). Plus the
variant-cache bound (ONE compiled variant per sampling-flag set, same
contract the ragged path is pinned to) and the counted-never-silent
fallback. The Mosaic-vs-oracle and wall-clock legs live in
tests_tpu/test_mega_decode_tpu.py.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels.mega_decode import mega_supported
from paddle_tpu.models import llama
from paddle_tpu.serving.engine import LLMEngine


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(
        llama.tiny_llama(vocab=64, hidden=32, layers=2, heads=4, kv_heads=2,
                         seq=128, ffn=64),
        dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _streams(params, cfg, kernel, prompts, n_new, **kw):
    eng = LLMEngine(params, cfg, max_slots=2, block_size=8,
                    max_model_len=64, prompt_buckets=[8, 32],
                    decode_steps=3, decode_kernel=kernel, **kw)
    ids = [eng.add_request(p, max_new_tokens=k)
           for p, k in zip(prompts, n_new)]
    out = eng.run()
    return [out[i] for i in ids], eng


@pytest.mark.parametrize("kv", [None, "int8"])
def test_engine_greedy_streams_mega_equals_ragged(model, kv):
    """The acceptance parity: greedy streams through the fused
    megakernel are bit-identical to the ragged path's over mixed
    lengths (incl. a 1-token prompt and an exact block boundary),
    plain and int8-KV pools."""
    cfg, params = model
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 64, size=n).tolist() for n in (1, 8, 13)]
    n_new = [6, 4, 5]
    a, _ = _streams(params, cfg, "ragged", prompts, n_new, kv_dtype=kv)
    b, eng = _streams(params, cfg, "mega", prompts, n_new, kv_dtype=kv)
    assert a == b
    assert all(k[0] == "mega" for k in eng._decode_cache)


def test_engine_mega_int8_weights_parity(model):
    """int8 weight-only params: the kernel streams the int8 tiles
    unconverted and applies the per-channel scales to the f32
    accumulator (the quant_matmul idiom, tiled) — streams must still
    match the ragged path bit for bit."""
    cfg, params = model
    qp = llama.quantize_params(params)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 64, size=n).tolist() for n in (5, 13)]
    a, _ = _streams(qp, cfg, "ragged", prompts, [6, 6])
    b, _ = _streams(qp, cfg, "mega", prompts, [6, 6])
    assert a == b


def test_engine_mega_prefix_cache_and_chunked_prefill_parity(model):
    """Prefix-cache hits + chunked prefill, one composition: cached
    history folds into the same true-length walk inside the fused
    kernel, and mid-chunk slots walk zero blocks (zeroed walk-lengths
    reach the kernel's scalar prefetch) until their final chunk lands."""
    cfg, params = model
    rng = np.random.default_rng(5)
    long_p = rng.integers(1, 64, size=26).tolist()
    short_p = rng.integers(1, 64, size=5).tolist()

    def run(kernel):
        eng = LLMEngine(params, cfg, max_slots=2, block_size=8,
                        max_model_len=64, prompt_buckets=[8, 32],
                        decode_steps=2, kv_dtype="int8",
                        prefix_cache=True, prefill_chunk=8,
                        decode_kernel=kernel)
        r1 = eng.add_request(short_p, max_new_tokens=5)
        r2 = eng.add_request(long_p, max_new_tokens=4)
        eng.run()
        r3 = eng.add_request(long_p, max_new_tokens=4)  # cache hit
        out = eng.run()
        assert eng.prefix_cache.hits >= 1
        return out[r1], out[r2], out[r3]

    assert run("ragged") == run("mega")


def test_engine_mega_swap_in_parity(model):
    """Swap-in restores: a slot continued from host-tier KV streams
    identically through the fused kernel."""
    import paddle_tpu.observability as obs

    cfg, params = model
    rng = np.random.default_rng(6)
    prompts = [rng.integers(1, 64, size=8).tolist() for _ in range(2)]

    def run(kernel):
        obs.get_registry().reset()
        obs.enable()
        try:
            eng = LLMEngine(params, cfg, max_slots=2, block_size=8,
                            max_model_len=64, num_blocks=5,
                            prompt_buckets=[8], kv_dtype="int8",
                            kv_swap_bytes=1 << 20, decode_kernel=kernel)
            ids = [eng.add_request(p, max_new_tokens=16) for p in prompts]
            out = eng.run()
            reg = obs.get_registry()
            assert reg.counter(
                "serving_kv_swap_in_total").labels().value >= 1
            return [out[i] for i in ids]
        finally:
            obs.disable()
            obs.get_registry().reset()

    assert run("ragged") == run("mega")


def test_engine_mega_spec_draft_parity(model):
    """Spec-decode composition — the second fusion target: the draft's
    k sequential steps run as ONE persistent multi-step launch (greedy
    argmax, embed gather and done/budget bookkeeping in-kernel) and the
    committed streams match the ragged wave's exactly."""
    cfg, params = model
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, 64, size=n).tolist() for n in (4, 11)]

    def run(kernel):
        a, eng = _streams(params, cfg, kernel, prompts, [6, 6],
                          draft_params=params, draft_config=cfg,
                          spec_tokens=3)
        assert eng.spec_waves >= 1
        return a, eng

    a, _ = run("ragged")
    b, eng = run("mega")
    assert a == b
    assert "mega" in eng._spec_draft_cache   # the fused draft compiled


def test_engine_mega_one_variant_per_flag_set(model):
    """The variant-cache bound: across growing lengths the mega cache
    never grows a length axis — exactly one compiled variant per
    sampling-flag set (the ragged contract), keyed ("mega", flags)."""
    cfg, params = model
    rng = np.random.default_rng(7)
    eng = LLMEngine(params, cfg, max_slots=2, block_size=8,
                    max_model_len=128, prompt_buckets=[8, 32],
                    decode_steps=2, decode_kernel="mega")
    for n, k in ((2, 4), (30, 8)):
        eng.add_request(rng.integers(1, 64, size=n).tolist(),
                        max_new_tokens=k)
        eng.run()              # separate runs force horizon growth
    assert len(eng._decode_cache) == 1, sorted(eng._decode_cache)
    assert all(k[0] == "mega" for k in eng._decode_cache)
    # a sampled request adds exactly one more flag-set variant
    eng.add_request(rng.integers(1, 64, size=5).tolist(),
                    max_new_tokens=3, temperature=0.9)
    eng.run()
    assert len(eng._decode_cache) == 2, sorted(eng._decode_cache)


def test_engine_mega_fallback_counted_never_silent(model, monkeypatch):
    """An ineligible mega pick falls back (ragged on TPU, bucketed
    off-TPU) and COUNTS it in serving_mega_fallback_total{reason} —
    and the stream is still correct."""
    import paddle_tpu.observability as obs
    import paddle_tpu.serving.engine as eng_mod

    cfg, params = model
    rng = np.random.default_rng(2)
    prompt = rng.integers(1, 64, size=6).tolist()
    ref, _ = _streams(params, cfg, "bucketed", [prompt], [4])

    # the screen is the served model's (models/llama_served.py)
    import paddle_tpu.models.llama_served as served_mod
    monkeypatch.setattr(served_mod, "mega_supported",
                        lambda *a, **k: (False, "vmem"))
    obs.get_registry().reset()
    obs.enable()
    try:
        out, eng = _streams(params, cfg, "mega", [prompt], [4])
        reg = obs.get_registry()
        assert reg.counter("serving_mega_fallback_total") \
            .labels(reason="vmem").value >= 1
        c = reg.counter("serving_decode_kernel_total")
        assert c.labels(path="mega").value == 0
        # off-TPU the counted fallback is the bucketed family
        assert c.labels(path="bucketed").value \
            + c.labels(path="dense").value >= 1
        assert out == ref
    finally:
        obs.disable()
        obs.get_registry().reset()


def test_engine_auto_off_tpu_never_picks_mega(model):
    """auto on CPU serves the bucketed path — mega requires a TPU
    backend (the kernel would run interpreted): its dispatch count
    stays ZERO, mirroring obs_dump's demo smoke."""
    import paddle_tpu.observability as obs

    cfg, params = model
    obs.get_registry().reset()
    obs.enable()
    try:
        eng = LLMEngine(params, cfg, max_slots=2, block_size=8,
                        max_model_len=128, prompt_buckets=[8])
        assert eng._decode_path() != "mega"
        eng.add_request(list(range(1, 6)), max_new_tokens=4)
        eng.run()
        reg = obs.get_registry()
        c = reg.counter("serving_decode_kernel_total")
        assert c.labels(path="mega").value == 0
        assert c.labels(path="bucketed").value \
            + c.labels(path="dense").value >= 1
    finally:
        obs.disable()
        obs.get_registry().reset()


def test_mega_supported_envelope(model):
    """The eligibility screen: serving-sized tiny models fit; a config
    whose ring/scratch envelope exceeds the ~12 MiB VMEM budget is
    rejected with reason "vmem" (the counted-fallback trigger)."""
    cfg, params = model
    ok, reason = mega_supported(params, cfg, n_slots=2, n_steps=3,
                                block_size=8, kv_int8=False)
    assert ok, reason
    ok, reason = mega_supported(params, cfg, n_slots=8, n_steps=65536,
                                block_size=8, kv_int8=False)
    assert not ok and reason == "vmem"


def test_engine_mega_mesh_path_choice_counted(model):
    """Fast-lane half of the mesh contract: a mega engine under a tp
    mesh constructs (the r18 ValueError is gone) and its path choice
    bows out counted with reason="mesh" — no decode dispatch needed."""
    import paddle_tpu.observability as obs
    from jax.sharding import Mesh

    cfg, params = model
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
    obs.get_registry().reset()
    obs.enable()
    try:
        eng = LLMEngine(params, cfg, max_slots=2, block_size=8,
                        max_model_len=64, prompt_buckets=[8, 32],
                        decode_steps=3, decode_kernel="mega", mesh=mesh)
        assert eng._decode_path() != "mega"
        assert obs.get_registry().counter("serving_mega_fallback_total") \
            .labels(reason="mesh").value >= 1
    finally:
        obs.disable()
        obs.get_registry().reset()


def test_engine_mega_mesh_counted_fallback(model):
    """r19: decode_kernel="mega" under a tp mesh no longer raises — it
    bows out COUNTED (reason="mesh", the fused kernel cannot be
    shard_mapped) and serves the tp-sharded ragged/bucketed walk with
    the same stream as an unmeshed non-mega engine."""
    import paddle_tpu.observability as obs
    from jax.sharding import Mesh

    cfg, params = model
    rng = np.random.default_rng(2)
    prompt = rng.integers(1, 64, size=6).tolist()
    ref, _ = _streams(params, cfg, "bucketed", [prompt], [4])

    mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
    obs.get_registry().reset()
    obs.enable()
    try:
        out, eng = _streams(params, cfg, "mega", [prompt], [4],
                            mesh=mesh)
        reg = obs.get_registry()
        assert reg.counter("serving_mega_fallback_total") \
            .labels(reason="mesh").value >= 1
        assert reg.counter("serving_decode_kernel_total") \
            .labels(path="mega").value == 0
        assert out == ref
    finally:
        obs.disable()
        obs.get_registry().reset()
