"""Llama through the refactored engine: the greedy streams of a tiny model
equal the ones the PARENT commit's engine gave (recorded there, before the
model interface, in ``tests/data/llama_streams_pr27.json``), over the paths
the interface moved: whole prompts, chunked prefill, the ragged walk,
multi-step decode, int8 KV under the prefix cache, a pool that preempts.
``llama_streams_pr29.json`` was recorded on PR 29's parent for the two
cases that take both branches of ``weight_only_matmul`` through the q/k/v
projections' barrier (``LlamaServed._qkv``): int8 weight-only leaves, and
bf16 weights at two slots on the ragged walk."""
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import llama
from paddle_tpu.serving import LLMEngine

# engine options
CASES = {
    "plain": dict(),
    "chunked": dict(prefill_chunk=16),
    "ragged": dict(decode_kernel="ragged"),
    "steps4": dict(decode_steps=4),
    "int8kv_prefix": dict(kv_dtype="int8", prefix_cache=True),
    "tight_pool": dict(num_blocks=14, prefill_chunk=16),
    "int8_weights": dict(),
    "bf16_two_slots": dict(decode_kernel="ragged", max_slots=2),
}
# the weights of a case: float32 dense leaves unless named here
WEIGHTS = {"int8_weights": (jnp.float32, True),
           "bf16_two_slots": (jnp.bfloat16, False)}
_DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED = {k: v for name in ("llama_streams_pr27.json",
                              "llama_streams_pr29.json")
            for k, v in json.load(open(os.path.join(_DATA, name))).items()}


@functools.lru_cache(maxsize=None)
def _tiny(dtype, int8_weights):
    cfg = llama.tiny_llama(vocab=97, hidden=64, layers=2, heads=4,
                           kv_heads=2)
    cfg = type(cfg)(**{**cfg.__dict__, "dtype": dtype, "remat": False})
    params = llama.init_params(cfg, jax.random.PRNGKey(5))
    if int8_weights:
        params = llama.quantize_params(params)
    return cfg, params


@pytest.mark.parametrize("case", sorted(CASES))
def test_streams_equal_the_parents(case):
    cfg, params = _tiny(*WEIGHTS.get(case, (jnp.float32, False)))
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, 97, size=n).tolist()
               for n in (5, 23, 40, 9, 61, 17)]
    kw = dict(max_slots=3, block_size=8, max_model_len=128,
              prompt_buckets=[16, 32, 64], seed=0)
    eng = LLMEngine(params, cfg, **{**kw, **CASES[case]})
    ids = [eng.add_request(p, max_new_tokens=12 + 3 * i)
           for i, p in enumerate(prompts)]
    res = eng.run()
    assert [res[i] for i in ids] == RECORDED[case]


def test_a_config_without_a_served_model_is_refused():
    from paddle_tpu.models import moe

    cfg = moe.tiny_moe()
    with pytest.raises(TypeError, match="names no served model"):
        LLMEngine({}, cfg, max_slots=2, max_model_len=64)
