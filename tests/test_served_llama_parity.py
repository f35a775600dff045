"""Llama through the refactored engine: the greedy streams of a tiny model
equal the ones the PARENT commit's engine gave (recorded there, before the
model interface, in ``tests/data/llama_streams_pr27.json``), over the paths
the interface moved: whole prompts, chunked prefill, the ragged walk,
multi-step decode, int8 KV under the prefix cache, a pool that preempts."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import llama
from paddle_tpu.serving import LLMEngine

CASES = {
    "plain": dict(),
    "chunked": dict(prefill_chunk=16),
    "ragged": dict(decode_kernel="ragged"),
    "steps4": dict(decode_steps=4),
    "int8kv_prefix": dict(kv_dtype="int8", prefix_cache=True),
    "tight_pool": dict(num_blocks=14, prefill_chunk=16),
}
RECORDED = json.load(open(os.path.join(os.path.dirname(__file__), "data",
                                       "llama_streams_pr27.json")))


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.tiny_llama(vocab=97, hidden=64, layers=2, heads=4,
                           kv_heads=2)
    cfg = type(cfg)(**{**cfg.__dict__, "dtype": jnp.float32, "remat": False})
    params = llama.init_params(cfg, jax.random.PRNGKey(5))
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, 97, size=n).tolist()
               for n in (5, 23, 40, 9, 61, 17)]
    return cfg, params, prompts


@pytest.mark.parametrize("case", sorted(CASES))
def test_streams_equal_the_parents(tiny, case):
    cfg, params, prompts = tiny
    eng = LLMEngine(params, cfg, max_slots=3, block_size=8,
                    max_model_len=128, prompt_buckets=[16, 32, 64], seed=0,
                    **CASES[case])
    ids = [eng.add_request(p, max_new_tokens=12 + 3 * i)
           for i, p in enumerate(prompts)]
    res = eng.run()
    assert [res[i] for i in ids] == RECORDED[case]


def test_a_config_without_a_served_model_is_refused():
    from paddle_tpu.models import moe

    cfg = moe.tiny_moe()
    with pytest.raises(TypeError, match="names no served model"):
        LLMEngine({}, cfg, max_slots=2, max_model_len=64)
