"""One row a prefill program, each in its own bucket (PR 31).

A wave of k rows admitted in one step — whole prompts, cache-hit suffixes,
chunks — is k one-row programs, each in ``_bucket_for(its piece)`` and
against its own history width, for every model family:

- every ``serving.prefill`` span says ``batch == 1`` and the bucket of ITS
  row, with the wave's size in ``wave``; ``serving.admit`` counts the rows;
- the tokens served equal those of the same requests admitted one a step
  (greedy, through the sampling program, with the prefix cache, with
  ``prefill_chunk``, with a draft model);
- no operand of a prefill program is wider than one row and no key of the
  compiled family holds a batch form;
- ``wave_rows`` is gone from the model interface.
"""
import dataclasses

import numpy as np
import pytest

import paddle_tpu  # noqa: F401  (forces the CPU/virtual-device conftest setup)
import jax
import jax.numpy as jnp

import paddle_tpu.observability as obs
from paddle_tpu.models import deepseek_v2, llama, llama_served
from paddle_tpu.observability import request_trace
from paddle_tpu.serving import LLMEngine

N = 4
BS = 8
BUCKETS = [8, 16, 32, 64]
# prompt lengths of a wave of k rows: no two neighbours in one bucket
LENS = {2: (27, 5), 3: (3, 40, 12), N: (7, 30, 14, 50)}
HEAD = 16                    # tokens the prefix-cache case's prompts share
CHUNK = 16


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(
        llama.tiny_llama(vocab=64, hidden=32, layers=2, heads=4, kv_heads=2,
                         seq=128, ffn=64),
        dtype=jnp.float32)
    return cfg, llama.init_params(cfg, jax.random.PRNGKey(0))


def _clear():
    obs.get_registry().reset()
    obs.get_tracer().clear()
    request_trace.get_request_tracer().clear()
    request_trace.get_exemplar_store().clear()


@pytest.fixture
def obs_on():
    _clear()
    obs.enable()
    try:
        yield
    finally:
        obs.disable()
        _clear()


def _engine(model, **kw):
    cfg, params = model
    return LLMEngine(params, cfg, max_slots=N, block_size=BS,
                     max_model_len=128, prompt_buckets=BUCKETS, **kw)


def _mode(name, model):
    """(engine keywords, request keywords) of a case."""
    cfg, params = model
    return {
        "greedy": ({}, {}),
        # the sampling program with one survivor: deterministic, so the
        # two admission orders can be compared token for token
        "sampled": ({}, dict(temperature=0.7, top_k=1)),
        "prefix_cache": (dict(prefix_cache=True), {}),
        "chunked": (dict(prefill_chunk=CHUNK), {}),
        "draft": (dict(draft_params=params, draft_config=cfg,
                       spec_tokens=3), {}),
    }[name]


def _prompts(k, mode):
    rng = np.random.default_rng(31 + k)
    head = rng.integers(1, 64, size=HEAD).tolist()
    out = [rng.integers(1, 64, size=n).tolist() for n in LENS[k]]
    if mode == "prefix_cache":   # cache-hit suffixes after the primer
        out = [head + p for p in out]
    return head, out


def _spy_operands(eng, seen):
    """Record the leading dimension of every array a prefill program is
    given besides the weights and the pools."""
    build = eng._prefill_operands

    def spied(row):
        bucket, flags, pnbk, args = build(row)
        seen.extend(a.shape[0] for i, a in enumerate(args)
                    if i not in (0, 4, 8))
        return bucket, flags, pnbk, args
    eng._prefill_operands = spied


def _target_prefills():
    return [s for s in obs.get_tracer().spans()
            if s.name == "serving.prefill"
            and s.attrs.get("model") != "draft"]


@pytest.mark.parametrize("mode", ["greedy", "sampled", "prefix_cache",
                                  "chunked", "draft"])
@pytest.mark.parametrize("k", [2, 3, N])
def test_a_wave_is_one_program_a_row_in_the_rows_own_bucket(
        model, obs_on, k, mode):
    eng_kw, req_kw = _mode(mode, model)
    head, prompts = _prompts(k, mode)

    def primed():
        eng = _engine(model, **eng_kw)
        if mode == "prefix_cache":
            eng.add_request(head + [1, 2, 3], max_new_tokens=2)
            eng.run()
            eng.results.clear()
        return eng

    # the burst: k requests queued before one step admits them all
    eng = primed()
    widths = []
    _spy_operands(eng, widths)
    obs.get_tracer().clear()
    rids = [eng.add_request(p, max_new_tokens=6, **req_kw) for p in prompts]
    eng.step()
    first = _target_prefills()
    assert len(first) == k
    admit = [s for s in obs.get_tracer().spans()
             if s.name == "serving.admit"]
    assert admit[0].attrs["wave"] == k
    hist = HEAD if mode == "prefix_cache" else 0
    for sp, rid, p in zip(first, rids, prompts):
        piece = len(p) - hist
        if mode == "chunked":
            piece = min(piece, CHUNK)
        assert sp.attrs["request_ids"] == [rid]
        assert sp.attrs["tokens"] == [piece]
        assert sp.attrs["start"] == [hist]
        assert sp.attrs["wave"] == k
        # its OWN history width, not the wave's widest
        assert sp.attrs["prefix_bucket"] == \
            eng.model.history_blocks(hist // BS, eng.mb) * BS
    out = eng.run()
    burst = [out[r] for r in rids]
    for sp in _target_prefills():       # the chunks that followed too
        assert sp.attrs["batch"] == 1
        assert sp.attrs["bucket"] == eng._bucket_for(sp.attrs["tokens"][0])
    assert len({s.attrs["bucket"] for s in first}) > 1
    assert widths and set(widths) == {1}
    # (bucket, flags, history width), the draft's one tag deeper: no key
    # holds a batch form
    assert all(len(key) == (4 if key[-1] == "draft" else 3)
               and isinstance(key[1], tuple) for key in eng._prefill)
    draft = [s for s in obs.get_tracer().spans()
             if s.name == "serving.prefill"
             and s.attrs.get("model") == "draft"]
    # the draft's program follows each row's
    assert len(draft) == (len(_target_prefills()) if mode == "draft" else 0)
    assert all(d.attrs["batch"] == 1 for d in draft)

    # the same requests admitted one a step
    ref = primed()
    rrids = []
    for p in prompts:
        rrids.append(ref.add_request(p, max_new_tokens=6, **req_kw))
        ref.step()
    rout = ref.run()
    assert burst == [rout[r] for r in rrids]
    assert all(len(t) == 6 for t in burst)


def test_sampled_rows_draw_from_the_engines_seed(model):
    """Rows sampled inside their own programs: the same burst on the same
    seed serves the same tokens, another seed other tokens."""
    def run(seed):
        eng = _engine(model, seed=seed)
        _head, prompts = _prompts(3, "greedy")
        rids = [eng.add_request(p, max_new_tokens=8, temperature=1.0,
                                top_k=20, top_p=0.95) for p in prompts]
        out = eng.run()
        assert all(key[1] == (True, True, True) for key in eng._prefill)
        return [out[r] for r in rids]
    assert run(5) == run(5)
    assert run(5) != run(6)


@pytest.mark.parametrize("served", [llama_served.LlamaServed,
                                    deepseek_v2.DeepseekV2Served])
def test_wave_rows_left_the_model_interface(served):
    assert not hasattr(served, "wave_rows")
    assert "wave_rows" not in (llama_served.__doc__ or "")
