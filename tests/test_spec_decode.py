"""r13 serving: draft-model speculative decoding — two-model engine with
batched verify and exact greedy parity.

Contracts under test:
- speculative greedy streams are EXACTLY the non-speculative greedy
  streams, token for token — f32, bf16-config and int8-KV pools, with a
  high-agreement draft, a SMALLER draft config, and a zero-acceptance
  adversarial draft (which must degenerate to >= 1 token per wave,
  never emit nothing, never diverge);
- the mechanism: with a high-agreement draft the engine commits > 1
  token per target verify call on average, at acceptance >= 60%,
  visible in both the host counters and the serving_spec_* metrics;
- composition: prefix-cache warm hits (the cached blocks carry BOTH
  models' KV), chunked prefill interleave, swap-out/in of a speculating
  slot, per-request eos, and admission churn all keep parity;
- mixed greedy/sampled waves fall back to the normal decode path
  (stale draft slots never re-enter spec) and still finish correctly;
- ``spec=False`` / no draft leaves the engine byte-identical: same
  compiled decode-variant count, no draft pools, no spec state;
- the block ledger free+backed+cached+squeezed == total balances at
  every step with spec on (draft KV shares the target's blocks).
"""
import dataclasses

import numpy as np
import pytest

import paddle_tpu  # noqa: F401  (forces the CPU/virtual-device conftest setup)
import jax
import jax.numpy as jnp

from paddle_tpu.models import llama
from paddle_tpu.serving import LLMEngine

BS = 8


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(
        llama.tiny_llama(vocab=64, hidden=32, layers=2, heads=4, kv_heads=2,
                         seq=128, ffn=64),
        dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def small_draft(model):
    """A genuinely smaller draft (half depth/width) sharing the vocab."""
    cfg, _ = model
    dcfg = llama.draft_config(cfg, num_layers=1)
    return dcfg, llama.init_params(dcfg, jax.random.PRNGKey(7))


def _engine(params, cfg, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("block_size", BS)
    kw.setdefault("max_model_len", 128)
    kw.setdefault("prompt_buckets", [8, 32])
    return LLMEngine(params, cfg, **kw)


def _run(params, cfg, prompts, n_new, **kw):
    eng = _engine(params, cfg, **kw)
    rids = [eng.add_request(p, max_new_tokens=n)
            for p, n in zip(prompts, n_new)]
    out = eng.run()
    return [out[r] for r in rids], eng


def _prompts(seed, sizes):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 64, size=n).tolist() for n in sizes]


# ---------------------------------------------------------------------------
# exact greedy parity
# ---------------------------------------------------------------------------
def _assert_parts_only_at_a_tie(params, cfg, prompt, base, spec):
    """bf16: the two runs' streams are equal up to the first position
    where they part, and there both picks are the best logit up to bf16
    rounding. The logits are a float32 forward of the same bf16 weights
    over the common context; the tolerance is 2^-6 of the best logit, two
    roundings of bf16's 8 significant bits (the partings seen read 1.4
    roundings or less). Past that position the contexts differ and
    nothing is compared."""
    if base == spec:
        return
    assert len(base) == len(spec)
    j = next(i for i, (x, y) in enumerate(zip(base, spec)) if x != y)
    f32 = dataclasses.replace(cfg, dtype=jnp.float32)
    logits = np.asarray(llama.forward(
        jax.tree_util.tree_map(lambda p: p.astype(jnp.float32), params),
        jnp.asarray([prompt + base[:j]]), f32)[0, -1])
    best = logits.max()
    for tok in (base[j], spec[j]):
        assert best - logits[tok] <= 2.0 ** -6 * abs(best), (
            j, tok, float(best), float(logits[tok]))


@pytest.mark.parametrize("variant", ["f32", "bf16", "int8kv"])
def test_spec_greedy_parity(model, variant):
    """Speculative greedy output == non-speculative greedy output,
    token for token, across dtype configs — the acceptance contract.

    bf16 note: the batched verify computes its matmuls at [N, S, h]
    shapes where the decode program runs [N, 1, h]; bf16 gemm low bits
    can differ across those shapes, so a knife-edge argmax tie (top-2
    logit gap inside bf16 rounding) may resolve differently — the same
    cross-program caveat docs/serving.md states for r10's warm-path
    logits. So in bf16 a stream may part from the plain one, but only
    at such a tie (``_assert_parts_only_at_a_tie``); f32 and
    int8-KV-over-f32 are exact (noise ~1e-7 vs argmax gaps)."""
    cfg, params = model
    kv = None
    if variant == "bf16":
        cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16)
        params = jax.tree_util.tree_map(
            lambda p: p.astype(jnp.bfloat16), params)
    elif variant == "int8kv":
        kv = "int8"
    prompts = _prompts(0, (1, 5, 11, 20, 3))
    n_new = (9, 12, 6, 11, 14)
    base, _ = _run(params, cfg, prompts, n_new, kv_dtype=kv)
    spec, eng = _run(params, cfg, prompts, n_new, kv_dtype=kv,
                     draft_params=params, draft_config=cfg, spec_tokens=4)
    if variant == "bf16":
        for prompt, b, s in zip(prompts, base, spec):
            _assert_parts_only_at_a_tie(params, cfg, prompt, b, s)
    else:
        assert base == spec
    assert eng.spec_waves > 0          # the spec path actually ran


def test_spec_parity_with_small_draft(model, small_draft):
    """A draft with its own (smaller) architecture: whatever it
    proposes, the verified stream equals the plain greedy stream."""
    cfg, params = model
    dcfg, dparams = small_draft
    prompts = _prompts(3, (4, 9, 17))
    n_new = (10, 8, 12)
    base, _ = _run(params, cfg, prompts, n_new)
    spec, eng = _run(params, cfg, prompts, n_new, draft_params=dparams,
                     draft_config=dcfg, spec_tokens=3)
    assert base == spec
    assert eng.spec_waves > 0


def test_spec_tp_sharded_parity(model):
    """r19: spec decode under a 2-device 'tp' mesh — target runs the
    shard_mapped ragged walk with KV heads split, the draft is
    replicated — and the verified streams stay equal to the unsharded
    spec streams (which are themselves the plain greedy streams)."""
    from jax.sharding import Mesh

    cfg, params = model
    prompts = _prompts(6, (4, 9, 15))
    n_new = (8, 7, 10)

    def run(mesh):
        out, eng = _run(params, cfg, prompts, n_new,
                        decode_kernel="ragged", mesh=mesh,
                        draft_params=params, draft_config=cfg,
                        spec_tokens=3)
        assert eng.spec_waves > 0
        return out

    assert run(None) == run(Mesh(np.asarray(jax.devices()[:2]), ("tp",)))


def test_spec_parity_with_eos(model):
    """Per-request eos: the chained decode path refuses to pipeline
    with an eos set; the spec wave composes with it — an eos emitted
    mid-wave truncates the commit there, exactly like step-wise
    decode."""
    cfg, params = model
    prompts = _prompts(11, (6, 9))
    # pick the eos from the plain run's own output so it actually fires
    base, _ = _run(params, cfg, prompts, (12, 12))
    eos = base[0][5]
    kw = dict(eos_token_id=int(eos))
    e1 = _engine(params, cfg)
    r1 = [e1.add_request(p, max_new_tokens=12, **kw) for p in prompts]
    o1 = e1.run()
    e2 = _engine(params, cfg, draft_params=params, draft_config=cfg,
                 spec_tokens=4)
    r2 = [e2.add_request(p, max_new_tokens=12, **kw) for p in prompts]
    o2 = e2.run()
    assert [o1[r] for r in r1] == [o2[r] for r in r2]
    assert e2.spec_waves > 0


def test_zero_acceptance_adversarial_draft(model):
    """A draft that agrees with nothing: every wave degenerates to the
    target's one new token (never fewer, never a stall), output still
    exactly the plain greedy stream."""
    cfg, params = model
    adversary = llama.init_params(cfg, jax.random.PRNGKey(99))
    prompts = _prompts(5, (7, 13))
    n_new = (10, 10)
    base, _ = _run(params, cfg, prompts, n_new)
    spec, eng = _run(params, cfg, prompts, n_new, draft_params=adversary,
                     draft_config=cfg, spec_tokens=4)
    assert base == spec
    # random-weights agreement on a 64-token vocab is ~1/64
    assert eng.spec_accepted <= 0.2 * eng.spec_proposed
    # >= 1 committed token per wave-slot, monotone forward progress:
    # every token except each request's prefill-sampled first one was
    # committed by a spec wave, in at most that many verify calls
    assert eng.spec_committed == sum(n_new) - len(prompts)
    assert eng.spec_verify_calls <= eng.spec_committed


# ---------------------------------------------------------------------------
# the mechanism: > 1 token per verify, acceptance >= 60%
# ---------------------------------------------------------------------------
def test_spec_mechanism_and_metrics(model):
    """The CPU mechanism proof (acceptance criterion): a synthetic
    high-agreement draft (the target itself) commits > 1 token per
    target verify call on average at acceptance >= 60%, and both the
    host counters and the serving_spec_* registry metrics show it."""
    import paddle_tpu.observability as obs

    cfg, params = model
    obs.enable()
    try:
        reg = obs.get_registry()
        c0 = reg.counter("serving_spec_proposed_total").labels().value
        a0 = reg.counter("serving_spec_accepted_total").labels().value
        prompts = _prompts(2, (5, 9, 14, 6))
        spec, eng = _run(params, cfg, prompts, (12, 12, 12, 12),
                         draft_params=params, draft_config=cfg,
                         spec_tokens=4)
        tokens_per_verify = eng.spec_committed / eng.spec_verify_calls
        acceptance = eng.spec_accepted / eng.spec_proposed
        assert tokens_per_verify > 1.0, (eng.spec_committed,
                                         eng.spec_verify_calls)
        assert acceptance >= 0.6
        assert reg.counter("serving_spec_proposed_total").labels().value \
            - c0 == eng.spec_proposed
        assert reg.counter("serving_spec_accepted_total").labels().value \
            - a0 == eng.spec_accepted
        assert reg.gauge("serving_spec_acceptance_rate").labels().value \
            >= 0.6
        assert reg.gauge("serving_spec_tokens_per_wave").labels().value \
            > 1.0
    finally:
        obs.disable()


# ---------------------------------------------------------------------------
# composition: prefix cache, chunked prefill, swap, sampled fallback
# ---------------------------------------------------------------------------
def test_spec_prefix_cache_warm_hit_parity(model):
    """A re-sent prompt matches its cached blocks — which carry BOTH
    models' KV — and the warm speculative stream equals the warm plain
    stream (and the cold one)."""
    cfg, params = model
    prompt = _prompts(6, (17,))[0]

    def run(**kw):
        eng = _engine(params, cfg, prefix_cache=True, **kw)
        r1 = eng.add_request(prompt, max_new_tokens=6)
        eng.run()
        r2 = eng.add_request(prompt, max_new_tokens=6)   # warm hit
        out = eng.run()
        assert eng.prefix_cache.hits >= 1
        return out[r1], out[r2], eng

    c1, w1, _ = run()
    c2, w2, eng = run(draft_params=params, draft_config=cfg,
                      spec_tokens=4)
    assert (c1, w1) == (c2, w2)
    assert c2 == w2                       # warm == cold either way
    assert eng.spec_waves > 0
    # the warm slot entered spec in sync: its draft KV was restored
    # from the same cached blocks, so acceptance stays high
    assert eng.spec_accepted / eng.spec_proposed >= 0.6


def test_spec_chunked_prefill_interleave_parity(model):
    """A long chunked prefill interleaves with another slot's spec
    waves: mid-chunk slots stay out of the wave, the final chunk joins
    in sync (both models prefill every piece), streams exact."""
    cfg, params = model
    long_p, short_p = _prompts(8, (26, 5))

    def run(**kw):
        eng = _engine(params, cfg, prefix_cache=True, prefill_chunk=8,
                      **kw)
        r1 = eng.add_request(short_p, max_new_tokens=8)
        r2 = eng.add_request(long_p, max_new_tokens=6)
        out = eng.run()
        return out[r1], out[r2]

    assert run() == run(draft_params=params, draft_config=cfg,
                        spec_tokens=3)


def test_spec_swap_out_in_of_speculating_slot(model):
    """Pool pressure preempts a speculating slot into the host KV tier
    (both models' pool entries move verbatim); the swap-in restores it
    mid-stream and parity holds against the plain engine under the
    same pressure."""
    import paddle_tpu.observability as obs

    cfg, params = model
    prompts = _prompts(9, (9, 12))
    n_new = (14, 14)
    # peak demand is 3 + 4 blocks; a 6-block pool MUST preempt one slot
    # through the swap tier mid-decode
    kw = dict(num_blocks=6, max_model_len=64, kv_swap_bytes=1 << 20)
    base, _ = _run(params, cfg, prompts, n_new, **kw)
    obs.enable()
    try:
        reg = obs.get_registry()
        s0 = reg.counter("serving_kv_swap_in_total").labels().value
        spec, eng = _run(params, cfg, prompts, n_new,
                         draft_params=params, draft_config=cfg,
                         spec_tokens=4, **kw)
        assert base == spec
        assert eng.spec_waves > 0
        # the tiny pool forced at least one preemption through the swap
        # tier while speculating
        assert reg.counter("serving_kv_swap_in_total").labels().value \
            > s0
    finally:
        obs.disable()


def test_spec_sampled_mix_falls_back_and_recovers_nothing_wrong(model):
    """A sampled request in the slot mix forces the wave onto the
    normal decode path (greedy slots advance there and go spec-stale);
    everything still finishes, greedy streams still equal the plain
    engine's, and spec re-engages for fresh admissions."""
    cfg, params = model
    prompts = _prompts(12, (5, 7, 6))
    base_eng = _engine(params, cfg, max_slots=2)
    b1 = base_eng.add_request(prompts[0], max_new_tokens=8)
    b2 = base_eng.add_request(prompts[1], max_new_tokens=6,
                              temperature=0.9, top_k=8)
    base_eng.run()
    b3 = base_eng.add_request(prompts[2], max_new_tokens=8)
    base_out = base_eng.run()

    eng = _engine(params, cfg, max_slots=2, draft_params=params,
                  draft_config=cfg, spec_tokens=4)
    r1 = eng.add_request(prompts[0], max_new_tokens=8)
    r2 = eng.add_request(prompts[1], max_new_tokens=6,
                         temperature=0.9, top_k=8)
    eng.run()
    r3 = eng.add_request(prompts[2], max_new_tokens=8)
    out = eng.run()
    # greedy streams match (sampled streams are key-order dependent and
    # deliberately not compared); every request terminated
    assert out[r1] == base_out[b1]
    assert out[r3] == base_out[b3]
    assert len(out[r2]) == len(base_out[b2]) == 6
    # the fresh admission after the sampled request drained re-engaged
    # the spec path
    assert eng.spec_waves > 0


def test_spec_ledger_balances_every_step(model):
    """free + backed + cached + squeezed == total at every step with
    spec on and the prefix cache in play — draft KV adds no terms."""
    cfg, params = model
    eng = _engine(params, cfg, num_blocks=9, max_model_len=64,
                  prefix_cache=True, draft_params=params,
                  draft_config=cfg, spec_tokens=4)
    rng = np.random.default_rng(4)
    shared = rng.integers(1, 64, size=BS).tolist()
    for i in range(4):
        tail = rng.integers(1, 64, size=int(rng.integers(2, 9))).tolist()
        eng.add_request(shared + tail if i % 2 else tail,
                        max_new_tokens=8)
    while eng.has_work():
        eng.step()
        acct = eng.block_accounting()
        assert acct["free"] + acct["backed"] + acct["cached"] \
            + acct["squeezed"] == acct["total"], acct
    assert eng.spec_waves > 0


# ---------------------------------------------------------------------------
# spec-off identity
# ---------------------------------------------------------------------------
def test_spec_off_is_byte_identical_same_variant_count(model):
    """``spec=False`` (or no draft) must leave the decode path exactly
    as it is today: same streams, same compiled decode-variant count,
    no draft pools, no draft prefill variants (test-enforced)."""
    cfg, params = model
    prompts = _prompts(1, (5, 11, 3))
    n_new = (8, 6, 9)
    base, beng = _run(params, cfg, prompts, n_new)
    off, oeng = _run(params, cfg, prompts, n_new, draft_params=params,
                     draft_config=cfg, spec=False)
    assert base == off
    assert len(oeng._decode_cache) == len(beng._decode_cache)
    assert sorted(oeng._decode_cache) == sorted(beng._decode_cache)
    assert sorted(oeng._prefill) == sorted(beng._prefill)
    # one-row programs only: (bucket, flags, history width), no batch
    # form and no draft family
    assert all(len(k) == 3 for k in beng._prefill)
    assert set(oeng.pools) == set(beng.pools)      # no dk/dv
    assert oeng.spec_waves == oeng.spec_verify_calls == 0
    # and with spec ON, the normal decode family is untouched: spec
    # waves never enter _decode_cache (their variants live in the
    # draft/verify caches, draft keyed per kernel, verify per history
    # bucket)
    spec, seng = _run(params, cfg, prompts, n_new, draft_params=params,
                      draft_config=cfg, spec_tokens=4)
    assert spec == base
    assert len(seng._decode_cache) == 0
    assert set(seng._spec_draft_cache) <= {"ragged", "bucketed"}
    # the draft's prefill programs mirror the target's, one tag deeper
    target = {k for k in seng._prefill if len(k) == 3}
    assert target == set(beng._prefill)
    assert {k[:3] for k in seng._prefill if len(k) == 4} == \
        {(b, (False, False, False), h) for b, _f, h in target}
    assert all(k[3] == "draft" for k in seng._prefill if len(k) == 4)


def test_spec_validation_errors(model):
    """Constructor contract: draft without config, vocab mismatch, and
    bad spec_tokens fail loudly."""
    cfg, params = model
    with pytest.raises(ValueError, match="draft_config"):
        _engine(params, cfg, draft_params=params)
    bad = dataclasses.replace(cfg, vocab_size=32)
    with pytest.raises(ValueError, match="vocab"):
        _engine(params, cfg, draft_params=params, draft_config=bad)
    with pytest.raises(ValueError, match="spec_tokens"):
        _engine(params, cfg, draft_params=params, draft_config=cfg,
                spec_tokens=0)


def test_llama_logits_all_matches_stepwise(model):
    """models/llama.forward_with_cache(logits_all=True) — the fixed-
    batch verify primitive — scores a piece exactly like consuming it
    one token at a time."""
    cfg, params = model
    rng = np.random.default_rng(3)
    prompt = jnp.asarray(rng.integers(1, 64, size=(1, 6)), jnp.int32)
    piece = jnp.asarray(rng.integers(1, 64, size=(1, 4)), jnp.int32)
    cache = llama.init_kv_cache(cfg, 1, 32)
    _, cache = llama.forward_with_cache(params, prompt, cache, cfg)
    all_logits, _ = llama.forward_with_cache(params, piece, cache, cfg,
                                             logits_all=True)
    assert all_logits.shape == (1, 4, cfg.vocab_size)
    step_cache = llama.init_kv_cache(cfg, 1, 32)
    _, step_cache = llama.forward_with_cache(params, prompt, step_cache,
                                             cfg)
    for j in range(4):
        lg, step_cache = llama.forward_with_cache(
            params, piece[:, j:j + 1], step_cache, cfg)
        np.testing.assert_allclose(np.asarray(all_logits[:, j]),
                                   np.asarray(lg), rtol=1e-5, atol=1e-5)
