"""r10 serving: radix prefix KV cache + chunked prefill.

Contracts under test:
- cache-hit streams are exactly the cold streams (greedy, bf16/f32 AND
  int8 KV pools), including through the eviction → host-spill →
  restore → hit path — the cached blocks ARE the cold run's blocks;
- chunked-prefill streams are exactly the one-shot-prefill streams, and
  chunks interleave with other slots' decode waves (tokens keep flowing
  while a long prefill is in flight — the bounded-TTFT mechanism);
- the block ledger extends to ``free + backed + cached + squeezed ==
  total`` at every step, through preemption and eviction, and drains to
  ``free + cached == total`` with nothing pinned;
- finish-time adoption enables multi-turn reuse (prompt+answer prefixes
  match on the next turn);
- the compiled prefill family stays bounded: the history axis adds
  power-of-two buckets to the existing (bucket, batch, flags) key, not
  a new variant family;
- observability: serving_prefix_cache_{hits,misses,evictions}_total,
  serving_prefill_tokens_skipped_total, the block/host-bytes gauges,
  and the request-trace ``cached_tokens`` summary field.
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


def _engine(params, cfg, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("block_size", BS)
    kw.setdefault("max_model_len", 128)
    kw.setdefault("prompt_buckets", [8, 32])
    return LLMEngine(params, cfg, **kw)


def _run_one(params, cfg, prompt, n, **kw):
    eng = _engine(params, cfg, **kw)
    rid = eng.add_request(prompt, max_new_tokens=n)
    return eng.run()[rid]


def _ledger_ok(eng):
    a = eng.block_accounting()
    assert a["free"] + a["backed"] + a["cached"] + a["squeezed"] \
        == a["total"], a
    pc = eng.prefix_cache
    if pc is not None:
        # the O(1) incremental counts must agree with a full-trie walk
        # at every checkpoint (they feed _avail_blocks / admission)
        nodes = list(pc._iter_nodes())
        assert pc.device_blocks == sum(
            1 for nd in nodes if nd.block is not None)
        assert pc.evictable_blocks == sum(
            1 for nd in nodes if nd.block is not None and nd.refcount == 0)
        assert pc.host_blocks == sum(1 for nd in nodes if nd.block is None)
    return a


# ---------------------------------------------------------------------------
# cache-hit parity
# ---------------------------------------------------------------------------
def test_cache_hit_stream_matches_cold_stream(model):
    """Warm streams == cold streams, and the warm admission provably
    skipped its cached prefix (hits/skipped counters, shorter prefill)."""
    cfg, params = model
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, 64, size=20).tolist()
    ref = _run_one(params, cfg, prompt, 8)

    eng = _engine(params, cfg, prefix_cache=True)
    r1 = eng.add_request(prompt, max_new_tokens=8)
    out1 = eng.run()[r1]
    r2 = eng.add_request(prompt, max_new_tokens=8)
    out2 = eng.run()[r2]
    assert out1 == ref and out2 == ref
    pc = eng.prefix_cache
    assert pc.hits == 1 and pc.misses == 1
    # 20 tokens: 2 full blocks cached (the 3rd holds the suffix tail)
    assert pc.tokens_skipped == 2 * BS
    _ledger_ok(eng)


def test_partial_prefix_hit(model):
    """A prompt sharing only the FIRST block matches one block; the
    divergent tail prefills — streams still exactly the cold runs."""
    cfg, params = model
    rng = np.random.default_rng(1)
    head = rng.integers(1, 64, size=BS).tolist()
    a = head + rng.integers(1, 64, size=7).tolist()
    b = head + rng.integers(1, 64, size=9).tolist()
    ref_a = _run_one(params, cfg, a, 6)
    ref_b = _run_one(params, cfg, b, 6)

    eng = _engine(params, cfg, prefix_cache=True)
    ra = eng.add_request(a, max_new_tokens=6)
    assert eng.run()[ra] == ref_a
    rb = eng.add_request(b, max_new_tokens=6)
    assert eng.run()[rb] == ref_b
    assert eng.prefix_cache.hits == 1
    assert eng.prefix_cache.tokens_skipped == BS


def test_cache_hit_and_chunk_parity_bf16(model):
    """The production dtype: warm and chunked greedy streams equal the
    cold stream under bf16 too. (The warm path's attention accumulates
    scores in f32 while the cold XLA path accumulates in bf16 — logits
    can differ in low bits, so this asserts the GREEDY TOKEN contract,
    which is what the engine serves; the TPU flash-kernel cold path is
    exercised by the chip lane.)"""
    cfg, params = model
    cfg16 = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    p16 = jax.tree_util.tree_map(lambda p: p.astype(jnp.bfloat16), params)
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, 64, size=20).tolist()
    ref = _run_one(p16, cfg16, prompt, 8)
    eng = _engine(p16, cfg16, prefix_cache=True, prefill_chunk=8)
    r1 = eng.add_request(prompt, max_new_tokens=8)
    out1 = eng.run()[r1]
    r2 = eng.add_request(prompt, max_new_tokens=8)
    out2 = eng.run()[r2]
    assert out1 == ref and out2 == ref
    assert eng.prefix_cache.hits == 1


def test_cache_hit_parity_int8_kv(model):
    """int8 KV pools: the cached blocks hold the SAME quantized payload
    a cold run writes (deterministic quantization of identical inputs),
    so warm greedy streams match cold ones bit for bit."""
    cfg, params = model
    rng = np.random.default_rng(2)
    prompt = rng.integers(1, 64, size=20).tolist()
    ref = _run_one(params, cfg, prompt, 8, kv_dtype="int8")

    eng = _engine(params, cfg, kv_dtype="int8", prefix_cache=True)
    r1 = eng.add_request(prompt, max_new_tokens=8)
    out1 = eng.run()[r1]
    r2 = eng.add_request(prompt, max_new_tokens=8)
    out2 = eng.run()[r2]
    assert out1 == ref and out2 == ref
    assert eng.prefix_cache.hits == 1


def test_eviction_spill_restore_hit_parity(model):
    """Pool pressure spills refcount-0 cached blocks to the host tier
    (device block freed, trie node stays matchable); a later match
    restores them bit-exactly and the stream equals the cold one."""
    import paddle_tpu.observability as obs

    cfg, params = model
    rng = np.random.default_rng(3)
    pa = rng.integers(1, 64, size=20).tolist()
    ref = _run_one(params, cfg, pa, 6)

    obs.get_registry().reset()
    obs.enable()
    try:
        # 8 usable blocks, one slot: filler traffic must evict pa's
        # cached blocks to make room
        eng = _engine(params, cfg, max_slots=1, max_model_len=64,
                      num_blocks=8, prefix_cache=True,
                      prefix_cache_host_bytes=1 << 20)
        ra = eng.add_request(pa, max_new_tokens=6)
        assert eng.run()[ra] == ref
        for _ in range(2):
            eng.add_request(rng.integers(1, 64, size=24).tolist(),
                            max_new_tokens=6)
            eng.run()
        _ledger_ok(eng)
        spilled = eng.prefix_cache.host_blocks
        assert spilled >= 1, "pressure never spilled a cached block"
        rb = eng.add_request(pa, max_new_tokens=6)
        assert eng.run()[rb] == ref
        assert eng.prefix_cache.hits >= 1
        reg = obs.get_registry()
        assert reg.counter("serving_prefix_cache_evictions_total").labels(
            kind="spill").value >= 1
        assert reg.counter("serving_prefix_cache_hits_total"
                           ).labels().value >= 1
        _ledger_ok(eng)
    finally:
        obs.disable()
        obs.get_registry().reset()


def test_eviction_drops_without_host_tier(model):
    """No host pool: eviction drops nodes (subtree and all) instead of
    spilling; the ledger still balances and traffic keeps flowing."""
    cfg, params = model
    rng = np.random.default_rng(4)
    eng = _engine(params, cfg, max_slots=1, max_model_len=64,
                  num_blocks=10, prefix_cache=True)
    for _ in range(4):
        eng.add_request(rng.integers(1, 64, size=20).tolist(),
                        max_new_tokens=6)
        eng.run()
        a = _ledger_ok(eng)
    assert eng.prefix_cache.host_blocks == 0
    assert a["free"] + a["cached"] == a["total"] and a["backed"] == 0


def test_multi_turn_adoption_at_finish(model):
    """A finished request's decode-grown full blocks enter the trie, so
    the next turn (prompt + answer + follow-up) matches past the
    original prompt."""
    cfg, params = model
    rng = np.random.default_rng(5)
    prompt = rng.integers(1, 64, size=14).tolist()
    eng = _engine(params, cfg, prefix_cache=True)
    r1 = eng.add_request(prompt, max_new_tokens=12)
    answer = eng.run()[r1]
    turn2 = prompt + answer + rng.integers(1, 64, size=5).tolist()
    ref = _run_one(params, cfg, turn2, 6)
    r2 = eng.add_request(turn2, max_new_tokens=6)
    assert eng.run()[r2] == ref
    # KV was valid through len(prompt+answer)-1 = 25 -> 3 full blocks
    assert eng.prefix_cache.tokens_skipped >= 3 * BS


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------
def test_chunked_prefill_matches_oneshot(model):
    """Fixed-token chunks produce exactly the one-shot prefill streams
    (with and without the cache riding along)."""
    cfg, params = model
    rng = np.random.default_rng(6)
    prompts = [rng.integers(1, 64, size=n).tolist() for n in (20, 31, 9)]
    refs = [_run_one(params, cfg, p, 7) for p in prompts]
    for cache in (False, True):
        eng = _engine(params, cfg, prefill_chunk=8, prefix_cache=cache)
        ids = [eng.add_request(p, max_new_tokens=7) for p in prompts]
        out = eng.run()
        assert [out[r] for r in ids] == refs, cache
        _ledger_ok(eng)


def test_chunked_prefill_interleaves_decode(model):
    """While one slot chunk-prefills a long prompt, the other slot's
    decode keeps emitting — the step is never monopolized."""
    cfg, params = model
    rng = np.random.default_rng(7)
    short = rng.integers(1, 64, size=6).tolist()
    long_p = rng.integers(1, 64, size=32).tolist()
    ref_long = _run_one(params, cfg, long_p, 4)

    eng = _engine(params, cfg, prefill_chunk=8)
    r0 = eng.add_request(short, max_new_tokens=24)
    eng.step()
    eng.step()
    r1 = eng.add_request(long_p, max_new_tokens=4)
    interleaved = 0
    for _ in range(64):
        toks = eng.step()
        if eng._chunks and any(rid == r0 for rid, _ in toks):
            interleaved += 1
        if r1 in eng.results:
            break
    out = eng.run()
    assert interleaved >= 1, \
        "no decode tokens emitted during the chunked prefill"
    assert out[r1] == ref_long


def test_chunk_size_rounds_and_validates(model):
    cfg, params = model
    eng = _engine(params, cfg, prefill_chunk=9)     # rounds up to 16
    assert eng.prefill_chunk == 16
    with pytest.raises(ValueError):
        _engine(params, cfg, prefill_chunk=256)     # > largest bucket (32)


def test_prefill_variant_family_stays_bounded(model):
    """The history axis adds only power-of-two buckets to the existing
    (bucket, flags) prefill key — mixed cold/warm/chunked traffic keeps
    the compiled set log-bounded, cold keys keep pnbk=0, and no key
    holds a batch form: every program takes one row."""
    cfg, params = model
    rng = np.random.default_rng(8)
    eng = _engine(params, cfg, prefix_cache=True, prefill_chunk=8)
    head = rng.integers(1, 64, size=BS).tolist()
    for i in range(8):
        tail = rng.integers(1, 64, size=int(rng.integers(2, 24))).tolist()
        eng.add_request(head + tail if i % 2 else tail,
                        max_new_tokens=3)
        if i % 3 == 0:
            eng.run()
    eng.run()
    keys = list(eng._prefill)
    assert all(len(k) == 3 for k in keys)       # (bucket, flags, pnbk)
    assert {k[0] for k in keys} <= set(eng.buckets)
    pnbks = {k[2] for k in keys}
    assert all(p == 0 or (p & (p - 1)) == 0 for p in pnbks), pnbks
    n_pnbk = eng.mb.bit_length() + 1
    assert len(keys) <= len(eng.buckets) * 8 * n_pnbk


# ---------------------------------------------------------------------------
# ledger + pressure
# ---------------------------------------------------------------------------
def test_ledger_balances_under_pressure_with_cache(model):
    """Tiny pool + cache + chunking + preemption: the extended ledger
    balances at every step and drains to free+cached with zero pins."""
    cfg, params = model
    rng = np.random.default_rng(9)
    head = rng.integers(1, 64, size=BS).tolist()
    eng = _engine(params, cfg, max_model_len=64, num_blocks=7,
                  prompt_buckets=[8, 32], prefix_cache=True,
                  prefill_chunk=8)
    ids = []
    for i in range(5):
        tail = rng.integers(1, 64, size=int(rng.integers(2, 10))).tolist()
        ids.append(eng.add_request(head + tail,
                                   max_new_tokens=int(rng.integers(6, 14))))
    while eng.has_work():
        eng.step()
        _ledger_ok(eng)
    a = _ledger_ok(eng)
    assert a["free"] + a["cached"] == a["total"] and a["backed"] == 0
    assert not any(nd.refcount
                   for nd in eng.prefix_cache._iter_nodes())
    for rid in ids:
        assert len(eng.results[rid]) >= 1
    assert eng.prefix_cache.hits >= 1


def test_request_trace_summary_carries_cached_tokens(model):
    """The request-trace summary names how many prompt tokens the cache
    served (0 for the cold request, the matched prefix for the hit)."""
    import paddle_tpu.observability as obs

    cfg, params = model
    rng = np.random.default_rng(10)
    prompt = rng.integers(1, 64, size=20).tolist()
    obs.get_registry().reset()
    obs.enable()
    # request ids are per-engine: clear the global trace ring so rows
    # from earlier tests' engines can't shadow this engine's ids
    obs.request_trace.get_request_tracer().clear()
    try:
        eng = _engine(params, cfg, prefix_cache=True)
        r1 = eng.add_request(prompt, max_new_tokens=4)
        eng.run()
        r2 = eng.add_request(prompt, max_new_tokens=4)
        eng.run()
        rows = {r["request_id"]: r
                for r in obs.requests_payload(limit=0)["requests"]}
        assert rows[r1]["cached_tokens"] == 0
        assert rows[r2]["cached_tokens"] == 2 * BS
        reg = obs.get_registry()
        assert reg.counter("serving_prefill_tokens_skipped_total"
                           ).labels().value == 2 * BS
        assert reg.gauge("serving_prefix_cache_blocks"
                         ).labels().value >= 2
    finally:
        obs.disable()
        obs.get_registry().reset()
