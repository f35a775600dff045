"""r12 ragged paged-attention Pallas decode kernel (arXiv 2604.15464).

Contracts under test (interpret mode — the chip lane is
tests_tpu/test_ragged_decode_tpu.py):
- the true-length block walk matches the dense gather reference
  (paged_attention) across mixed lengths including length-1 and exact
  block-boundary lengths, for f32 and bf16 pools; and, one case a
  length, at every length that straddles an edge of the walk's chunks
  (0, 1, bs-1, bs, C*bs-1, C*bs, C*bs+1, the table's full width), at 8,
  4 and 2 KV heads under 4 query heads each, over 4-D pools and over
  5-D pools read at a layer other than 0;
- masked-tail exactness: garbage in the tail of the last block, and NaN
  and inf in the blocks past the length and in the trash block — which
  lie inside the last chunk the walk fetches — change NOTHING
  (bit-identical output — the masked exp is exactly 0.0 and a dead V
  row is never multiplied);
- int8 KV pools: the in-kernel scale folding (attn_qk/attn_pv math)
  matches dequantize-then-attend;
- prefix-cache-hit shaped tables: slots sharing physical history blocks;
- through the engine: greedy token streams ragged ≡ bucketed, bf16 and
  int8 KV, under a speculative draft and with int8 weight-only params,
  including a prefix-cache-hit admission and a swap-in restore;
- the decode compile cache holds exactly ONE variant per sampling-flag
  set on the ragged path (the acceptance bound), while the off-TPU
  fallback is counted in serving_decode_kernel_total — never silent;
- which path serves (``serving.engine.decode_path``), with the backend
  given: the one tier-1 hold on what ``auto`` picks on a TPU.
"""
import dataclasses
import functools
import importlib
from unittest import mock

import numpy as np
import pytest

import paddle_tpu  # noqa: F401  (forces the CPU/virtual-device conftest setup)
import jax
import jax.numpy as jnp

from paddle_tpu.kernels.paged_attention import (PagedKVCache,
                                                paged_attention,
                                                ragged_decode_partial,
                                                ragged_paged_decode)
from paddle_tpu.kernels.quant_matmul import dequantize_kv, quantize_kv
from paddle_tpu.models import deepseek_v2, llama
from paddle_tpu.serving import LLMEngine
from paddle_tpu.serving.engine import decode_path

# kernels/__init__ re-exports a FUNCTION named paged_attention, which
# shadows the module on attribute access
_kernel_mod = importlib.import_module("paddle_tpu.kernels.paged_attention")

BS, HKV, G, D, MB = 4, 2, 2, 16, 4


def _mk(rng, n_slots, dtype, lens, bs=BS, hkv=HKV, g=G, mb=MB):
    nb = n_slots * mb + 1
    kp = jnp.asarray(rng.standard_normal((nb, bs, hkv, D)), dtype)
    vp = jnp.asarray(rng.standard_normal((nb, bs, hkv, D)), dtype)
    table = jnp.asarray(rng.permutation(np.arange(1, nb)).reshape(n_slots,
                                                                  mb),
                        jnp.int32)
    q = jnp.asarray(rng.standard_normal((n_slots, g * hkv, D)), dtype)
    return q, PagedKVCache(kp, vp, table, jnp.asarray(lens, jnp.int32))


# ---------------------------------------------------------------------------
# kernel-level parity (interpret mode)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 3e-2)])
def test_ragged_kernel_matches_dense_reference(dtype, atol):
    """Mixed lengths — 1 token, one exact block, a mid-block tail, and
    the full table — against the XLA gather reference."""
    rng = np.random.default_rng(0)
    q, cache = _mk(rng, 4, dtype, [1, BS, 2 * BS + 3, MB * BS])
    want = paged_attention(q, cache)
    got = ragged_paged_decode(q, cache)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


# the walk moves C blocks per loop iteration; C follows from the shapes
# (kernels/paged_attention._walk_chunk_blocks). The cases below shrink the
# rows a chunk aims at, so that C is 4 at every head count and a table of
# 2C+1 blocks holds two full chunks and a partial one in interpret mode's
# time; the last case runs the rule as it stands.
EDGE_BS, EDGE_G, EDGE_C = 8, 4, 4
EDGE_LENS = {"0": lambda bs, c, mb: 0, "1": lambda bs, c, mb: 1,
             "bs-1": lambda bs, c, mb: bs - 1, "bs": lambda bs, c, mb: bs,
             "C*bs-1": lambda bs, c, mb: c * bs - 1,
             "C*bs": lambda bs, c, mb: c * bs,
             "C*bs+1": lambda bs, c, mb: c * bs + 1,
             "full": lambda bs, c, mb: mb * bs}


# (KV heads, 5-D pools read at layer 1, block size, rows a chunk aims at)
EDGE_CASES = {f"hkv{h}-{'5d-layer1' if layered else '4d'}":
              (h, layered, EDGE_BS, EDGE_C * EDGE_BS * h)
              for h in (8, 4, 2) for layered in (False, True)}
EDGE_CASES["derived-chunk"] = (8, False, 16, _kernel_mod._WALK_ROWS)


@functools.lru_cache(maxsize=None)
def _edge_run(hkv, layered, bs, rows):
    """One walk over a slot per edge length; (got, want, lens, chunk).
    ``layered`` reads plane 1 of 5-D pools whose plane 0 is poison."""
    with mock.patch.object(_kernel_mod, "_WALK_ROWS", rows):
        c = _kernel_mod._walk_chunk_blocks(bs, hkv, D, 4, 10 ** 6)
        mb = 2 * c + 1
        lens = [f(bs, c, mb) for f in EDGE_LENS.values()]
        q, cache = _mk(np.random.default_rng(hkv + 10 * layered), len(lens),
                       jnp.float32, lens, bs=bs, hkv=hkv, g=EDGE_G, mb=mb)
        want = paged_attention(q, cache)
        if layered:
            cache = cache._replace(
                k_pool=jnp.stack([jnp.full_like(cache.k_pool, jnp.nan),
                                  cache.k_pool]),
                v_pool=jnp.stack([jnp.full_like(cache.v_pool, jnp.inf),
                                  cache.v_pool]))
        got = ragged_paged_decode(q, cache, layer=int(layered))
    return np.asarray(got), np.asarray(want), lens, c


@pytest.mark.parametrize("case", list(EDGE_CASES))
@pytest.mark.parametrize("length", list(EDGE_LENS))
def test_ragged_chunk_edge_lengths_match_dense_reference(length, case):
    """One case a length at every edge of the chunked walk: shorter than
    a block, a block, one short of a chunk, a chunk, one past it (the
    second chunk holds one live token), the table's full width (two
    chunks and a partial one) — and the empty slot, which emits 0. At 8,
    4 and 2 KV heads under 4 query heads each, over 4-D pools and 5-D
    ones read at layer 1, with C held at 4; and once with the chunk the
    rule derives by itself (blocks of 16 under 8 KV heads: 8 blocks)."""
    got, want, lens, c = _edge_run(*EDGE_CASES[case])
    assert c == (8 if case == "derived-chunk" else EDGE_C)
    i = list(EDGE_LENS).index(length)
    if lens[i] == 0:
        assert np.all(got[i] == 0.0)
    else:
        np.testing.assert_allclose(got[i], want[i], atol=1e-5)


def test_walk_chunk_follows_the_shapes():
    """C comes from block size, KV heads, head dim, pool dtype and a VMEM
    budget: 1024 flat rows at the cells' shapes and at a tp=2 shard's,
    halved where two chunks of K and V would not fit, never wider than
    the table."""
    chunk = _kernel_mod._walk_chunk_blocks
    assert chunk(16, 8, 128, 2, 160) == 8
    assert chunk(16, 4, 128, 2, 160) == 16
    assert chunk(64, 8, 128, 2, 40) == 2
    assert chunk(16, 8, 128, 2, 3) == 3
    assert chunk(256, 8, 128, 2, 40) == 1
    big = chunk(16, 8, 512, 4, 160)              # 4 MiB would be 16 MiB
    assert big < 8
    assert 4 * big * 16 * 8 * 512 * 4 <= _kernel_mod._WALK_VMEM_BYTES


def test_ragged_masked_tail_bit_exact():
    """Poisoning every position past each slot's length (the last
    block's tail AND whole out-of-range blocks) must not change a single
    bit: masked columns underflow to an exact 0.0 and skipped blocks are
    never read."""
    rng = np.random.default_rng(1)
    lens = [3, BS + 1, 2 * BS]
    q, cache = _mk(rng, 3, jnp.float32, lens)
    clean = ragged_paged_decode(q, cache)
    kp = np.array(cache.k_pool)
    vp = np.array(cache.v_pool)
    for n, ln in enumerate(lens):
        tbl = np.asarray(cache.block_table[n])
        for b in range(MB):
            lo = max(0, ln - b * BS)
            kp[tbl[b], lo:] = 1e4      # garbage tail / whole block
            vp[tbl[b], lo:] = -1e4
    poisoned = ragged_paged_decode(q, PagedKVCache(
        jnp.asarray(kp), jnp.asarray(vp), cache.block_table, cache.lengths))
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(poisoned))


@pytest.mark.parametrize("dead", ["trash-block", "stale-blocks"])
def test_ragged_masked_tail_bit_exact_inside_the_last_chunk(dead):
    """The chunked walk fetches whole chunks of C blocks, and a slot's
    last chunk reaches past its length. What lies there — the trash block
    0 every dead table entry points at in the engine, or blocks another
    request left behind — may hold anything: NaN and inf there, and
    finite garbage in the last live block's tail, leave every bit of the
    output as it was. (0.0 times a NaN V row would be NaN: the dead rows
    of a chunk are never multiplied as they were fetched.)"""
    bs, hkv, c = 4, 2, 4
    mb = 2 * c + 1
    # one block of a chunk, all but one, one token into the second chunk,
    # the second chunk's last block but one
    lens = [3, (c - 1) * bs, c * bs + 1, (2 * c - 1) * bs - 2]
    q, cache = _mk(np.random.default_rng(9), len(lens), jnp.float32, lens,
                   bs=bs, hkv=hkv, g=2, mb=mb)
    kp, vp = np.array(cache.k_pool), np.array(cache.v_pool)
    tbl = np.array(cache.block_table)
    if dead == "trash-block":
        for i, ln in enumerate(lens):
            tbl[i, -(-ln // bs):] = 0

    def run(kp, vp):
        with mock.patch.object(_kernel_mod, "_WALK_ROWS", c * bs * hkv):
            assert _kernel_mod._walk_chunk_blocks(bs, hkv, D, 4, mb) == c
            return np.asarray(ragged_paged_decode(q, PagedKVCache(
                jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tbl),
                jnp.asarray(lens, jnp.int32))))

    clean = run(kp, vp)
    assert np.isfinite(clean).all()
    kp, vp = kp.copy(), vp.copy()
    kp[0], vp[0] = np.nan, np.inf                    # the trash block
    for i, ln in enumerate(lens):
        for b in range(mb):
            lo = max(0, ln - b * bs)
            if lo == 0 and tbl[i, b]:                # a whole dead block
                kp[tbl[i, b]], vp[tbl[i, b]] = np.inf, np.nan
            elif lo < bs:                            # the live block's tail
                kp[tbl[i, b], lo:], vp[tbl[i, b], lo:] = 1e4, -1e4
    np.testing.assert_array_equal(clean, run(kp, vp))


def test_ragged_int8_matches_dequant_reference():
    """int8 pools stream unconverted; the per-entry K scale multiplies
    the scores and the V scale folds into the probabilities — the result
    must match dequantizing the pools first (the attn_qk/attn_pv
    contract, in-kernel)."""
    rng = np.random.default_rng(2)
    q, cache = _mk(rng, 3, jnp.float32, [2, BS + 3, 3 * BS])
    qk, ks = quantize_kv(cache.k_pool)
    qv, vs = quantize_kv(cache.v_pool)
    got = ragged_paged_decode(q, PagedKVCache(qk, qv, cache.block_table,
                                              cache.lengths),
                              ks_pool=ks, vs_pool=vs)
    want = paged_attention(q, PagedKVCache(
        dequantize_kv(qk, ks, jnp.float32),
        dequantize_kv(qv, vs, jnp.float32),
        cache.block_table, cache.lengths))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_ragged_shared_history_blocks():
    """Prefix-cache-hit shape: two slots pin the SAME physical history
    blocks (refcounted trie nodes) and diverge in their private tails —
    the walk reads shared blocks per slot, no aliasing surprises."""
    rng = np.random.default_rng(3)
    q, cache = _mk(rng, 2, jnp.float32, [2 * BS + 2, 3 * BS + 1])
    tbl = np.array(cache.block_table)
    tbl[1, :2] = tbl[0, :2]            # shared 2-block history
    cache = PagedKVCache(cache.k_pool, cache.v_pool, jnp.asarray(tbl),
                         cache.lengths)
    np.testing.assert_allclose(np.asarray(ragged_paged_decode(q, cache)),
                               np.asarray(paged_attention(q, cache)),
                               atol=1e-5)


def test_ragged_layered_pool_layer_select_and_zero_length():
    """The engine's pools are [L, NB, BS, Hkv, D]: ``layer`` must select
    the right plane; a zero-length slot emits exactly 0 (the combine
    identity) and the partial state (acc=0, m=-1e30, l=0)."""
    rng = np.random.default_rng(4)
    q, cache = _mk(rng, 2, jnp.float32, [0, BS + 2])
    kp = jnp.stack([jnp.zeros_like(cache.k_pool), cache.k_pool])
    vp = jnp.stack([jnp.zeros_like(cache.v_pool), cache.v_pool])
    got = ragged_paged_decode(q, PagedKVCache(kp, vp, cache.block_table,
                                              cache.lengths), layer=1)
    want = paged_attention(q, cache)
    assert np.all(np.asarray(got[0]) == 0.0)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                               atol=1e-5)
    acc, m, l = ragged_decode_partial(q, kp, vp, cache.block_table,
                                      cache.lengths, layer=1)
    assert np.all(np.asarray(acc[0]) == 0.0)
    assert np.all(np.asarray(l[0]) == 0.0)
    assert np.all(np.asarray(m[0]) == -1e30)


# ---------------------------------------------------------------------------
# engine integration: ragged ≡ bucketed greedy streams, one variant
# ---------------------------------------------------------------------------
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
def test_engine_greedy_streams_ragged_equals_bucketed(model, kv):
    """The acceptance parity: greedy token streams through the ragged
    kernel are bit-identical to the bucketed path's, bf16-config and
    int8-KV, over mixed lengths incl. a 1-token prompt and an exact
    block-boundary prompt."""
    cfg, params = model
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 64, size=n).tolist() for n in (1, 8, 13)]
    a, _ = _streams(params, cfg, "bucketed", prompts, (6, 5, 6),
                    kv_dtype=kv)
    b, eng = _streams(params, cfg, "ragged", prompts, (6, 5, 6),
                      kv_dtype=kv)
    assert a == b
    assert all(k[0] == "ragged" for k in eng._decode_cache)


def test_engine_ragged_spec_draft_parity(model):
    """Speculation on the ragged path: the draft program walks the DRAFT's
    pools at their true lengths (``ServeOpts(ragged=True, prefix="d")``),
    and the committed streams equal the bucketed engine's waves'."""
    cfg, params = model
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, 64, size=n).tolist() for n in (4, 11)]

    def run(kernel):
        out, eng = _streams(params, cfg, kernel, prompts, (6, 6),
                            draft_params=params, draft_config=cfg,
                            spec_tokens=3)
        assert eng.spec_waves >= 1
        return out, eng

    a, _ = run("bucketed")
    b, eng = run("ragged")
    assert a == b
    assert "ragged" in eng._spec_draft_cache     # the draft walked too


def test_engine_ragged_int8_weights_parity(model):
    """int8 weight-only params feed the projections around the walk
    unconverted; the streams equal the bucketed path's."""
    cfg, params = model
    qp = llama.quantize_params(params)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 64, size=n).tolist() for n in (5, 13)]
    a, _ = _streams(qp, cfg, "bucketed", prompts, (6, 6))
    b, eng = _streams(qp, cfg, "ragged", prompts, (6, 6))
    assert a == b
    assert all(k[0] == "ragged" for k in eng._decode_cache)


def test_engine_ragged_prefix_cache_hit_parity(model):
    """A finished prompt re-sent through the prefix cache (pinned
    history blocks, suffix-only prefill) must stream the same tokens on
    both decode paths — the cached history folds into the same
    true-length walk, no special prefix_nbk axis."""
    cfg, params = model
    rng = np.random.default_rng(5)
    prompt = rng.integers(1, 64, size=17).tolist()

    def run(kernel):
        eng = LLMEngine(params, cfg, max_slots=2, block_size=8,
                        max_model_len=64, prompt_buckets=[8, 32],
                        decode_steps=2, kv_dtype="int8",
                        prefix_cache=True, decode_kernel=kernel)
        r1 = eng.add_request(prompt, max_new_tokens=5)
        eng.run()
        r2 = eng.add_request(prompt, max_new_tokens=5)  # cache hit
        out = eng.run()
        assert eng.prefix_cache.hits >= 1
        return out[r1], out[r2]

    assert run("bucketed") == run("ragged")


def test_engine_ragged_chunked_prefill_parity(model):
    """Chunked prefill interleaved with decode waves: mid-chunk slots
    are excluded from the ragged walk (zeroed lengths) until their
    final chunk lands, and the streams match the bucketed path."""
    cfg, params = model
    rng = np.random.default_rng(8)
    long_p = rng.integers(1, 64, size=26).tolist()
    short_p = rng.integers(1, 64, size=5).tolist()

    def run(kernel):
        eng = LLMEngine(params, cfg, max_slots=2, block_size=8,
                        max_model_len=64, prompt_buckets=[8, 32],
                        decode_steps=2, prefix_cache=True,
                        prefill_chunk=8, decode_kernel=kernel)
        r1 = eng.add_request(short_p, max_new_tokens=8)
        r2 = eng.add_request(long_p, max_new_tokens=4)
        out = eng.run()
        return out[r1], out[r2]

    assert run("bucketed") == run("ragged")


def test_engine_ragged_swap_in_parity(model):
    """Pool pressure preempts the newest slot into the host KV tier;
    its swap-in restore (bit-exact blocks, no re-prefill) must continue
    the stream identically under the ragged kernel."""
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

    assert run("bucketed") == run("ragged")


def test_engine_ragged_one_variant_per_flag_set(model):
    """The acceptance bound: across mixed and GROWING lengths the ragged
    decode cache never grows a length axis — exactly one compiled
    variant per sampling-flag set, while the same workload compiles
    multiple prefix buckets on the bucketed path."""
    cfg, params = model
    rng = np.random.default_rng(7)

    def run(kernel):
        eng = LLMEngine(params, cfg, max_slots=2, block_size=8,
                        max_model_len=128, prompt_buckets=[8, 32],
                        decode_steps=2, decode_kernel=kernel)
        for i, (n, k) in enumerate(((2, 4), (10, 6), (30, 8))):
            eng.add_request(rng.integers(1, 64, size=n).tolist(),
                            max_new_tokens=k)
            eng.run()          # separate runs force horizon growth
        return eng

    ragged = run("ragged")
    assert len(ragged._decode_cache) == 1, sorted(ragged._decode_cache)
    assert all(k[0] == "ragged" for k in ragged._decode_cache)
    bucketed = run("bucketed")
    assert len(bucketed._decode_cache) > 1       # the family ragged kills
    # a sampled request adds exactly one more flag-set variant
    ragged.add_request(rng.integers(1, 64, size=5).tolist(),
                       max_new_tokens=3, temperature=0.9)
    ragged.run()
    assert len(ragged._decode_cache) == 2, sorted(ragged._decode_cache)


def test_engine_fallback_counted_never_silent(model):
    """decode_kernel="auto" off-TPU serves the bucketed path and COUNTS
    it in serving_decode_kernel_total{path}; serving_decode_variants
    mirrors the compile cache."""
    import paddle_tpu.observability as obs

    cfg, params = model
    obs.get_registry().reset()
    obs.enable()
    try:
        eng = LLMEngine(params, cfg, max_slots=2, block_size=8,
                        max_model_len=128, prompt_buckets=[8])
        assert eng._decode_path() == "bucketed"    # the CPU, under tier-1
        eng.add_request(list(range(1, 6)), max_new_tokens=4)
        eng.run()
        reg = obs.get_registry()
        c = reg.counter("serving_decode_kernel_total")
        assert c.labels(path="bucketed").value \
            + c.labels(path="dense").value >= 1
        assert c.labels(path="ragged").value == 0
        assert reg.gauge("serving_decode_variants").labels().value \
            == len(eng._decode_cache) >= 1
        assert eng.kv_read_bytes_total > 0
    finally:
        obs.disable()
        obs.get_registry().reset()


# ---------------------------------------------------------------------------
# which path: the rule the cells depend on, with the backend GIVEN (every
# tier-1 run detects "cpu", so the TPU's side is held here and nowhere else)
# ---------------------------------------------------------------------------
def _dense(head_dim):
    return llama.LlamaConfig(hidden_size=4 * head_dim, num_heads=4,
                             num_kv_heads=2, head_dim=head_dim,
                             num_layers=1).served_model()


SERVED = {"dense128": _dense(128), "dense64": _dense(64),
          "latent": deepseek_v2.DeepseekV2Config(num_layers=1).served_model()}
INT8_MESSAGE = "Slice shape along dimension 3 must be aligned to tiling"
NAMES_THE_THREE = "'auto', 'ragged' or 'bucketed'"


@pytest.mark.parametrize("asked,backend,model,kv,want", [
    ("auto", "tpu", "dense128", "bf16", "ragged"),
    ("auto", "tpu", "dense64", "bf16", "bucketed"),
    ("auto", "tpu", "dense128", "int8", "bucketed"),
    ("ragged", "tpu", "dense128", "int8", INT8_MESSAGE),
    ("ragged", "tpu", "dense64", "bf16", "dimension 4 must be aligned"),
    ("ragged", "tpu", "dense128", "bf16", "ragged"),
    ("bucketed", "tpu", "dense128", "bf16", "bucketed"),
    ("auto", "cpu", "dense128", "bf16", "bucketed"),
    ("ragged", "cpu", "dense128", "int8", "ragged"),
    ("ragged", "cpu", "dense64", "bf16", "ragged"),
    ("auto", "tpu", "latent", "bf16", "ragged"),
    ("auto", "cpu", "latent", "bf16", "bucketed"),
    ("fused", "tpu", "dense128", "bf16", NAMES_THE_THREE),
    ("fused", "cpu", "dense128", "bf16", NAMES_THE_THREE),
    ("fused", "cpu", "latent", "bf16", NAMES_THE_THREE),
])
def test_decode_path_is_one_rule(asked, backend, model, kv, want):
    """``auto`` on a TPU walks where Mosaic compiles the walk and gathers
    where it refuses; off a TPU it gathers; a path asked for by name is
    taken or, at a shape the TPU's compiler refuses, an error with the
    compiler's message; any other name (the withdrawn fused kernel's
    among them) is a ValueError naming the three."""
    ask = functools.partial(decode_path, asked, backend, SERVED[model],
                            kv == "int8")
    if want in ("ragged", "bucketed"):
        assert ask() == want
    else:
        error = ValueError if want == NAMES_THE_THREE else NotImplementedError
        with pytest.raises(error, match=want):
            ask()


@pytest.fixture
def on_a_tpu(monkeypatch):
    """The engine detects a TPU; nothing is compiled or run."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _engine(cfg, **kw):
    return LLMEngine({}, cfg, max_slots=2, block_size=8, max_model_len=64,
                     **kw)


def test_engine_asks_the_rule_for_target_and_draft(on_a_tpu):
    """A target at head dim 128 with a draft off 128, ``auto`` on a TPU:
    the target walks and the draft gathers, each by its own shape. By
    name the draft's refusal is the constructor's error."""
    target, draft = SERVED["dense128"].config, SERVED["dense64"].config
    eng = _engine(target, draft_params={}, draft_config=draft)
    assert eng._decode_path() == "ragged"
    assert eng._decode_path(draft=True) == "bucketed"
    assert _engine(target, kv_dtype="int8")._decode_path() == "bucketed"
    with pytest.raises(NotImplementedError, match="dimension 4"):
        _engine(target, draft_params={}, draft_config=draft,
                decode_kernel="ragged")
    with pytest.raises(NotImplementedError, match=INT8_MESSAGE):
        _engine(target, kv_dtype="int8", decode_kernel="ragged")


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
@pytest.mark.parametrize("family", ["dense128", "latent"])
def test_engine_refuses_an_unknown_decode_kernel(family, backend,
                                                 monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    with pytest.raises(ValueError, match=NAMES_THE_THREE):
        _engine(SERVED[family].config, decode_kernel="fused")
