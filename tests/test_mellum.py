"""Mellum2 (``mellum``) through ``LLMEngine`` on the CPU, small and seeded,
against the plain reference ``benchmark/reference/mellum_f32.py`` (which
imports nothing of the program): the served tokens through BOTH kinds of
cache, across the window's edge, pieces' boundaries, a slot's reuse and a
preemption; the two kernels' new operands against a dense masked softmax;
YaRN's table, the router and the grouped matmul's tile by the formulas of
ISSUE 35; and what the engine refuses."""
import functools
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest, weights
from benchmark.reference import mellum_f32 as ref
from paddle_tpu.kernels.paged_attention import flat_decode_partial
from paddle_tpu.kernels.pallas_attention import flash_partial
from paddle_tpu.models import deepseek_v2, mellum, rope
from paddle_tpu.serving import LLMEngine

md = importlib.import_module("paddle_tpu.kernels.moe_dispatch")
FAM = manifest.load_family("mellum")
PUBLISHED = manifest.Manifest().config("mellum2-12b-a2.5b-serve")
BASE = {k: PUBLISHED[k] for k in (
    "family", "kind", "attention_bias", "tie_word_embeddings",
    "use_sliding_window", "norm_topk_prob", "rms_norm_eps",
    "rope_parameters")}
# one period (three window layers and a full one), a window of 32 tokens
# over blocks of 8: a ring of five blocks
MODEL = {**BASE, **FAM.tiny(BASE), "sliding_window": 32}
W, BS = 32, 8
KEY = weights.seed_key(7)
F32, BF16 = jnp.float32, jnp.bfloat16
# contexts of 0.5, 1, 1.5 and 5 windows, and one under a block
PROMPTS = (16, 32, 48, 160, 5)


@functools.lru_cache(maxsize=None)
def _params():
    return jax.jit(lambda k: FAM.make_params(MODEL, k, F32))(KEY)


def _engine(max_slots=3, **kw):
    cfg = FAM.program_config(MODEL, max_seq_len=256, dtype=F32)
    kw.setdefault("prompt_buckets", [16, 32])
    return LLMEngine(_params(), cfg, max_slots=max_slots, block_size=BS,
                     max_model_len=256, seed=0, **kw)


def _prompts(lens, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, size=n).tolist() for n in lens]


def _served(kw, n_new=40, lens=PROMPTS, max_slots=3):
    prompts = _prompts(lens)
    eng = _engine(max_slots, **kw)
    ids = [eng.add_request(p, max_new_tokens=n_new) for p in prompts]
    res = eng.run()
    return eng, prompts, [res[i] for i in ids]


def _reference_gaps(prompts, served, model=MODEL):
    """For each served position, how far the served token's reference
    LOGIT lies below the reference's best (the full forward pass over
    prompt + served tokens: no cache, no ring, no pieces)."""
    top = {n: FAM.make_top(model, KEY, n, F32)
           for n in ("embed", "head", "final_norm")}
    gaps = []
    with jax.default_matmul_precision("highest"):
        layers = [FAM.make_layer(model, KEY, l, F32)
                  for l in range(model["num_hidden_layers"])]
        for p, out in zip(prompts, served):
            x = FAM.reference.embed(jnp.asarray([p + out]), top)
            for l, lp in enumerate(layers):
                x = ref.layer(x, lp, model, None, l)
            lg = ref.head_logits(x[0], top, model)[len(p) - 1:-1]
            tok = jnp.asarray(out)
            gaps.append(np.asarray(
                lg.max(-1) - jnp.take_along_axis(lg, tok[:, None], 1)[:, 0]))
    return np.concatenate(gaps)


# a float32 engine against the float32-highest reference: the two differ by
# summation order alone, so a served token lies below the reference's best
# only where two logits are 1e-5 apart. A window layer that sees one block
# too much, or all of its history, moves the served tokens by whole logits
# (the planted faults below read 0.9 and more on the mean)
GAP_LIMIT = 1e-3


@pytest.mark.parametrize("kw", [
    # whole prompts: 48 and 160 take the max_model_len bucket, which is
    # LONGER than the window, so the piece itself is banded
    dict(),
    # pieces of W: every piece sees its own chunk and the W - 1 before it
    dict(prefill_chunk=32),
    # pieces of W / 2
    dict(prefill_chunk=16),
    # pieces of 24 in blocks of 8 with buckets of 16 and 32: a length that
    # is no multiple of the window, the last piece ends INSIDE a block
    dict(prefill_chunk=24),
    # the walk kernel with a start (interpreted), over the ring as it lies
    dict(prefill_chunk=32, decode_kernel="ragged"),
    # two tokens a decode call, five slots
    dict(prefill_chunk=32, decode_steps=2, max_slots=5),
    dict(prefill_chunk=16, decode_steps=2, decode_kernel="ragged")],
    ids=["whole", "pieces-of-w", "pieces-of-half-w", "pieces-off-the-block",
         "walk-with-a-start", "two-steps-five-slots", "two-steps-walk"])
def test_served_tokens_agree_with_the_reference(kw):
    """Prefill (whole or in pieces), then 40 decoded tokens through both
    kinds of cache (every context crosses the window's edge or starts
    beyond it), against the reference's full forward pass at every
    position."""
    kw = dict(kw)
    eng, prompts, served = _served(kw, max_slots=kw.pop("max_slots", 3))
    assert all(len(s) == 40 for s in served)
    acc = eng.block_accounting()
    assert acc["backed"] == 0 and acc["window"]["backed"] == 0
    assert eng.win.recycled > 0            # blocks were written again
    gaps = _reference_gaps(prompts, served)
    assert gaps.max() <= GAP_LIMIT, gaps.max()


@pytest.mark.parametrize("fault", ["whole-history", "one-block-too-much",
                                   "no-yarn"])
def test_planted_faults_are_seen(fault, monkeypatch):
    """What ``correct`` must call not correct on the chip, here in float32:
    a window layer that attends to its whole history, one that reads one
    block too much, YaRN's factor left off the full layers."""
    init = mellum.MellumServed.__init__

    def wide(self, config, window):
        init(self, config)
        self.window = window

    if fault == "whole-history":
        monkeypatch.setattr(mellum.MellumServed, "__init__",
                            lambda s, c: wide(s, c, 256))
    elif fault == "one-block-too-much":
        monkeypatch.setattr(mellum.MellumServed, "__init__",
                            lambda s, c: wide(s, c, W + BS))
    else:
        freqs = mellum.MellumServed._freqs
        monkeypatch.setattr(
            mellum.MellumServed, "_freqs",
            lambda s: {**freqs(s), "f": (freqs(s)["f"][0], 1.0)})
    _eng, prompts, served = _served(dict(prefill_chunk=32), lens=(48, 160))
    gaps = _reference_gaps(prompts, served)
    assert gaps.mean() > 0.01 and gaps.max() > 0.1, (gaps.mean(), gaps.max())


def test_a_slot_reused_by_a_shorter_request_sees_nothing_of_the_old_window():
    """One slot: a long request fills the ring and writes blocks again in
    place, then a short one takes the slot (and the same physical blocks):
    its tokens are the reference's, as if the slot were new."""
    eng = _engine(max_slots=1, prefill_chunk=32)
    long_, short = _prompts((160, 11), seed=9)
    a = eng.add_request(long_, max_new_tokens=30)
    b = eng.add_request(short, max_new_tokens=30)
    res = eng.run()
    assert eng.win.recycled > 0
    gaps = _reference_gaps([long_, short], [res[a], res[b]])
    assert gaps.max() <= GAP_LIMIT, gaps.max()


def test_preemption_by_recompute_and_readmission():
    """A full pool too small for three growing requests: the newest is
    preempted (both ledgers freed), admitted again, prefilled over prompt +
    generated, and every served token is still the reference's."""
    prompts = _prompts((40, 44, 36), seed=4)
    eng = _engine(max_slots=3, prefill_chunk=32, num_blocks=22)
    ids = [eng.add_request(p, max_new_tokens=48) for p in prompts]
    preempted = False
    while eng.has_work():
        eng.step()
        preempted |= any(r.generated for r in eng.queue)
    assert preempted
    served = [eng.results[i] for i in ids]
    assert all(len(s) == 48 for s in served)
    gaps = _reference_gaps(prompts, served)
    assert gaps.max() <= GAP_LIMIT, gaps.max()


def test_spans_and_counters_of_the_window(monkeypatch):
    import paddle_tpu.observability as obs
    from paddle_tpu.observability import get_tracer

    obs.enable()
    try:
        before = obs.snapshot()
        eng, _p, _s = _served(dict(prefill_chunk=32), n_new=12,
                              lens=(70, 20))
        snap = obs.snapshot()
    finally:
        obs.disable()
    spans = [s for s in get_tracer().spans()]
    dec = [s.attrs for s in spans if s.name == "serving.decode"
           and "window_bytes" in s.attrs]
    assert dec and all(0 < a["window_bytes"] < a["kv_bytes"] for a in dec)
    # (off a TPU the whole ring of every slot is gathered dense)
    assert all(a["window_walk_blocks"] == eng.N * (W // BS + 1) for a in dec)
    pre = [s.attrs for s in spans if s.name == "serving.prefill"
           and "hist_window" in s.attrs]
    # the pieces of the 70-token prompt start at 0, 32, 64: a window layer
    # gathers min(start, W - 1) tokens of history
    assert sorted({(a["start"][0], a["hist_window"]) for a in pre}) >= [
        (0, 0), (32, 31), (64, 31)]
    value = lambda snap, name: sum(
        s["value"] for m in snap["metrics"] if m["name"] == name
        for s in m["series"])
    # three window layers, a ring of five blocks of 8 tokens, K and V of
    # two heads of 64 in float32
    assert value(snap, "serving_window_bytes_per_slot") == \
        3 * 5 * 8 * 2 * 2 * 64 * 4
    assert value(snap, "serving_kv_bytes_per_token") == 2 * 2 * 64 * 4
    assert value(snap, "serving_window_blocks_recycled_total") \
        - value(before, "serving_window_blocks_recycled_total") \
        == eng.win.recycled > 0


# -- what the engine refuses --------------------------------------------------
@pytest.mark.parametrize("kw,feature", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(kv_swap_bytes=1 << 20), "kv_swap"),
    (dict(kv_dtype="int8"), "kv_int8"),
    (dict(role="decode"), "disagg"),
    (dict(draft_params={}, draft_config=object()), "spec")])
def test_unsupported_features_are_refused_with_their_reason(kw, feature):
    with pytest.raises(NotImplementedError, match=feature):
        _engine(**kw)


def test_a_model_of_one_kind_of_layer_is_refused():
    cfg = FAM.program_config(dict(MODEL, num_hidden_layers=3))
    with pytest.raises(ValueError, match="both kinds"):
        cfg.served_model()
    with pytest.raises(ValueError):
        FAM.program_config(dict(MODEL, tie_word_embeddings=True))


# -- the kernels' new operands, against a dense masked softmax ---------------
def _dense_attention(q, k, v, keep, scale):
    """q [Hq, D], k/v [T, Hkv, D], keep [T] -> [Hq, D] (float64)."""
    G = q.shape[0] // k.shape[1]
    out = np.zeros(q.shape, np.float64)
    for h in range(q.shape[0]):
        s = np.where(keep, k[:, h // G] @ q[h] * scale, -np.inf)
        p = np.exp(s - s.max())
        out[h] = (p / p.sum()) @ v[:, h // G]
    return out


@pytest.mark.parametrize("lens", [(0, 5, 32, 47, 163), (33, 64, 65, 8, 200)])
def test_the_walk_with_a_start_over_a_ring(lens):
    """``flat_decode_partial`` with ``starts`` (interpreted): a slot's
    logical block b in column b % ring, stale rows behind and ahead of the
    window in the ring's blocks, against a dense softmax over [start,
    len)."""
    rng = np.random.default_rng(1)
    N, Hq, Hkv, D, ring = len(lens), 8, 2, 64, W // BS + 1
    lens = np.asarray(lens)
    starts = np.maximum(0, lens - W + 1)
    pool = rng.standard_normal((1, 1 + N * ring, BS, 2 * Hkv * D)
                               ).astype(np.float32)
    q = rng.standard_normal((N, Hq, D)).astype(np.float32)
    table = 1 + np.arange(N * ring, dtype=np.int32).reshape(N, ring)
    acc, m, l = flat_decode_partial(
        jnp.asarray(q), jnp.asarray(pool), jnp.asarray(table),
        jnp.asarray(lens), n_kv=Hkv, starts=jnp.asarray(starts), name="w")
    for n in range(N):
        if lens[n] == 0:
            assert float(jnp.max(l[n])) == 0.0     # the combine's identity
            continue
        pos = np.arange(int(starts[n]) // BS * BS, int(lens[n]))
        rows = np.stack([pool[0, table[n, (p // BS) % ring], p % BS]
                         for p in pos]).reshape(len(pos), 2, Hkv, D)
        want = _dense_attention(q[n], rows[:, 1], rows[:, 0],
                                pos >= starts[n], 1 / math.sqrt(D))
        got = np.asarray(acc[n] / l[n][..., None]).reshape(Hq, D)
        assert np.abs(got - want).max() < 1e-4, n


def test_the_walk_without_a_start_is_the_parents_kernel(monkeypatch):
    """No ``starts``: three scalar operands and no ring, as before (the
    kernel lowered, not interpreted, so that the call is in the text)."""
    pa = importlib.import_module("paddle_tpu.kernels.paged_attention")
    monkeypatch.setattr(pa, "_interpret", lambda: False)
    rng = np.random.default_rng(2)
    pool = jnp.asarray(rng.standard_normal((1, 9, BS, 256)), F32)
    q = jnp.asarray(rng.standard_normal((2, 4, 64)), F32)
    table = jnp.arange(1, 9, dtype=jnp.int32).reshape(2, 4)
    walk = functools.partial(flat_decode_partial, n_kv=2)

    def operands(jaxpr):
        """Operand counts of the Pallas calls, nested ones too."""
        out = []
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                out.append(len(eqn.invars))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                out += operands(sub)
        return out

    lens = jnp.asarray([9, 30])
    # layer, table, lengths, the queries, the pool; and the starts
    assert operands(jax.make_jaxpr(walk)(q, pool, table, lens).jaxpr) == [5]
    assert operands(jax.make_jaxpr(walk)(
        q, pool, table, lens, starts=jnp.asarray([0, 3])).jaxpr) == [6]


@pytest.mark.parametrize("causal,lo", [(False, (80 - W + 1, 10)),
                                       (False, (-5, 300)),
                                       (True, (1 - W, 1 - W))])
def test_the_banded_flash_partial(causal, lo):
    """``flash_partial`` with ``band_lo`` (interpreted, tiles of 16):
    row r sees column c only where c >= r + lo, beside the key length and
    the causal flag; rows whose band holds no key read -1e30."""
    rng = np.random.default_rng(3)
    G, Gk, S, T, D = 4, 2, 64, 64 if causal else 96, 128
    q = rng.standard_normal((G, S, D)).astype(np.float32)
    k = rng.standard_normal((Gk, T, D)).astype(np.float32)
    v = rng.standard_normal((Gk, T, D)).astype(np.float32)
    kv_len = np.asarray([T if causal else 80, T])
    o, lse = flash_partial(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), scale=0.1,
        causal=causal, kv_len=jnp.asarray(kv_len), band_lo=jnp.asarray(lo),
        block_q=16, block_kv=16)
    r, c = np.arange(S)[:, None], np.arange(T)[None, :]
    for g in range(G):
        gk = g // 2
        keep = (c < kv_len[gk]) & (c >= r + lo[gk])
        if causal:
            keep &= c <= r
        s = np.where(keep, q[g] @ k[gk].T * 0.1, -np.inf)
        has = keep.any(axis=1)
        p = np.exp(s - np.where(has, s.max(-1), 0.0)[:, None])
        want = (p / np.maximum(p.sum(-1, keepdims=True), 1e-30)) @ v[gk]
        # (lo = 300 leaves a group with no key at all)
        assert np.abs(want - np.asarray(o[g]))[has].max(initial=0.0) < 1e-4
        assert (np.asarray(lse[g])[~has] <= -1e29).all()


# -- the formulas of ISSUE 35, section 1 ---------------------------------------
def test_yarn_s_table_at_factor_16():
    inv = np.asarray(rope.yarn_frequencies(128, 500000.0, 16.0, 8192, 32.0,
                                           1.0))
    i = np.arange(64)
    f = 500000.0 ** (-2.0 * i / 128)
    low = math.floor(128 * math.log(8192 / (32 * 2 * math.pi))
                     / (2 * math.log(500000)))
    high = math.ceil(128 * math.log(8192 / (1 * 2 * math.pi))
                     / (2 * math.log(500000)))
    assert (low, high) == (18, 35)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    want = f / 16 * ramp + f * (1 - ramp)
    assert np.allclose(inv, want, rtol=1e-6)
    assert np.allclose(inv[:19], f[:19], rtol=1e-6)       # fast dims kept
    assert np.allclose(inv[35:], f[35:] / 16, rtol=1e-6)  # slow ones cut
    # the reference computes the same table on its own
    got, factor = ref.inv_freq(PUBLISHED, "full_attention")
    assert np.allclose(np.asarray(got), want, rtol=1e-6)
    assert factor == 1.2772588722239782 == pytest.approx(
        0.1 * math.log(16) + 1)
    plain, one = ref.inv_freq(PUBLISHED, "sliding_attention")
    assert np.allclose(np.asarray(plain), f, rtol=1e-6) and one == 1.0
    # the model's two tables are these two
    fr = FAM.program_config(PUBLISHED).served_model()._freqs()
    assert np.allclose(np.asarray(fr["f"][0]), want, rtol=1e-6)
    assert np.allclose(np.asarray(fr["w"][0]), f, rtol=1e-6)
    assert (fr["w"][1], fr["f"][1]) == (1.0, 1.2772588722239782)


def test_deepseek_v2_s_yarn_is_the_shared_one():
    c = deepseek_v2.DeepseekV2Config()
    inv, mscale = deepseek_v2.yarn_inv_freq(c)
    want = rope.yarn_frequencies(c.qk_rope_head_dim, c.rope_theta,
                                 c.rope_factor, c.rope_original_max,
                                 c.rope_beta_fast, c.rope_beta_slow)
    assert np.array_equal(np.asarray(inv), np.asarray(want))
    assert mscale == 1.0


def test_the_router_renormalised_ties_to_the_lower_index():
    """``routing_from_logits`` against section 1: softmax in float32 over
    all experts, top-k, divided by the chosen ones' sum; equal logits go
    to the lower index; and the reference's gates are the same."""
    logits = jnp.asarray([[2.0, 1.0, 2.0, 0.0, 2.0, -1.0],
                          [0.0, 0.0, 0.0, 0.0, 0.0, 0.0],
                          [5.0, 4.0, 3.0, 2.0, 1.0, 0.0]], jnp.bfloat16)
    r = md.routing_from_logits(logits, 2)
    assert r.weights.dtype == jnp.float32
    assert np.asarray(r.idx).tolist() == [[0, 2], [0, 1], [0, 1]]
    p = np.exp(np.asarray(logits, np.float64))
    p /= p.sum(-1, keepdims=True)
    want = np.take_along_axis(p, np.asarray(r.idx), 1)
    want /= want.sum(-1, keepdims=True)
    assert np.allclose(np.asarray(r.weights), want, atol=1e-6)
    assert np.allclose(np.asarray(r.weights).sum(-1), 1.0, atol=1e-6)
    gates, idx = ref.route(logits, 2, True)
    assert np.asarray(idx).tolist() == np.asarray(r.idx).tolist()
    assert np.allclose(np.asarray(gates), np.asarray(r.weights), atol=1e-6)


@pytest.mark.parametrize("kind,quant", [
    ("sliding_attention", None), ("sliding_attention", "one_block"),
    ("sliding_attention", "whole_history"), ("full_attention", None)])
def test_the_reference_reads_the_keys_its_mask_lets_it_see(kind, quant):
    """The reference's attention gives a block of query rows only the
    stretch of keys that its layer's mask can let them see: against one
    softmax over ALL keys under the mask of section 1, in float64, at a
    context of four blocks of rows and a window of 40."""
    S, H, Hkv, d, h, W = 4 * ref.ATTN_BLOCK, 4, 2, 16, 32, 40
    m = {"num_attention_heads": H, "num_key_value_heads": Hkv, "head_dim": d,
         "rms_norm_eps": 1e-6, "sliding_window": W,
         "rope_parameters": {k: {"rope_type": "default", "rope_theta": 1e4}
                             for k in ("sliding_attention",
                                       "full_attention")}}
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    p = {"wq": jax.random.normal(ks[0], (h, H * d)) / 6,
         "wk": jax.random.normal(ks[1], (h, Hkv * d)) / 6,
         "wv": jax.random.normal(ks[2], (h, Hkv * d)) / 6,
         "wo": jnp.eye(H * d), "q_norm": jnp.ones(d), "k_norm": jnp.ones(d)}
    hn = jax.random.normal(ks[3], (1, S, h))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.attention(hn, p, m, quant, kind))[0]
        inv, f = ref.inv_freq(m, kind)
        q = ref.rope_half(ref.rms_norm((hn @ p["wq"]).reshape(1, S, H, d),
                                       p["q_norm"], 1e-6), inv, f)
        k = ref.rope_half(ref.rms_norm((hn @ p["wk"]).reshape(1, S, Hkv, d),
                                       p["k_norm"], 1e-6), inv, f)
        v = (hn @ p["wv"]).reshape(S, Hkv, d)
    q, k, v = (np.asarray(a, np.float64).reshape(S, -1, d) for a in (q, k, v))
    reach = {None: W if kind == "sliding_attention" else S,
             "one_block": W + 16, "whole_history": S}[quant]
    i, j = np.arange(S)[:, None], np.arange(S)[None, :]
    mask = (j <= i) & (i - j < reach)
    want = np.zeros((S, H, d))
    for hq in range(H):
        sc = q[:, hq] @ k[:, hq // (H // Hkv)].T / np.sqrt(d)
        sc = np.where(mask, sc, -np.inf)
        pr = np.exp(sc - sc.max(-1, keepdims=True))
        want[:, hq] = (pr / pr.sum(-1, keepdims=True)) @ v[:, hq // (H // Hkv)]
    assert np.abs(got - want.reshape(S, H * d)).max() < 2e-5


@pytest.mark.parametrize("quant", [None, "int8"])
def test_the_reference_s_pairs_by_expert_are_every_expert_masked(quant):
    """The reference's expert layer lays the (token, choice) pairs out
    expert by expert: against every expert run over every token with its
    gate as a mask, in float64."""
    T, h, f, E, k = 96, 32, 24, 8, 3
    m = {"num_experts": E, "num_experts_per_tok": k, "norm_topk_prob": True}
    ks = jax.random.split(jax.random.PRNGKey(9), 5)
    p = {"router": jax.random.normal(ks[0], (h, E)),
         "e_gate": jax.random.normal(ks[1], (E, h, f)) / 6,
         "e_up": jax.random.normal(ks[2], (E, h, f)) / 6,
         "e_down": jax.random.normal(ks[3], (E, f, h)) / 5}
    x = jax.random.normal(ks[4], (1, T, h))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.moe(x, p, m, quant))[0]
        w = {n: np.asarray(ref._w(p, n, quant), np.float64) for n in p}
        gates, idx = ref.route(x[0] @ ref._w(p, "router", quant), k, True)
    gates, idx = np.asarray(gates, np.float64), np.asarray(idx)
    x64 = np.asarray(x[0], np.float64)
    want = np.zeros((T, h))
    for e in range(E):
        g = np.where(idx == e, gates, 0.0).sum(-1)            # [T]
        a = x64 @ w["e_gate"][e]
        want += g[:, None] * ((a / (1 + np.exp(-a)) * (x64 @ w["e_up"][e]))
                              @ w["e_down"][e])
    assert (np.bincount(idx.reshape(-1), minlength=E) > 0).all()
    assert np.abs(got - want).max() < 2e-5


# every expert shape a cell runs: (contraction, output side, the rule's tile)
GMM_TILES = [
    (2304, 1792, (128, 2304, 896)),      # Mellum2's gate and up: 7 -> 2 tiles
    (896, 2304, (128, 896, 2304)),       # its down projection: 9 -> 1
    (2048, 3584, (128, 2048, 896)),      # LFM2's: 7 -> 4
    (1792, 2048, (128, 1792, 1024)),     # 4 -> 2
    (5120, 3072, (128, 512, 1024)),      # DeepSeek-V2's: what they were
    (1536, 5120, (128, 512, 1024)),
    (2560, 1536, (128, 2560, 768)),      # Ling's: 3 -> 2
    (768, 2560, (128, 768, 2560))]       # 5 -> 1


def _rule_s_tile(k, n, monkeypatch):
    """The tilings ``_static_gmm`` hands the Mosaic kernel for bf16 rows."""
    seen = []
    monkeypatch.setattr(md, "_mosaic", lambda: True)
    monkeypatch.setattr(md, "_gmm_tuned", lambda xs, w, gs, tiles, full:
                        seen.append(tiles) or jnp.zeros((xs.shape[0], n)))
    md._static_gmm(jnp.zeros((8, k), BF16), jnp.zeros((2, k, n), BF16),
                   jnp.asarray([3, 5], jnp.int32))
    monkeypatch.undo()
    (tiles,) = seen
    assert tiles[0] == tiles[1] == tiles[2]
    return tiles[0]


@pytest.mark.parametrize("k,n,tile", GMM_TILES)
def test_the_grouped_matmul_s_tile_by_rule(k, n, tile, monkeypatch):
    """``_static_gmm``'s rule at every expert shape a cell runs: the widest
    column tile that divides the output side and fits (no side padded),
    DeepSeek-V2's tiles unchanged; and off a TPU the function is
    ``ragged_dot``."""
    assert _rule_s_tile(k, n, monkeypatch) == tile
    assert n % tile[2] == 0 and k % tile[1] == 0
    gs = jnp.asarray([3, 5], jnp.int32)
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.standard_normal((8, k)), F32)
    w = jnp.asarray(rng.standard_normal((2, k, n)), F32)
    assert np.array_equal(np.asarray(md._static_gmm(xs, w, gs)),
                          np.asarray(jax.lax.ragged_dot(xs, w, gs)))


@pytest.mark.parametrize("k,n", [(k, n) for k, n, _ in GMM_TILES])
def test_the_column_tile_is_the_widest_that_fits(k, n, monkeypatch):
    """The rule's own properties: the column tile is a multiple of 128 that
    divides ``n``; on the narrow branch the contraction is whole, the
    reckoned footprint is under the kernel's memory and no wider divisor
    would fit."""
    tm, tk, tn = _rule_s_tile(k, n, monkeypatch)
    assert tm == 128 and tn % 128 == 0 and n % tn == 0 and k % tk == 0
    if max(k, n) > md._HELD_NARROW:
        return
    assert tk == k and tn == md._column_tile(k, n, 2)
    assert md._gmm_footprint(k, tn, 2) <= md._GMM_VMEM
    wider = [t for t in range(tn + 128, n + 1, 128) if n % t == 0]
    assert all(md._gmm_footprint(k, t, 2) > md._GMM_VMEM for t in wider)
    # float32 rows take twice the bytes a block: a narrower tile, by the
    # same rule
    tn4 = md._column_tile(k, n, 4)
    assert tn4 <= tn and n % tn4 == 0


@pytest.mark.parametrize("groups,rows,shapes,old,new", [
    # Mellum2's piece: 8,448 pairs + half a tile an expert, a layer
    (64, 12544, ((2304, 1792, 256, 896), (896, 2304, 256, 2304)),
     1.50e9, 1.03e9),
    # LFM2's: 4,352 pairs + half a tile an expert
    (32, 6400, ((2048, 3584, 512, 896), (1792, 2048, 512, 1024)),
     1.05e9, 0.93e9)], ids=["mellum2", "lfm2"])
def test_a_piece_s_rows_are_read_once_a_column_tile(groups, rows, shapes,
                                                    old, new):
    """The arithmetic the column tile rests on, by ``megablox.gmm``'s own
    ``cost_estimate``: ``x`` once a column tile, the weights once a group,
    the output once. A layer of Mellum2's piece moves 1.50 GB at the old
    tiles and 1.03 at the rule's, LFM2's 1.05 and 0.93."""
    moved = lambda which: sum(
        md._gmm_bytes(rows, groups, k, n, (t_old, t_new)[which])
        for k, n, t_old, t_new in shapes)
    assert abs(moved(0) - old) < 0.005e9 and abs(moved(1) - new) < 0.005e9
    assert all(t_new == md._column_tile(k, n, 2)
               for k, n, _t_old, t_new in shapes)
