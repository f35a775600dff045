"""Trinity (``afmoe``) through ``LLMEngine`` on the CPU, small and seeded,
against the plain reference ``benchmark/reference/afmoe_f32.py`` (which
imports nothing of the program): the served tokens through BOTH kinds of
cache at a window of a few blocks that the ring passes several times, in
pieces, with a piece that carries the decode rows, with slots reused; each
planted fault of ISSUE 42 section 8 fails the same comparison; the shares'
routed parts and the shared expert once add up to the uncut layer; the
router's four numbers; what is refused at construction."""
import functools
import importlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest, weights
from benchmark.reference import afmoe_f32 as ref
from paddle_tpu.models import afmoe, mellum, window_kv
from paddle_tpu.serving import LLMEngine

md = importlib.import_module("paddle_tpu.kernels.moe_dispatch")
FAM = manifest.load_family("afmoe")
PUBLISHED = manifest.Manifest().config("trinity-large-preview-serve-ep8")
BASE = {k: v for k, v in PUBLISHED.items()
        if not isinstance(v, (list, dict)) or k == "rope_scaling"}
# a dense window layer, then three window layers and a full one, a window
# of 32 tokens over blocks of 8: a ring of five blocks
MODEL = {**BASE, **FAM.tiny(BASE), "sliding_window": 32}
W, BS = 32, 8
KEY = weights.seed_key(11)
F32 = jnp.float32
# contexts of 0.5, 1.5 and 5 windows, and one under a block
PROMPTS = (16, 48, 160, 5)


# the cell's bias (normal at 0.005) changes a weight by half a per cent where
# it is counted in: a float32 argmax does not move. Here it is twenty times
# louder, in the program's tree and in the reference's alike, so that "the
# bias counted into the weights" is a fault these tests can see
BIAS_UP = 20.0


def _louder(layer):
    return ({**layer, "expert_bias": layer["expert_bias"] * BIAS_UP}
            if "expert_bias" in layer else layer)


@functools.lru_cache(maxsize=None)
def _params():
    tree = jax.jit(lambda k: FAM.make_params(MODEL, k, F32))(KEY)
    return {**tree, "layers": [_louder(l) for l in tree["layers"]]}


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


def _reference_gaps(prompts, served, quant=None, model=MODEL):
    """For each served position, how far the served token's reference
    LOGIT lies below the reference's best (the full forward pass over
    prompt + served tokens: no cache, no ring, no pieces); ``quant`` plants
    a fault in the reference."""
    top = {n: FAM.make_top(model, KEY, n, F32)
           for n in ("embed", "head", "final_norm")}
    gaps = []
    with jax.default_matmul_precision("highest"):
        layers = [_louder(FAM.make_layer(model, KEY, l, F32))
                  for l in range(model["num_hidden_layers"])]
        for p, out in zip(prompts, served):
            x = FAM.reference.embed(jnp.asarray([p + out]), top)
            for l, lp in enumerate(layers):
                x = ref.layer(x, lp, model, quant, l)
            lg = ref.head_logits(x[0], top, model)[len(p) - 1:-1]
            tok = jnp.asarray(out)
            gaps.append(np.asarray(
                lg.max(-1) - jnp.take_along_axis(lg, tok[:, None], 1)[:, 0]))
    return np.concatenate(gaps)


# a float32 engine against the float32-highest reference: the two differ by
# summation order alone, so a served token lies below the reference's best
# only where two logits are 1e-5 apart; every planted fault below moves the
# served tokens by tenths of a logit and more
GAP_LIMIT = 1e-3


@pytest.mark.parametrize("kw", [
    # pieces of W: every piece sees its own chunk and the W - 1 before it
    dict(prefill_chunk=32),
    # pieces of 24 in blocks of 8: the last piece ends INSIDE a block
    dict(prefill_chunk=24),
    # the walk kernel with a start (interpreted) over the ring as it lies,
    # and the step's last piece carries the decode rows in its program
    dict(prefill_chunk=32, decode_kernel="ragged")],
    ids=["pieces-of-w", "pieces-off-the-block", "piece-carries-the-rows"])
def test_served_tokens_agree_with_the_reference(kw):
    """Prefill in pieces, then 40 decoded tokens through both kinds of
    cache (the 160-token context is five windows: the ring of five blocks
    is passed four times before the first decoded token), against the
    reference's full forward pass at every position."""
    eng, prompts, served = _served(dict(kw))
    assert all(len(s) == 40 for s in served)
    assert eng._piggyback == ("decode_kernel" in kw)
    acc = eng.block_accounting()
    assert acc["backed"] == 0 and acc["window"]["backed"] == 0
    assert eng.win.recycled > 0            # blocks were written again
    gaps = _reference_gaps(prompts, served)
    assert gaps.max() <= GAP_LIMIT, gaps.max()


@functools.lru_cache(maxsize=None)
def _sound_run():
    _eng, prompts, served = _served(dict(prefill_chunk=32), n_new=32,
                                    lens=(48, 160))
    return prompts, served


@pytest.mark.parametrize("fault", sorted(ref.FAULTS))
def test_planted_faults_are_seen(fault):
    """What ``correct`` must call not correct on the chip, here in float32:
    each fault of ISSUE 42 section 8 planted in the reference's place (as
    ``benchmark/calibrate.py`` plants the int8 control) against the tokens
    the sound engine served."""
    prompts, served = _sound_run()
    assert _reference_gaps(prompts, served).max() <= GAP_LIMIT
    gaps = _reference_gaps(prompts, served, fault)
    # the same comparison fails: a served token lies below the faulty
    # reference's best by several times what summation order explains
    assert gaps.max() > 5 * GAP_LIMIT, gaps.max()
    if fault != "bias_in_weights":
        # (a bias counted in changes each weight by a tenth here, and the
        # norm after the experts takes most of that back: it flips a few
        # near-ties, which the float32 comparison still sees and bf16 on
        # the chip does not: limits/agent-offline.json has the reading)
        assert gaps.mean() > 0.01 and gaps.max() > 0.1, (gaps.mean(),
                                                         gaps.max())


def test_a_slot_reused_by_a_shorter_request_sees_nothing_of_the_old_window():
    """One slot: a long request fills the ring and writes blocks again in
    place, then a short one takes the slot (and the same physical blocks):
    its tokens are the reference's, as if the slot were new."""
    eng = _engine(max_slots=1, prefill_chunk=32)
    long_, short = _prompts((160, 11), seed=9)
    a = eng.add_request(long_, max_new_tokens=24)
    b = eng.add_request(short, max_new_tokens=24)
    res = eng.run()
    assert eng.win.recycled > 0
    gaps = _reference_gaps([long_, short], [res[a], res[b]])
    assert gaps.max() <= GAP_LIMIT, gaps.max()


def test_spans_and_counters_of_the_window_and_the_share():
    import paddle_tpu.observability as obs
    from paddle_tpu.observability import get_tracer

    value = lambda snap, name: sum(
        s["value"] for m in snap["metrics"] if m["name"] == name
        for s in m["series"])
    obs.enable()
    try:
        before = obs.snapshot()
        eng, prompts, served = _served(dict(prefill_chunk=32), n_new=12,
                                       lens=(70, 20))
        snap = obs.snapshot()
    finally:
        obs.disable()
    spans = list(get_tracer().spans())
    dec = [s.attrs for s in spans if s.name == "serving.decode"
           and "window_bytes" in s.attrs]
    # four window layers beside ONE full one: the window kind is the larger
    # part of what a step reads while contexts are short
    assert dec and all(0 < a["window_bytes"] < a["kv_bytes"] for a in dec)
    assert all("expert_rows" in a and "experts_hit" in a for a in dec)
    delta = lambda name: value(snap, name) - value(before, name)
    # a token is past the window when prompt + tokens so far exceed W: all
    # 12 of the 70-token request, those of the 20-token one from the 13th
    assert delta("serving_window_bounded_tokens_total") == 12
    assert delta("serving_tokens_total") == 24
    # four window layers, a ring of five blocks of 8 tokens, K and V of two
    # heads of 64 in float32; one full layer
    assert value(snap, "serving_window_bytes_per_slot") == \
        4 * 5 * 8 * 2 * 2 * 64 * 4
    assert value(snap, "serving_kv_bytes_per_token") == 2 * 2 * 64 * 4
    # a share of 8 of 32 experts under top-2 over four expert layers: the
    # pairs held here are about a quarter of the pairs routed
    routed, here = (delta("serving_moe_routed_total"),
                    delta("serving_moe_assigned_total"))
    assert routed > 0 and 0.1 < here / routed < 0.45, (routed, here)


def test_mellum_counts_the_tokens_past_its_window_too():
    """The counter is the engine's, for any model with window entries."""
    import paddle_tpu.observability as obs

    fam = manifest.load_family("mellum")
    pub = manifest.Manifest().config("mellum2-12b-a2.5b-serve")
    m = {**{k: pub[k] for k in (
        "family", "kind", "attention_bias", "tie_word_embeddings",
        "use_sliding_window", "norm_topk_prob", "rms_norm_eps",
        "rope_parameters")}, **fam.tiny(pub), "sliding_window": 16}
    params = jax.jit(lambda k: fam.make_params(m, k, F32))(KEY)
    value = lambda snap: sum(
        s["value"] for mm in snap["metrics"]
        if mm["name"] == "serving_window_bounded_tokens_total"
        for s in mm["series"])
    obs.enable()
    try:
        before = value(obs.snapshot())
        eng = LLMEngine(params, fam.program_config(m, max_seq_len=64,
                                                   dtype=F32),
                        max_slots=2, block_size=BS, max_model_len=64,
                        seed=0, prompt_buckets=[16])
        eng.add_request(_prompts((10,))[0], max_new_tokens=10)
        eng.run()
        after = value(obs.snapshot())
    finally:
        obs.disable()
    assert after - before == 4             # contexts of 17, 18, 19, 20


# -- the plumbing is shared, not copied ----------------------------------------
def test_the_two_kind_plumbing_is_one_copy():
    for cls in (afmoe.AfmoeServed, mellum.MellumServed):
        assert issubclass(cls, window_kv.TwoKindCache)
        for name in ("make_pools", "prefill_begin", "decode_begin",
                     "decode_step_begin", "_prefill_attention",
                     "_decode_attention", "ring_init", "pack_entries",
                     "_kind"):
            assert name not in vars(cls), (cls.__name__, name)
    # a full layer's kind has no angles: nothing is turned by zero
    served = FAM.program_config(MODEL).served_model()
    assert sorted(served._freqs()) == ["w"]
    assert served.window_entries == ("kvw0", "kvw1", "kvw2", "kvw3")
    assert served.window == 32 and served._full == [4]


def test_a_full_layer_carries_no_position():
    """The program's full layer: q and k of a token do not depend on where
    the token stands; a window layer's do."""
    served = FAM.program_config(MODEL, dtype=F32).served_model()
    p = _params()["layers"][4]
    hn = jax.random.normal(jax.random.PRNGKey(1), (1, 4, 256))
    ang = {k: (jnp.arange(4.0)[None, :, None] * 7.0 * f[None, None, :], m)
           for k, (f, m) in served._freqs().items()}
    q0, k0, _ = served._qkv(hn, p, None)
    q1, k1, _ = served._qkv(hn, p, ang["w"])
    assert np.array_equal(np.asarray(q0[:, 0]), np.asarray(q1[:, 0]))
    assert np.abs(np.asarray(q0 - q1))[:, 1:].max() > 0.1
    assert "f" not in ang


# -- what is refused, with its reason -----------------------------------------
@pytest.mark.parametrize("kw,feature", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(kv_swap_bytes=1 << 20), "kv_swap"),
    (dict(kv_dtype="int8"), "kv_int8"),
    (dict(role="decode"), "disagg"),
    (dict(draft_params={}, draft_config=object()), "spec")])
def test_unsupported_features_are_refused_with_their_reason(kw, feature):
    with pytest.raises(NotImplementedError, match=feature):
        _engine(**kw)


@pytest.mark.parametrize("over,match", [
    (dict(num_shared_experts=2), "shared expert"),
    (dict(held_first=28, n_routed_experts=8), "outside the router"),
    (dict(num_dense_layers=5), "expert layer"),
    (dict(layer_types=["sliding_attention"] * 5), "both kinds"),
    (dict(score_func="softmax"), "sigmoid router"),
    (dict(n_group=2), "no group limit"),
    (dict(mup_enabled=False), "sqrt"),
    (dict(tie_word_embeddings=True), "untied")])
def test_what_the_model_cannot_do_is_refused_at_construction(over, match):
    with pytest.raises(ValueError, match=match):
        FAM.program_config({**MODEL, **over}).served_model()


# -- the share ----------------------------------------------------------------
def test_the_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """Eight shares of four experts each under a router 32 wide: the routed
    parts that the PROGRAM computes for each share, plus the shared expert
    counted once, are what the REFERENCE gives for the uncut layer (all 32
    experts held)."""
    m = {**MODEL, "n_routed_experts": 32, "held_first": 0}
    h, f, E = m["hidden_size"], m["moe_intermediate_size"], 32
    p = FAM.make_layer(m, KEY, 1, F32)            # an expert layer, uncut
    assert p["e_gate"].shape == (E, h, f) and p["router"].shape == (h, E)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 96, h))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.moe(x, p, m, None, held=(0, E)))[0]
        shared = np.asarray(ref.swiglu(x[0], p["s_gate"], p["s_up"],
                                       p["s_down"]))
        total = np.zeros_like(want)
        hit = []
        for first in range(0, E, 4):
            cut = {**m, "n_routed_experts": 4, "held_first": first}
            served = FAM.program_config(cut, dtype=F32).served_model()
            lp = afmoe.from_published(
                {**p, **{n: p[n][first:first + 4]
                         for n in ("e_gate", "e_up", "e_down")}},
                served.config)
            y, counts = served._ffn(lp, 1, x[0], jnp.ones((96,), bool))
            total += np.asarray(y) - shared
            hit.append(float(counts[1]))
            # the reference given the same share agrees with the program
            assert np.abs(np.asarray(ref.moe(x, {**p, **{
                n: p[n][first:first + 4]
                for n in ("e_gate", "e_up", "e_down")}}, cut, None))[0]
                - np.asarray(y)).max() < 2e-5
    assert sum(hit) == 96 * m["num_experts_per_tok"]   # every pair, once
    assert np.abs(total + shared - want).max() < 5e-5


def test_the_router_s_four_numbers():
    """Sigmoids in float32 over the whole width; the bias in the selection
    and in no weight; top-k renormalised over the chosen (+ 1e-20) and
    scaled by ``route_scale``; ties to the lower index: the program's
    function against the reference's and against the formula."""
    s = jnp.asarray([[0.9, 0.5, 0.9, 0.2, 0.9, 0.1],
                     [0.5, 0.5, 0.5, 0.5, 0.5, 0.5],
                     [0.6, 0.5, 0.4, 0.3, 0.2, 0.1]], jnp.float32)
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.0, 0.0, 0.6], jnp.float32)
    gates, idx = md.sigmoid_bias_routing(s, bias, 2, 2.448, True, eps=1e-20)
    assert np.asarray(idx).tolist() == [[0, 2], [5, 0], [5, 0]]
    chosen = np.take_along_axis(np.asarray(s, np.float64), np.asarray(idx), 1)
    want = 2.448 * chosen / chosen.sum(-1, keepdims=True)
    assert np.allclose(np.asarray(gates), want, atol=1e-6)     # no bias in it
    assert np.allclose(np.asarray(gates).sum(-1), 2.448, atol=1e-5)
    rw, ridx = ref.route(s, bias, 2, 2.448, True)
    assert np.asarray(ridx).tolist() == np.asarray(idx).tolist()
    assert np.allclose(np.asarray(rw), np.asarray(gates), atol=1e-6)
    # the other rules' callers keep their 1e-6
    g6, _ = md.sigmoid_bias_routing(s, bias, 2, 1.0, True)
    assert np.allclose(np.asarray(g6).sum(-1),
                       chosen.sum(-1) / (chosen.sum(-1) + 1e-6), atol=1e-6)


@pytest.mark.parametrize("quant", [None, "int8", "wrong_share"])
def test_the_reference_s_pairs_by_held_expert_are_every_expert_masked(quant):
    """The reference's expert layer lays a block's (token, choice) pairs out
    held expert by held expert, the others behind the last group: against
    every held expert run over every token with its weight as a mask, in
    float64, for a share in the middle of the router's width."""
    T, h, f, Wd, k, first, count = 96, 32, 24, 16, 3, 4, 4
    m = {"num_experts_per_tok": k, "route_scale": 2.448, "route_norm": True,
         "held_first": first, "n_routed_experts": count}
    ks = jax.random.split(jax.random.PRNGKey(9), 9)
    p = {"router": jax.random.normal(ks[0], (h, Wd)),
         "expert_bias": jax.random.normal(ks[1], (Wd,)) * 0.05,
         "e_gate": jax.random.normal(ks[2], (count, h, f)) / 6,
         "e_up": jax.random.normal(ks[3], (count, h, f)) / 6,
         "e_down": jax.random.normal(ks[4], (count, f, h)) / 5,
         "s_gate": jax.random.normal(ks[5], (h, f)) / 6,
         "s_up": jax.random.normal(ks[6], (h, f)) / 6,
         "s_down": jax.random.normal(ks[7], (f, h)) / 5}
    x = jax.random.normal(ks[8], (1, T, h))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.moe(x, p, m, quant))[0]
        w = {n: np.asarray(ref._w(p, n, quant), np.float64) for n in p
             if p[n].ndim > 1}
        scores = jax.nn.sigmoid(x[0] @ ref._w(p, "router", quant))
        gates, idx = ref.route(scores, p["expert_bias"], k, 2.448, True)
    gates, idx = np.asarray(gates, np.float64), np.asarray(idx)
    x64 = np.asarray(x[0], np.float64)
    silu = lambda a: a / (1 + np.exp(-a))
    want = (silu(x64 @ w["s_gate"]) * (x64 @ w["s_up"])) @ w["s_down"]
    lo = first + (count if quant == "wrong_share" else 0)
    for e in range(count):
        g = np.where(idx == lo + e, gates, 0.0).sum(-1)           # [T]
        want += g[:, None] * ((silu(x64 @ w["e_gate"][e])
                               * (x64 @ w["e_up"][e])) @ w["e_down"][e])
    held = (idx >= lo) & (idx < lo + count)
    assert 0 < held.sum() < idx.size            # some pairs lie elsewhere
    assert np.abs(got - want).max() < 2e-5


def test_the_reference_imports_nothing_of_the_program():
    src = open(ref.__file__).read()
    assert "paddle_tpu" not in src.split('"""', 2)[2]
    assert not [n for n in sys.modules[ref.__name__].__dict__.values()
                if getattr(n, "__name__", "").startswith("paddle_tpu")]
