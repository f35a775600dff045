"""Ling-3.0-flash (``ling_hybrid``) through ``LLMEngine`` on the CPU, small
and seeded, against the plain reference ``benchmark/reference/
ling_hybrid_f32.py`` (which imports nothing of the program): the served
tokens through the latent cache AND the matrix state that is advanced in
place, the state's life (zeroed, carried across pieces, advanced where
active, never leaked), the router with its top-two group score, the four
shares of an expert layer, the spans, and what the engine refuses."""
import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest, weights
from benchmark.reference import ling_hybrid_f32 as ref
from paddle_tpu.models import ling_hybrid
from paddle_tpu.serving import LLMEngine

md = importlib.import_module("paddle_tpu.kernels.moe_dispatch")
FAM = manifest.load_family("ling_hybrid")
BASE = manifest.Manifest().config("ling-3.0-flash-serve-ep4")
MODEL = dict(BASE, **FAM.tiny(BASE))     # KDA-dense, MLA-moe, KDA-moe
KEY = weights.seed_key(7)
F32 = jnp.float32
PROMPTS = (5, 50, 23, 70, 9)
H, D = MODEL["num_attention_heads"], MODEL["head_dim"]


@functools.lru_cache(maxsize=None)
def _params():
    return jax.jit(lambda k: FAM.make_params(MODEL, k, F32))(KEY)


def _engine(max_slots=3, **kw):
    cfg = FAM.program_config(MODEL, max_seq_len=128, dtype=F32)
    return LLMEngine(_params(), cfg, max_slots=max_slots, block_size=8,
                     max_model_len=128, prompt_buckets=[16, 32], seed=0, **kw)


def _prompts(lens=PROMPTS, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, size=n).tolist() for n in lens]


def _served(kw, n_new=24, lens=PROMPTS, max_slots=3):
    prompts = _prompts(lens)
    eng = _engine(max_slots, **kw)
    ids = [eng.add_request(p, max_new_tokens=n_new) for p in prompts]
    res = eng.run()
    return eng, prompts, [res[i] for i in ids]


@functools.lru_cache(maxsize=None)
def _reference():
    """The reference's top and layers, made once, and one compiled program
    a layer (and one for the control's rounded layer)."""
    top = {n: FAM.make_top(MODEL, KEY, n, F32)
           for n in ("embed", "final_norm", "lm_head")}
    layers = [FAM.make_layer(MODEL, KEY, l, F32)
              for l in range(MODEL["num_hidden_layers"])]
    run = [jax.jit(functools.partial(ref.layer, m=MODEL, quant=None, l=l))
           for l in range(len(layers))]
    return top, layers, run


def _reference_gaps(prompts, served, round_to=None):
    """For each served position, how far the served token's reference
    LOGIT lies below the reference's best (the full forward pass over
    prompt + served tokens: the recurrence token by token, no cache, no
    state carried; the sequences padded on the right to one length and run
    as one batch, which a causal model's earlier positions cannot see);
    with ``round_to`` the reference is the control: weights and the
    activations between layers rounded to that dtype, and the gap is that
    of ITS first token under the sound reference."""
    cast = (lambda a: a.astype(round_to).astype(F32)) if round_to \
        else (lambda a: a)
    top, layers, run = _reference()
    seqs = [p + out for p, out in zip(prompts, served)]
    width = -(-max(map(len, seqs)) // 32) * 32
    gaps = []
    with jax.default_matmul_precision("highest"):
        x = xc = FAM.reference.embed(jnp.asarray(
            [s + [0] * (width - len(s)) for s in seqs]), top)
        for l, lp in enumerate(layers):
            x = run[l](x, lp)
            if round_to:
                xc = cast(run[l](xc, jax.tree_util.tree_map(cast, lp)))
        for i, (p, out) in enumerate(zip(prompts, served)):
            at = slice(len(p) - 1, len(p) - 1 + len(out))
            lg = ref.head_logits(x[i], top, MODEL)[at]
            tok = jnp.asarray(out)
            if round_to:
                tok = ref.head_logits(xc[i], top, MODEL)[at].argmax(-1)
            gaps.append(np.asarray(
                lg.max(-1) - jnp.take_along_axis(lg, tok[:, None], 1)[:, 0]))
    return np.concatenate(gaps)


# a float32 engine against the float32-highest reference: the two differ by
# summation order alone (the chunked scan against the recurrence, blockwise
# softmax, the absorbed form, the grouped matmul), 1e-5 of a unit-scale
# logit, so a served token can lie below the reference's best only where
# two logits are that close. A state that is lost, stale or another
# request's moves the served tokens by whole logits (the planted faults
# below), and the control, the reference rounded to bf16, by up to 0.1.
GAP_LIMIT = 1e-3


@pytest.mark.parametrize("kw", [
    # whole prompts (bucket 32 holds 5, 9, 23; 50 and 70 take the
    # max_model_len bucket): no piece carries a state
    dict(),
    # pieces of 16 = two blocks: every boundary falls ON a block and ON a
    # sub-block of the scan
    dict(prefill_chunk=16),
    # pieces of 24 in blocks of 8 with prompts of 50 and 70: the last
    # piece ends INSIDE a block and inside a chunk of the scan, and decode
    # goes on from there
    dict(prefill_chunk=24),
    # the latent walk kernel (interpreted), and with it the ONE program in
    # which a step's last piece carries the decode rows
    dict(prefill_chunk=16, decode_kernel="ragged"),
    # a second engine shape: two tokens a decode call, five slots
    dict(prefill_chunk=16, decode_steps=2, max_slots=5)],
    ids=["whole", "pieces-on-a-block", "pieces-inside-a-block",
         "ragged-walk", "two-steps-five-slots"])
def test_served_tokens_agree_with_the_reference(kw):
    """Prefill (whole or in pieces), then decode through the cache AND the
    state, against the reference's full forward pass, at every position."""
    kw = dict(kw)
    eng, prompts, served = _served(kw, max_slots=kw.pop("max_slots", 3))
    assert all(len(s) == 24 for s in served)
    assert eng.block_accounting()["backed"] == 0        # every block back
    gaps = _reference_gaps(prompts, served)
    assert gaps.max() <= GAP_LIMIT, gaps.max()


def _counter(snap, name, **labels):
    return sum(s["value"] for m in snap["metrics"] if m["name"] == name
               for s in m["series"]
               if all(s["labels"].get(k) == v for k, v in labels.items()))


def test_a_pool_too_small_preempts_recomputes_the_state_and_agrees():
    """Preemption by recompute: the victim's 13 MB a slot are not
    snapshotted, its re-admission starts from zero and recomputes the state
    with the tokens, and the served tokens are the reference's."""
    import paddle_tpu.observability as obs

    obs.enable()
    try:
        before = obs.snapshot()
        eng, prompts, served = _served(dict(prefill_chunk=16, num_blocks=14))
        after = obs.snapshot()
    finally:
        obs.disable()
    moved = lambda name, **lb: (_counter(after, name, **lb)
                                - _counter(before, name, **lb))
    assert moved("serving_preemptions_total") > 0
    assert moved("serving_state_resets_total", reason="preempt") > 0
    assert moved("serving_state_resets_total", reason="admit") == 5
    assert _reference_gaps(prompts, served).max() <= GAP_LIMIT


def test_a_reused_slot_does_not_see_the_last_request_s_state():
    """Five requests through ONE slot, one after the other: each begins
    from zero state whatever the slot held; a slot's rows hold what its
    last request left, the trash row nothing."""
    eng, prompts, served = _served(dict(prefill_chunk=16), max_slots=1)
    assert _reference_gaps(prompts, served).max() <= GAP_LIMIT
    state = {n: np.asarray(eng.pools[n]) for n in eng.model.state_entries}
    assert all(np.abs(s[:, 0]).max() > 0 for s in state.values())
    assert all(np.abs(s[:, 1]).max() == 0 for s in state.values())  # trash


def test_an_idle_slot_s_state_does_not_move_through_a_call():
    """Two requests in three slots: the third slot is idle through every
    call and its rows of every entry stay as planted, bit for bit; the
    others' move."""
    eng = _engine(prefill_chunk=16)
    for n in eng.model.state_entries:
        eng.pools[n] = eng.pools[n].at[:, 2].set(7.0)
    rng = np.random.default_rng(5)
    ids = [eng.add_request(rng.integers(0, 256, size=n).tolist(),
                           max_new_tokens=6) for n in (20, 7)]
    res = eng.run()
    assert all(len(res[i]) == 6 for i in ids)
    for n in eng.model.state_entries:
        got = np.asarray(eng.pools[n])
        assert (got[:, 2] == 7.0).all(), n
        assert np.abs(got[:, 0]).max() > 0 and (got[:, 0] != 7.0).any()


@pytest.mark.parametrize("fault", ["piece-from-zero", "start-takes-slot",
                                   "decode-stands-still"])
def test_a_planted_fault_in_the_state_moves_whole_logits(fault, monkeypatch):
    """What the limits of ``reason-offline`` are held against: a continuing
    piece that begins from zero state, a row that starts its context with
    what the slot held, a decode step that never advances the matrix
    state. With a state that is a layer's WHOLE memory each reads far past
    the limit (a convolution's two inputs, LFM2's, moved two positions)."""
    from paddle_tpu.kernels import kda
    from paddle_tpu.serving import engine as eng_mod

    if fault == "decode-stands-still":
        real = kda.kda_step
        monkeypatch.setattr(kda, "kda_step", lambda q, k, v, g, beta, entry,
                            act, **kw: real(q, k, v, g, beta, entry,
                                            jnp.zeros_like(act), **kw))
    else:
        zero = fault == "piece-from-zero"

        class EngineJnp:
            """``jax.numpy`` as ``serving/engine.py`` alone sees it, with
            the one ``where`` of ``_paged_prefill`` that picks a row's
            carried state or zeros (a scalar 0 against a state entry's
            rows) planted."""

            def __getattr__(self, name):
                return getattr(jnp, name)

            @staticmethod
            def where(cond, a, b):
                if getattr(a, "ndim", 0) >= 3 and np.ndim(b) == 0 \
                        and not isinstance(b, jax.Array) and b == 0 \
                        and cond.ndim == a.ndim and cond.shape[0] == 1:
                    return jnp.zeros_like(a) if zero else a
                return jnp.where(cond, a, b)

        monkeypatch.setattr(eng_mod, "jnp", EngineJnp())
    kw = dict(prefill_chunk=16)
    max_slots = 1 if fault == "start-takes-slot" else 3
    _eng, prompts, served = _served(kw, max_slots=max_slots)
    assert _reference_gaps(prompts, served).max() > 0.1


def test_the_bf16_control_fails_the_limit():
    _eng, prompts, served = _served(dict(prefill_chunk=16))
    control = _reference_gaps(prompts, served, round_to=jnp.bfloat16)
    assert control.max() > GAP_LIMIT, control.max()


def test_the_entries_and_the_bytes_follow_what_is_declared():
    """One latent pool for the one MLA layer, two per-slot entries a KDA
    layer, the matrices float32 and named as advanced in place; block
    bytes and the state's bytes a slot follow."""
    eng = _engine()
    m, c = eng.model, eng.model.config
    assert set(eng.pools) == {"c0", "s0", "s1", "u0", "u1"}
    assert eng.pools["c0"].shape == (1, eng.nb, 8, 256)   # 128 + 16 -> 256
    assert eng.pools["s0"].shape == (1, eng.N + 1, H, D, D)
    assert eng.pools["s0"].dtype == jnp.float32
    assert eng.pools["u0"].shape == (1, eng.N + 1, 3, 3 * H * D)
    assert m.state_entries == ("s0", "s1", "u0", "u1")
    assert m.state_in_place == ("s0", "s1") and m.scan_layers == 2
    assert eng._pool_block_bytes() == 256 * 4 * eng.bs
    assert eng._state_bytes_per_slot == 2 * (H * D * D * 4 + 9 * H * D * 4)
    assert m.cache_kind == "latent"
    assert [c.is_mla_layer(l) for l in range(3)] == [False, True, False]
    assert [c.is_moe_layer(l) for l in range(3)] == [False, True, True]


def test_the_spans_and_the_gauge_carry_the_state_s_counts():
    import paddle_tpu.observability as obs

    obs.enable()
    try:
        obs.get_tracer().clear()
        eng, _prompts_, _served_ = _served(
            dict(prefill_chunk=16, decode_kernel="ragged"), n_new=8)
        spans = [(s.name, dict(s.attrs)) for s in obs.get_tracer().spans()]
        snap = obs.snapshot()
    finally:
        obs.disable()
    per_slot = eng._state_bytes_per_slot
    assert _counter(snap, "serving_state_bytes_per_slot") == per_slot
    pre = [a for n, a in spans if n == "serving.prefill"]
    dec = [a for n, a in spans if n == "serving.decode"]
    assert pre and dec
    for a in pre:
        assert a["state_in"] == (a["start"][0] > 0)
        assert a["scan_tokens"] == 2 * a["tokens"][0]        # 2 KDA layers
        assert a["state_bytes"] == per_slot * (1 + a["decode_slots"])
    assert any(a["decode_slots"] for a in pre)
    assert all(a["state_bytes"] == per_slot * a["slots"] for a in dec)
    assert all("expert_rows" in a and "experts_hit" in a for a in pre + dec)


# -- the router ---------------------------------------------------------------
def _literal_route(scores, bias, n_group, topk_group, top_k, scale):
    """NumPy, one token at a time: (experts in rank order, their weights)."""
    idx, w = [], []
    for s in np.asarray(scores, np.float64):
        b = s + np.asarray(bias, np.float64)
        per = len(s) // n_group
        group = [np.sort(b[g * per:(g + 1) * per])[-2:].sum()
                 for g in range(n_group)]
        # a stable sort on the negated score: ties to the lower index
        keep = sorted(np.argsort(-np.asarray(group), kind="stable")[
            :topk_group])
        cand = [e for g in keep for e in range(g * per, (g + 1) * per)]
        order = sorted(cand, key=lambda e: (-b[e], e))[:top_k]
        idx.append(order)
        w.append([s[e] / sum(s[o] for o in order) * scale for e in order])
    return np.asarray(idx), np.asarray(w)


def test_the_router_is_the_literal_top_two_group_rule_ties_included():
    rng = np.random.default_rng(1)
    scores = rng.uniform(0.05, 0.95, size=(64, 32)).astype(np.float32)
    scores[:8] = np.round(scores[:8], 1)          # many exact ties
    scores[8] = 0.5                               # all tied
    bias = (rng.standard_normal(32) * 0.05).astype(np.float32)
    bias[:4] = 0.0
    for b in (bias, np.zeros(32, np.float32)):
        g, idx = md.sigmoid_bias_routing(jnp.asarray(scores), jnp.asarray(b),
                                         3, 2.5, True, 4, 2)
        want_idx, want_w = _literal_route(scores, b, 4, 2, 3, 2.5)
        assert np.asarray(idx).tolist() == want_idx.tolist()
        np.testing.assert_allclose(np.asarray(g), want_w, rtol=2e-6)
        # and the reference's dense gates name the same experts
        dense = np.asarray(ref.route(jnp.asarray(scores), jnp.asarray(b), 4,
                                     2, 3, 2.5))
        assert [sorted(np.nonzero(r)[0]) for r in dense] == [
            sorted(r) for r in want_idx.tolist()]
    # a group that holds the single best expert loses to two with two good
    # ones: the top-TWO sum, not the maximum (DeepSeek-V2's rule)
    s = np.full((1, 8), 0.1, np.float32)
    s[0, 0] = 0.9                                  # group 0: 0.9 + 0.1
    s[0, 2:4] = 0.6                                # group 1: 1.2
    s[0, 4:6] = 0.55                               # group 2: 1.1
    _g, idx = md.sigmoid_bias_routing(jnp.asarray(s), jnp.zeros(8), 2, 1.0,
                                      True, 4, 2)
    assert sorted(np.asarray(idx)[0].tolist()) == [2, 3]
    # without groups the function is LFM2's, as it was
    _g, flat = md.sigmoid_bias_routing(jnp.asarray(s), jnp.zeros(8), 2)
    assert sorted(np.asarray(flat)[0].tolist()) == [0, 2]


def test_the_four_shares_sum_to_the_uncut_layer():
    """The share test: the router's width cut into its four shares
    (``held_first`` 0, 8, 16, 24 of 32 here; 0, 128, 256, 384 of 512 in the
    configuration), each with the shared expert: their routed parts and ONE
    shared expert sum to the reference's uncut layer, and each share is the
    reference's share."""
    wide = dict(MODEL, n_routed_experts=32, num_experts=32, held_first=0)
    pub = FAM.make_layer(wide, KEY, 2, F32)            # all 32 experts
    x = jax.random.normal(jax.random.PRNGKey(2), (40, MODEL["hidden_size"]),
                          F32)
    valid = jnp.arange(40) < 33
    with jax.default_matmul_precision("highest"):
        whole = ref.moe(x[None], pub, wide, None, (0, 32))[0]
        shared = ref.swiglu(x, pub["s_gate"], pub["s_up"], pub["s_down"])
        total, assigned = 0.0, 0.0
        for first in (0, 8, 16, 24):
            share = dict(MODEL, n_routed_experts=8, num_experts=8,
                         held_first=first)
            cfg = FAM.program_config(share, dtype=F32)
            cut = dict(pub, **{n: pub[n][first:first + 8]
                               for n in ("e_gate", "e_up", "e_down")})
            p = ling_hybrid.from_published(cut, cfg)
            y, counts = cfg.served_model()._ffn(p, 2, x, valid)
            want = ref.moe(x[None], cut, share, None)[0]
            np.testing.assert_allclose(np.asarray(y[:33]),
                                       np.asarray(want[:33]), atol=2e-5)
            total = total + (y - shared)
            assigned += float(counts[1])
            assert float(counts[0]) == 33 * 3          # routed: every pair
    np.testing.assert_allclose(np.asarray((total + shared)[:33]),
                               np.asarray(whole[:33]), atol=5e-5)
    assert assigned == 33 * 3                          # each pair held once


# -- what the engine refuses --------------------------------------------------
@pytest.mark.parametrize("feature,kw", [
    ("prefix_cache", dict(prefix_cache=True)),
    ("kv_swap", dict(kv_swap_bytes=1 << 20)),
    ("kv_int8", dict(kv_dtype="int8")),
    ("mesh", dict(mesh=object())),
    ("disagg", dict(role="decode")),
    ("spec", dict(draft_params={}, draft_config=object()))])
def test_what_the_model_cannot_do_is_refused_with_its_reason(feature, kw):
    with pytest.raises(NotImplementedError) as e:
        _engine(**kw)
    assert feature in str(e.value)
    assert ling_hybrid.LingHybridServed.unsupported[feature] in str(e.value)


def test_what_the_configuration_cannot_be_is_refused():
    cfg = FAM.program_config(MODEL, dtype=F32)
    import dataclasses

    for bad in (dict(kda_lower_bound=-8.0), dict(short_conv_kernel_size=3),
                dict(num_layers=1), dict(held_first=30)):
        with pytest.raises(ValueError):
            dataclasses.replace(cfg, **bad).served_model()
    assert set(ling_hybrid.LingHybridServed.unsupported) == {
        "spec", "prefix_cache", "kv_swap", "mesh", "kv_int8", "disagg"}
