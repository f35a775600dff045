"""LFM2-8B-A1B (``lfm2_moe``) through ``LLMEngine`` on the CPU, small and
seeded, against the plain reference ``benchmark/reference/lfm2_moe_f32.py``
(which imports nothing of the program): the served tokens through the
paged cache AND the per-slot state, the state's life (zeroed, carried
across pieces, advanced where active, never leaked), the router, the
expert layer with all experts held, and what the engine refuses."""
import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest, weights
from benchmark.reference import lfm2_moe_f32 as ref
from paddle_tpu.models import lfm2_moe
from paddle_tpu.serving import LLMEngine

md = importlib.import_module("paddle_tpu.kernels.moe_dispatch")
FAM = manifest.load_family("lfm2_moe")
BASE = {"family": "lfm2_moe", "kind": "serve", "conv_L_cache": 3,
        "conv_bias": False, "norm_eps": 1e-5, "norm_topk_prob": True,
        "use_expert_bias": True, "routed_scaling_factor": 1,
        "rope_theta": 1000000}
MODEL = dict(BASE, **FAM.tiny(BASE))     # conv-dense, attn-moe, conv-moe
KEY = weights.seed_key(7)
F32 = jnp.float32
PROMPTS = (5, 50, 23, 70, 9)


@functools.lru_cache(maxsize=None)
def _params():
    return jax.jit(lambda k: FAM.make_params(MODEL, k, F32))(KEY)


def _engine(max_slots=3, **kw):
    cfg = FAM.program_config(MODEL, max_seq_len=128, dtype=F32)
    return LLMEngine(_params(), cfg, max_slots=max_slots, block_size=8,
                     max_model_len=128, prompt_buckets=[16, 32], seed=0, **kw)


def _served(kw, n_new=24, lens=PROMPTS, max_slots=3):
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 256, size=n).tolist() for n in lens]
    eng = _engine(max_slots, **kw)
    ids = [eng.add_request(p, max_new_tokens=n_new) for p in prompts]
    res = eng.run()
    return eng, prompts, [res[i] for i in ids]


def _reference_gaps(prompts, served, round_to=None):
    """For each served position, how far the served token's reference
    LOGIT lies below the reference's best (the full forward pass over
    prompt + served tokens, no cache and no state); with ``round_to`` the
    reference is the control: weights and the activations between layers
    rounded to that dtype, and the gap is that of ITS first token under
    the sound reference."""
    cast = (lambda a: a.astype(round_to).astype(F32)) if round_to \
        else (lambda a: a)
    top = {n: FAM.make_top(MODEL, KEY, n, F32)
           for n in ("embed", "final_norm")}
    gaps = []
    with jax.default_matmul_precision("highest"):
        layers = [FAM.make_layer(MODEL, KEY, l, F32)
                  for l in range(MODEL["num_hidden_layers"])]
        for p, out in zip(prompts, served):
            seq = jnp.asarray([p + out])
            x = xc = FAM.reference.embed(seq, top)
            for l, lp in enumerate(layers):
                x = ref.layer(x, lp, MODEL, None, l)
                if round_to:
                    xc = cast(ref.layer(
                        xc, jax.tree_util.tree_map(cast, lp), MODEL, None, l))
            lg = ref.head_logits(x[0], top, MODEL)[len(p) - 1:-1]
            tok = jnp.asarray(out)
            if round_to:
                tok = ref.head_logits(xc[0], top, MODEL)[
                    len(p) - 1:-1].argmax(-1)
            gaps.append(np.asarray(
                lg.max(-1) - jnp.take_along_axis(lg, tok[:, None], 1)[:, 0]))
    return np.concatenate(gaps)


# a float32 engine against the float32-highest reference: the two differ by
# summation order alone (blockwise softmax, the grouped matmul, the carried
# state against the shifted sequence), 1e-5 of a unit-scale logit, so a
# served token can lie below the reference's best only where two logits are
# that close. A state that is lost, stale or another request's moves the
# served tokens by whole logits (read while writing this: zeroing the
# carried state of the continuing pieces gives a widest gap of 1.7), and
# the control, the reference rounded to bf16, by up to 0.1.
GAP_LIMIT = 1e-3


@pytest.mark.parametrize("kw", [
    # whole prompts (bucket 32 holds 5, 9, 23; 50 and 70 take the
    # max_model_len bucket): no piece carries a state
    dict(),
    # pieces of 16 = two blocks: every boundary falls ON a block
    dict(prefill_chunk=16),
    # pieces of 24 in blocks of 8 with prompts of 50 and 70: the last
    # piece ends INSIDE a block, and decode goes on from there
    dict(prefill_chunk=24),
    # the flat walk kernel (interpreted) over rows of all KV heads
    dict(prefill_chunk=16, decode_kernel="ragged"),
    # a second engine shape: two tokens a decode call, five slots
    dict(prefill_chunk=16, decode_steps=2, max_slots=5)],
    ids=["whole", "pieces-on-a-block", "pieces-inside-a-block",
         "ragged-walk", "two-steps-five-slots"])
def test_served_tokens_agree_with_the_reference(kw):
    """Prefill (whole or in pieces), then decode through the cache AND the
    state, against the reference's full forward pass."""
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
    """Preemption by recompute: the victim's state is not snapshotted, its
    re-admission starts from zero and recomputes it with the tokens."""
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
    from zero state whatever the slot held, and a slot's rows of the state
    entries hold what its last request left (they are not zeroed at the
    end, only at the next start)."""
    eng, prompts, served = _served(dict(prefill_chunk=16), max_slots=1)
    assert _reference_gaps(prompts, served).max() <= GAP_LIMIT
    state = {n: np.asarray(eng.pools[n]) for n in eng.model.state_entries}
    assert all(np.abs(s[:, 0]).max() > 0 for s in state.values())
    assert all(np.abs(s[:, 1]).max() == 0 for s in state.values())  # trash


def test_an_idle_slot_s_state_does_not_move_through_a_call():
    """Two requests in three slots: the third slot is idle through every
    call and its state rows stay as planted; the others' move."""
    eng = _engine(prefill_chunk=16)
    marks = {n: jnp.full_like(eng.pools[n][:, 2], 7.0)
             for n in eng.model.state_entries}
    for n, mark in marks.items():
        eng.pools[n] = eng.pools[n].at[:, 2].set(mark)
    rng = np.random.default_rng(5)
    ids = [eng.add_request(rng.integers(0, 256, size=n).tolist(),
                           max_new_tokens=6) for n in (20, 7)]
    res = eng.run()
    assert all(len(res[i]) == 6 for i in ids)
    for n in eng.model.state_entries:
        got = np.asarray(eng.pools[n])
        assert (got[:, 2] == 7.0).all(), n
        assert np.abs(got[:, 0]).max() > 0 and (got[:, 0] != 7.0).any()


def test_the_bf16_control_fails_the_limit():
    _eng, prompts, served = _served(dict(prefill_chunk=16))
    control = _reference_gaps(prompts, served, round_to=jnp.bfloat16)
    assert control.max() > GAP_LIMIT, control.max()


def test_layers_write_one_kind_of_entry_and_the_bytes_follow():
    """Pools over the attention layers only, a state entry a convolution
    layer; block bytes, kv bytes per token and the state's bytes a slot
    follow what is declared."""
    eng = _engine()
    m, c = eng.model, eng.model.config
    assert set(eng.pools) == {"kv0", "s0", "s1"}
    assert eng.pools["kv0"].shape == (1, eng.nb, 8, 256)  # [V | K], 2 x 64 each
    assert eng.pools["s0"].shape == (1, eng.N + 1, 2, c.hidden_size)
    per_token = 2 * c.num_kv_heads * c.head_dim * 4             # one layer, f32
    assert eng._pool_block_bytes() == per_token * eng.bs
    assert eng._state_bytes_per_slot == 2 * 2 * c.hidden_size * 4
    assert m.state_entries == ("s0", "s1")


# -- the router ---------------------------------------------------------------
def test_the_bias_moves_the_selection_and_no_weight():
    scores = jnp.asarray([[0.9, 0.8, 0.3, 0.2], [0.6, 0.5, 0.4, 0.1]], F32)
    zero = jnp.zeros((4,), F32)
    bias = jnp.asarray([0.0, -0.7, 0.0, 0.5], F32)     # 1 out, 3 in (row 0)
    g0, i0 = md.sigmoid_bias_routing(scores, zero, 2)
    g1, i1 = md.sigmoid_bias_routing(scores, bias, 2)
    assert i0.tolist() == [[0, 1], [0, 1]]
    assert i1.tolist() == [[0, 3], [0, 3]]
    # the weights are the UNBIASED scores of the chosen, renormalised
    np.testing.assert_allclose(
        np.asarray(g1[0]), [0.9 / (1.1 + 1e-6), 0.2 / (1.1 + 1e-6)], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(g0.sum(-1)), 1.0, atol=1e-5)
    # without renormalisation the gates are the scores, times the scale
    g2, _ = md.sigmoid_bias_routing(scores, bias, 2, scale=2.0, renorm=False)
    np.testing.assert_allclose(np.asarray(g2[0]), [1.8, 0.4], rtol=1e-6)


def test_ties_go_to_the_lower_index_as_in_the_reference():
    scores = jnp.full((3, 6), 0.5, F32)
    _g, idx = md.sigmoid_bias_routing(scores, jnp.zeros((6,), F32), 3)
    assert idx.tolist() == [[0, 1, 2]] * 3
    gates = ref.route(scores, jnp.zeros((6,), F32), 3, True, 1.0)
    assert (np.asarray(gates) > 0).tolist() == [[True] * 3 + [False] * 3] * 3


def test_the_seeded_bias_changes_who_is_selected_and_empties_no_expert():
    """What the configuration's ``assumed`` says of the bias, at the tiny
    size: it changes the selected set on some percent of tokens and every
    expert keeps rows."""
    p = FAM.make_layer(MODEL, KEY, 1, F32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4096, MODEL["hidden_size"]))
    scores = jax.nn.sigmoid(x @ p["router"])
    k = MODEL["num_experts_per_tok"]
    _g, with_b = md.sigmoid_bias_routing(scores, p["expert_bias"], k)
    _g, no_b = md.sigmoid_bias_routing(scores, jnp.zeros_like(
        p["expert_bias"]), k)
    changed = float(np.mean(np.any(np.sort(with_b) != np.sort(no_b), -1)))
    assert 0.01 < changed < 0.2, changed
    assert len(np.unique(np.asarray(with_b))) == MODEL["num_experts"]


def test_held_expert_ffn_with_all_held_equals_the_reference_s_sum():
    """The whole expert layer (router, bias, renormalisation, all experts
    held, pad rows unrouted) against the reference's dense sum."""
    cfg = FAM.program_config(MODEL, dtype=F32)
    m = cfg.served_model()
    pub = FAM.make_layer(MODEL, KEY, 2, F32)
    p = lfm2_moe.from_published(pub, cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (40, cfg.hidden_size), F32)
    valid = jnp.arange(40) < 33
    with jax.default_matmul_precision("highest"):
        y, counts = m._ffn(p, 2, x, valid)
        want = ref.moe(x[None], pub, MODEL, None)[0]
    np.testing.assert_allclose(np.asarray(y[:33]), np.asarray(want[:33]),
                               atol=2e-5)
    assert float(jnp.abs(y[33:]).max()) == 0.0          # pad rows: unrouted
    routed, assigned, hit, _full, _tiles = (float(c) for c in counts)
    assert routed == assigned == 33 * cfg.num_experts_per_tok  # all held
    assert hit <= cfg.num_experts


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
    assert lfm2_moe.Lfm2MoeServed.unsupported[feature] in str(e.value)


def test_every_unsupported_feature_is_one_the_engine_asks_about():
    assert set(lfm2_moe.Lfm2MoeServed.unsupported) == {
        "spec", "prefix_cache", "kv_swap", "mesh", "kv_int8", "disagg"}
