"""DeepSeek-V2 through ``LLMEngine`` on the CPU, small and seeded, against
the plain reference ``benchmark/reference/deepseek_v2_f32.py`` (which
imports nothing of the program): the served tokens, the absorbed form, the
routing rule, the chip's share, the latent walk, and what the engine
refuses for this model."""
import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest, weights
from benchmark.reference import deepseek_v2_f32 as ref
from paddle_tpu.models import deepseek_v2
from paddle_tpu.serving import LLMEngine

md = importlib.import_module("paddle_tpu.kernels.moe_dispatch")
pa = importlib.import_module("paddle_tpu.kernels.paged_attention")
FAM = manifest.load_family("deepseek_v2")
BASE = {"family": "deepseek_v2", "kind": "serve", "first_k_dense_replace": 1,
        "moe_layer_freq": 1, "norm_topk_prob": False,
        "scoring_func": "softmax", "topk_method": "group_limited_greedy",
        "routed_scaling_factor": 16, "rms_norm_eps": 1e-6,
        "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                         "mscale": 0.707, "mscale_all_dim": 0.707,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"}}
MODEL = dict(BASE, **FAM.tiny(BASE))
KEY = weights.seed_key(7)
F32 = jnp.float32


@functools.lru_cache(maxsize=None)
def _params():
    return jax.jit(lambda k: FAM.make_params(MODEL, k, F32))(KEY)


def _served(kw, n_new=24):
    cfg = FAM.program_config(MODEL, max_seq_len=128, dtype=F32)
    params = _params()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 256, size=n).tolist()
               for n in (5, 50, 23, 70, 9)]
    eng = LLMEngine(params, cfg, max_slots=3, block_size=8, max_model_len=128,
                    prompt_buckets=[16, 32], seed=0, **kw)
    ids = [eng.add_request(p, max_new_tokens=n_new) for p in prompts]
    res = eng.run()
    return eng, prompts, [res[i] for i in ids]


def _reference_gaps(prompts, served, round_to=None):
    """For each served position, how far the served token's reference
    logit lies below the reference's best; with ``round_to`` the reference
    is the control: weights and the activations between layers rounded to
    that dtype, and the gap is that of ITS first token under the sound
    reference."""
    cast = (lambda a: a.astype(round_to).astype(F32)) if round_to \
        else (lambda a: a)
    top = {n: FAM.make_top(MODEL, KEY, n, F32)
           for n in ("embed", "final_norm", "lm_head")}
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
# summation order alone (blockwise softmax, the absorbed form, the grouped
# matmul), 1e-5 of a unit-scale logit, so a served token can lie below the
# reference's best only where two logits are that close. The control, the
# reference rounded to bf16, puts tokens first that lie up to 0.1 below.
GAP_LIMIT = 1e-3


@pytest.mark.parametrize("kw", [
    dict(prefill_chunk=16), dict(prefill_chunk=16, decode_kernel="ragged")],
    ids=["chunked", "chunked-ragged-walk"])
def test_served_tokens_agree_with_the_reference(kw):
    """Chunked prefill, then decode through the latent cache, against the
    reference's full forward pass over prompt + served tokens."""
    eng, prompts, served = _served(kw)
    assert all(len(s) == 24 for s in served)
    assert eng.block_accounting()["backed"] == 0        # every block back
    gaps = _reference_gaps(prompts, served)
    assert gaps.max() <= GAP_LIMIT, gaps.max()


def test_a_pool_too_small_preempts_and_still_agrees():
    import paddle_tpu.observability as obs

    obs.enable()
    try:
        before = obs.snapshot()
        eng, prompts, served = _served(dict(prefill_chunk=16, num_blocks=14))
        pre = lambda s: sum(x["value"] for m in s["metrics"]
                            if m["name"] == "serving_preemptions_total"
                            for x in m["series"])
        assert pre(obs.snapshot()) > pre(before)
    finally:
        obs.disable()
    assert _reference_gaps(prompts, served).max() <= GAP_LIMIT


def test_the_bf16_control_fails_the_limit():
    _eng, prompts, served = _served(dict(prefill_chunk=16))
    control = _reference_gaps(prompts, served, round_to=jnp.bfloat16)
    assert control.max() > GAP_LIMIT, control.max()


def test_the_absorbed_form_equals_the_expanded_form():
    cfg = FAM.program_config(MODEL, dtype=F32)
    m = cfg.served_model()
    p = deepseek_v2.from_published(FAM.make_layer(MODEL, KEY, 1, F32), cfg)
    rng = np.random.default_rng(0)
    H, dn, dr, r = (cfg.num_heads, cfg.qk_nope_head_dim,
                    cfg.qk_rope_head_dim, cfg.kv_lora_rank)
    T = 37
    q_nope = jnp.asarray(rng.normal(size=(H, dn)), F32)
    q_rope = jnp.asarray(rng.normal(size=(H, dr)), F32)
    lat = jnp.asarray(rng.normal(size=(T, r)), F32)
    k_r = jnp.asarray(rng.normal(size=(T, dr)), F32)
    s = cfg.softmax_scale
    # expanded: per-head keys and values
    k_nope = jnp.einsum("tc,hdc->thd", lat, p["w_uk"])
    v = jnp.einsum("tc,hcd->thd", lat, p["w_uv"])
    sc = (jnp.einsum("hd,thd->ht", q_nope, k_nope)
          + jnp.einsum("hd,td->ht", q_rope, k_r)) * s
    want = jnp.einsum("ht,thd->hd", jax.nn.softmax(sc, -1), v)
    # absorbed: all heads against the shared padded rows
    rows = jnp.concatenate(
        [lat, k_r, jnp.zeros((T, cfg.latent_width - r - dr), F32)], -1)
    q_abs = m._absorb(p, q_nope, q_rope)
    pr = jax.nn.softmax(jnp.einsum("hw,tw->ht", q_abs, rows) * s, -1)
    got = jnp.einsum("hc,hcd->hd", pr @ rows[:, :r], p["w_uv"])
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def _route_by_hand(p, n_group, topk_group, top_k, scale):
    """The rule stated with loops; ties to the lower index."""
    E = len(p)
    per = E // n_group
    score = [max(p[g * per:(g + 1) * per]) for g in range(n_group)]
    groups = sorted(range(n_group), key=lambda g: (-score[g], g))[:topk_group]
    masked = [p[e] if e // per in groups else 0.0 for e in range(E)]
    chosen = sorted(range(E), key=lambda e: (-masked[e], e))[:top_k]
    return chosen, [scale * masked[e] for e in chosen]


def test_group_limited_routing_against_the_rule_by_hand():
    rng = np.random.default_rng(5)
    probs = rng.random((40, 32)).astype(np.float32)
    probs[0, :] = 0.25                       # everything tied
    probs[1, 3] = probs[1, 11] = probs[1, 19] = probs[1, 27] = 0.9  # groups
    probs[2, 8:16] = 0.5                     # ties inside one group
    probs /= probs.sum(-1, keepdims=True)
    gates, idx = md.group_limited_routing(jnp.asarray(probs), 4, 2, 3, 16.0)
    dense = ref.route(jnp.asarray(probs), 4, 2, 3, 16.0)
    for t in range(len(probs)):
        chosen, g = _route_by_hand(probs[t].tolist(), 4, 2, 3, 16.0)
        assert idx[t].tolist() == chosen, t
        np.testing.assert_allclose(gates[t], g, rtol=1e-6)
        assert sorted(np.nonzero(np.asarray(dense[t]))[0].tolist()) == \
            sorted(chosen)


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts of all the shares (one routing group each) plus
    the shared expert counted once are the uncut reference layer, for the
    reference and for the program's expert layer alike."""
    full = dict(MODEL, n_routed_experts=32, held_first=0)
    assert full["router_width"] == 32
    p = FAM.make_layer(full, KEY, 1, F32)
    rng = np.random.default_rng(2)
    hn = jnp.asarray(rng.normal(size=(1, 19, MODEL["hidden_size"])), F32)
    with jax.default_matmul_precision("highest"):
        uncut = ref.moe(hn, p, full, None, held=(0, 32))
        shared = ref.swiglu(hn, p["s_gate"].astype(F32),
                            p["s_up"].astype(F32), p["s_down"].astype(F32))
        per = 32 // MODEL["n_group"]
        parts, prog_parts = [], []
        cfg = FAM.program_config(full, dtype=F32)
        x = hn[0]
        probs = jax.nn.softmax(x @ p["router"], -1)
        gates, idx = md.group_limited_routing(probs, 4, 2, 3, 16.0)
        for g in range(MODEL["n_group"]):
            sl = slice(g * per, (g + 1) * per)
            pg = dict(p, e_gate=p["e_gate"][sl], e_up=p["e_up"][sl],
                      e_down=p["e_down"][sl])
            parts.append(ref.moe(hn, pg, full, None, held=(g * per, per))
                         - shared)
            y, counts = md.held_expert_ffn(
                x, gates, idx, jnp.ones((19,), bool),
                jnp.concatenate([pg["e_gate"], pg["e_up"]], -1),
                pg["e_down"], g * per)
            prog_parts.append(y)
            # pairs routed; the fullest expert's rows x held over the
            # pairs held is its load over the mean: at least 1
            assert counts[0] == 19 * 3
            assert counts[1] == 0 or counts[3] / counts[1] >= 1.0
    np.testing.assert_allclose(sum(parts) + shared, uncut, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(sum(prog_parts)[None] + shared, uncut,
                               rtol=1e-5, atol=1e-5)


def test_pad_rows_are_not_routed():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(12, 16)), F32)
    probs = jax.nn.softmax(jnp.asarray(rng.normal(size=(12, 8)), F32))
    gates, idx = md.group_limited_routing(probs, 2, 1, 2, 1.0)
    e_gu = jnp.asarray(rng.normal(size=(8, 16, 8)), F32)
    e_down = jnp.asarray(rng.normal(size=(8, 4, 16)), F32)
    valid = jnp.arange(12) < 7
    y, counts = md.held_expert_ffn(x, gates, idx, valid, e_gu, e_down, 0)
    assert counts[0] == 14 and counts[1] == 14
    assert float(jnp.abs(y[7:]).max()) == 0.0
    assert float(jnp.abs(y[:7]).max()) > 0.0


@pytest.mark.parametrize("lens", [[0, 5, 48, 17], [48, 48, 0, 1],
                                  [8, 16, 24, 40]],
                         ids=["ragged", "full-and-dead", "block-edges"])
def test_the_latent_walk_against_jax_numpy(lens):
    rng = np.random.default_rng(0)
    N, Hq, W, V, bs, NB, MB = 4, 8, 256, 128, 8, 40, 6
    pool = jnp.asarray(rng.normal(size=(2, NB, bs, W)), F32)
    pool = pool.at[..., 192:].set(0)
    q = jnp.asarray(rng.normal(size=(N, Hq, W)), F32).at[..., 192:].set(0)
    tbl = jnp.asarray(rng.permutation(NB - 1)[:N * MB].reshape(N, MB) + 1,
                      jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    acc, m, l = pa.latent_decode_partial(q, pool, tbl, lens, layer=1,
                                         v_cols=V, sm_scale=0.3)
    rows = pool[1][tbl].reshape(N, MB * bs, W)
    s = jnp.einsum("nhw,ntw->nht", q, rows) * 0.3
    mask = jnp.arange(MB * bs)[None, None, :] < lens[:, None, None]
    p = jax.nn.softmax(jnp.where(mask, s, -1e30), -1) * mask
    want = jnp.einsum("nht,ntv->nhv", p, rows[..., :V])
    got = acc / jnp.maximum(l, 1e-30)[..., None]
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(got[live], want[live], rtol=1e-5, atol=1e-5)
    # a dead slot gives the combine's identity
    assert float(jnp.abs(acc[~live]).max(initial=0.0)) == 0.0
    assert np.all(np.asarray(l)[~live] == 0.0)
    assert np.all(np.asarray(m)[~live] == -1e30)


@pytest.mark.parametrize("feature,kw", [
    ("spec", dict(draft_params={}, draft_config="x")),
    ("prefix_cache", dict(prefix_cache=True)),
    ("kv_swap", dict(kv_swap_bytes=1 << 20)),
    ("mesh", dict(mesh="a mesh")),
    ("kv_int8", dict(kv_dtype="int8")),
    ("disagg", dict(role="decode")),
    ("decode_steps", dict(decode_steps=4))])
def test_what_the_model_cannot_do_is_refused_at_construction(feature, kw):
    cfg = FAM.program_config(MODEL, dtype=F32)
    with pytest.raises(NotImplementedError, match=feature):
        LLMEngine({}, cfg, max_slots=2, block_size=8, max_model_len=64, **kw)
