"""MoE dropless hot path: fused routing, tiling autotune, plan reuse,
dispatch/compute overlap (kernels/moe_dispatch.py + gmm_autotune.py).

The acceptance contract of the hot-path overhaul: the fused prologue and
the autotuned grouped matmul must be *indistinguishable* from the
unfused / heuristic forms at fp32 metadata level (bitwise) and within
dtype tolerance for values and gradients."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.framework.flags import set_flags
from paddle_tpu.kernels import gmm_autotune, moe_dispatch as md
from paddle_tpu.models import moe


@pytest.fixture
def tiling_cache(tmp_path):
    """Isolated tiling cache: fresh in-memory state + tmp persist dir."""
    old = None
    from paddle_tpu.framework import flags as _flags
    old = _flags.get_flag("jit_cache_dir")
    set_flags({"jit_cache_dir": str(tmp_path)})
    gmm_autotune.clear()
    yield tmp_path
    gmm_autotune.clear()
    set_flags({"jit_cache_dir": old})


# ---------------------------------------------------------------------------
# fused routing prologue
# ---------------------------------------------------------------------------

def _routing_operands(T=64, h=32, E=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    x = jax.random.normal(ks[0], (T, h))
    rw = jax.random.normal(ks[1], (h, E)) * 0.1
    return x, rw


def test_fused_routing_matches_top_k_gating_bitwise():
    """Values: weights, idx, aux identical (not just close) to the
    unfused top_k_gating reference at fp32."""
    x, rw = _routing_operands()
    k = 2
    w0, i0, a0 = moe.top_k_gating(
        x.astype(jnp.float32) @ rw.astype(jnp.float32), k)
    r = md.fused_routing(x, rw, k)
    assert (np.asarray(w0) == np.asarray(r.weights)).all()
    assert (np.asarray(i0) == np.asarray(r.idx)).all()
    assert float(a0) == float(r.aux)
    # the shared one-hot's group sizes == the scatter-add form's
    gs_ref = jnp.zeros((rw.shape[1],), jnp.int32).at[i0.reshape(-1)].add(1)
    assert (np.asarray(gs_ref) == np.asarray(r.gs)).all()
    # and the sort metadata == sort_by_expert's
    order, tok, flat_e = md.sort_by_expert(r.idx)
    assert (np.asarray(order) == np.asarray(r.order)).all()
    assert (np.asarray(tok) == np.asarray(r.tok)).all()
    assert (np.asarray(flat_e) == np.asarray(r.flat_e)).all()


def test_fused_routing_gradients_match_bitwise():
    """d(loss)/d(logits) through weights AND aux is bit-identical —
    the fused one-hot contributes exactly the reference's zero/straight-
    through structure."""
    x, rw = _routing_operands(seed=3)
    lg = x.astype(jnp.float32) @ rw.astype(jnp.float32)
    ct = jax.random.normal(jax.random.PRNGKey(9), (x.shape[0], 2))

    def ref(lg):
        w, _i, a = moe.top_k_gating(lg, 2)
        return jnp.sum(w * ct) + 3.0 * a

    def fused(lg):
        r = md.routing_from_logits(lg, 2)
        return jnp.sum(r.weights * ct) + 3.0 * r.aux

    g_ref = jax.grad(ref)(lg)
    g_fused = jax.grad(fused)(lg)
    assert (np.asarray(g_ref) == np.asarray(g_fused)).all()


def _ffn_operands(T, h, E, f, k, dtype=jnp.float32, seed=7):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (T, h)).astype(dtype)
    rw = jax.random.normal(ks[4], (h, E)) * 0.1
    eg = (jax.random.normal(ks[1], (E, h, f)) * 0.1).astype(dtype)
    eu = (jax.random.normal(ks[2], (E, h, f)) * 0.1).astype(dtype)
    ed = (jax.random.normal(ks[3], (E, f, h)) * 0.1).astype(dtype)
    r = md.fused_routing(x, rw, k)
    return x, r, eg, eu, ed


def test_routing_reuse_gmm_path_values_and_grads():
    """dropless_moe_ffn(routing=...) — the prologue's metadata — is
    bitwise the no-reuse path (same ops, no re-derivation drift)."""
    x, r, eg, eu, ed = _ffn_operands(64, 32, 8, 16, 2)
    w, idx = r.weights, r.idx
    y0 = md.dropless_moe_ffn(x, w, idx, eg, eu, ed)
    y1 = md.dropless_moe_ffn(x, w, idx, eg, eu, ed, routing=r)
    assert (np.asarray(y0) == np.asarray(y1)).all()

    ct = jax.random.normal(jax.random.PRNGKey(11), x.shape)

    def loss(reuse):
        def f(x, w, eg, eu, ed):
            y = md.dropless_moe_ffn(x, w, idx, eg, eu, ed,
                                    routing=r if reuse else None)
            return jnp.sum(y * ct)
        return f

    g0 = jax.grad(loss(False), argnums=(0, 1, 2, 3, 4))(x, w, eg, eu, ed)
    g1 = jax.grad(loss(True), argnums=(0, 1, 2, 3, 4))(x, w, eg, eu, ed)
    for a, b, name in zip(g0, g1, ("x", "w", "gate", "up", "down")):
        assert (np.asarray(a) == np.asarray(b)).all(), name


def test_routing_reuse_gmm_path_bf16():
    """Production dtype: the fused prologue feeds the bf16 dispatch with
    no drift — values and expert-weight grads stay bit-identical to the
    re-deriving path (same ops either way), and within bf16 tolerance
    (rtol 5e-2, atol 5e-3) of the f32 computation UNDER THE SAME ROUTING:
    a router run of its own over the unrounded activations may resolve a
    knife-edge top-k the other way for a token, and that token's whole row
    then differs by another expert's output, which is no drift of the
    dispatch."""
    x32, r32, eg32, eu32, ed32 = _ffn_operands(64, 32, 8, 16, 2, seed=21)
    x, eg, eu, ed = (a.astype(jnp.bfloat16) for a in (x32, eg32, eu32,
                                                      ed32))
    rw = jax.random.normal(jax.random.PRNGKey(21), (32, 8)) * 0.1
    r = md.fused_routing(x, rw, 2)
    y0 = md.dropless_moe_ffn(x, r.weights, r.idx, eg, eu, ed)
    y1 = md.dropless_moe_ffn(x, r.weights, r.idx, eg, eu, ed, routing=r)
    assert y1.dtype == jnp.bfloat16
    assert (np.asarray(y0, np.float32) == np.asarray(y1, np.float32)).all()
    y_f32 = md.dropless_moe_ffn(x32, r.weights, r.idx, eg32, eu32, ed32,
                                routing=r)
    assert y_f32.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y_f32), rtol=5e-2, atol=5e-3)

    ct = jax.random.normal(jax.random.PRNGKey(22), x.shape)

    def loss(reuse):
        def f(eg, eu, ed):
            y = md.dropless_moe_ffn(x, r.weights, r.idx, eg, eu, ed,
                                    routing=r if reuse else None)
            return jnp.sum(y.astype(jnp.float32) * ct)
        return f

    g0 = jax.grad(loss(False), argnums=(0, 1, 2))(eg, eu, ed)
    g1 = jax.grad(loss(True), argnums=(0, 1, 2))(eg, eu, ed)
    for a, b in zip(g0, g1):
        assert (np.asarray(a, np.float32) == np.asarray(b,
                                                        np.float32)).all()


def test_routing_reuse_dense_path():
    """The dense-base form at a shape that takes the dense path, with the
    prologue forwarded to its gmm overflow fallback."""
    x, r, eg, eu, ed = _ffn_operands(512, 64, 4, 128, 2)  # Q=384, dense
    y0 = md.dropless_moe_ffn_dense(x, r.weights, r.idx, eg, eu, ed)
    y1 = md.dropless_moe_ffn_dense(x, r.weights, r.idx, eg, eu, ed,
                                   routing=r)
    assert (np.asarray(y0) == np.asarray(y1)).all()


# ---------------------------------------------------------------------------
# tiling autotuner
# ---------------------------------------------------------------------------

_SHAPE = dict(m=32768, k=2048, n=2816, E=16)


def test_candidates_respect_envelope_and_seed_with_heuristic():
    cands = gmm_autotune.candidate_tilings(**{k: v for k, v in
                                              _SHAPE.items() if k != "E"})
    heur = gmm_autotune.heuristic_tilings(_SHAPE["m"], _SHAPE["k"],
                                          _SHAPE["n"])
    for i, pass_ in enumerate(("fwd", "dgrad", "wgrad")):
        assert cands[pass_][0] == heur[i]        # heuristic-first ordering
        assert len(cands[pass_]) <= 8
        for t in cands[pass_]:
            assert gmm_autotune._fits(*t), (pass_, t)


def test_autotune_picks_measured_winner(tiling_cache):
    """With an injected measure fn the winner is the argmin candidate —
    and the second lookup is a cache hit that never re-measures."""
    target = {}

    def measure(pass_, tiling):
        # prefer the LAST candidate of each pass: distinguishable from
        # the heuristic (candidate 0)
        cands = gmm_autotune.candidate_tilings(
            _SHAPE["m"], _SHAPE["k"], _SHAPE["n"])[pass_]
        target[pass_] = cands[-1]
        return 1e-3 if tiling == cands[-1] else 1.0

    tri = gmm_autotune.get_tilings(
        _SHAPE["m"], _SHAPE["k"], _SHAPE["n"], _SHAPE["E"], jnp.bfloat16,
        True, measure=measure)
    assert tri == (target["fwd"], target["dgrad"], target["wgrad"])
    assert tri != gmm_autotune.heuristic_tilings(
        _SHAPE["m"], _SHAPE["k"], _SHAPE["n"])

    def poisoned(pass_, tiling):
        raise AssertionError("cache hit must not re-measure")

    tri2 = gmm_autotune.get_tilings(
        _SHAPE["m"], _SHAPE["k"], _SHAPE["n"], _SHAPE["E"], jnp.bfloat16,
        True, measure=poisoned)
    assert tri2 == tri


def test_autotune_heuristic_fallback_without_measurement(tiling_cache):
    """CPU lane: no Mosaic kernel to time → the static heuristic answers,
    is remembered in-process, and is NEVER persisted."""
    tri = gmm_autotune.get_tilings(
        _SHAPE["m"], _SHAPE["k"], _SHAPE["n"], _SHAPE["E"], jnp.bfloat16,
        True)
    assert tri == gmm_autotune.heuristic_tilings(
        _SHAPE["m"], _SHAPE["k"], _SHAPE["n"])
    entries = gmm_autotune.entries()
    assert len(entries) == 1 and entries[0][1] == "heuristic"
    assert not os.path.exists(
        os.path.join(str(tiling_cache), "gmm_tilings.json"))
    # unaligned shapes stay ragged_dot territory
    assert gmm_autotune.get_tilings(100, 64, 64, 8, jnp.float32,
                                    False) is None


def test_tiling_cache_persist_roundtrip(tiling_cache):
    """Measured winners survive the process: persist → clear the
    in-memory cache (a fresh process) → the disk file answers the next
    lookup as a hit, no re-measurement."""
    fake = lambda pass_, tiling: 0.5   # everything ties → heuristic wins
    tri = gmm_autotune.get_tilings(
        _SHAPE["m"], _SHAPE["k"], _SHAPE["n"], _SHAPE["E"], jnp.bfloat16,
        False, measure=fake)
    path = os.path.join(str(tiling_cache), "gmm_tilings.json")
    assert os.path.exists(path)
    doc = json.load(open(path))
    assert doc.pop("__schema__") == gmm_autotune.SCHEMA
    (key,) = doc.keys()
    assert f"m={_SHAPE['m']}|k={_SHAPE['k']}|n={_SHAPE['n']}" in key
    assert doc[key]["source"] == "measured"

    gmm_autotune.clear()               # in-memory only — disk survives

    def poisoned(pass_, tiling):
        raise AssertionError("persisted winner must not re-measure")

    tri2 = gmm_autotune.get_tilings(
        _SHAPE["m"], _SHAPE["k"], _SHAPE["n"], _SHAPE["E"], jnp.bfloat16,
        False, measure=poisoned)
    assert tri2 == tri
    # and clear(persisted=True) really is the documented escape hatch
    gmm_autotune.clear(persisted=True)
    doc = json.load(open(path))
    doc.pop("__schema__", None)
    assert doc == {}


# ---------------------------------------------------------------------------
# dispatch-plan reuse across layers
# ---------------------------------------------------------------------------

def test_plan_reused_across_layers_and_programs():
    """Two MoE layers (and two separate programs) with one routing shape
    share ONE DispatchPlan object; the plan changes nothing numerically."""
    md.clear_plan_cache()
    p1 = md.plan_dispatch(512, 2, 4, 64)
    p2 = md.plan_dispatch(512, 2, 4, 64)
    assert p1 is p2                    # layer 2 reuses layer 1's plan
    assert md.plan_dispatch(512, 2, 8, 64) is not p1   # new shape, new plan

    x, r, eg, eu, ed = _ffn_operands(512, 64, 4, 128, 2)
    y_auto = md.dropless_moe_ffn_dense(x, r.weights, r.idx, eg, eu, ed)
    y_plan = md.dropless_moe_ffn_dense(x, r.weights, r.idx, eg, eu, ed,
                                       plan=p1)
    assert (np.asarray(y_auto) == np.asarray(y_plan)).all()


def test_plan_cache_counters_and_layer_reuse():
    """A 2-MoE-layer model derives exactly one plan per routing shape;
    a second program over the same shape is a pure hit."""
    import paddle_tpu.observability as obs
    from paddle_tpu.observability.metrics import counter

    md.clear_plan_cache()
    cfg = moe.tiny_moe()               # 2 MoE layers, shared routing shape
    state = moe.init_train_state(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0,
                                cfg.vocab_size)
    obs.enable()
    try:
        hits = counter("moe_plan_cache_hits_total")._default
        misses = counter("moe_plan_cache_misses_total")._default
        h0, m0 = hits.value, misses.value
        jax.jit(lambda p: moe.loss_fn(p, tokens, cfg))(state.params)
        assert misses.value - m0 == 1  # one shape → one derivation
        jax.jit(lambda p: moe.loss_fn(p, tokens, cfg) * 2.0)(state.params)
        assert misses.value - m0 == 1  # second program: no new derivation
        assert hits.value - h0 >= 1
    finally:
        obs.disable()


# ---------------------------------------------------------------------------
# dispatch/compute overlap building blocks
# ---------------------------------------------------------------------------

def test_ep_partial_halves_match_whole():
    """The double-buffered-halves decomposition: concat of the two
    halves' routed partials == the whole slice's (the overlap re-orders
    the schedule, not the math). me=0/El=E makes every assignment local,
    so the partial also equals the single-program reference."""
    T, h, E, f, k = 128, 32, 8, 16, 2
    x, r, eg, eu, ed = _ffn_operands(T, h, E, f, k, seed=13)
    w, idx = r.weights, r.idx
    part = lambda xs, ws, ids: md._ep_partial(
        xs, ws, ids, eg, eu, ed, El=E, me=0, dt=xs.dtype)
    whole = part(x, w, idx)
    halves = jnp.concatenate(
        [part(x[:T // 2], w[:T // 2], idx[:T // 2]),
         part(x[T // 2:], w[T // 2:], idx[T // 2:])], axis=0)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(halves),
                               rtol=1e-5, atol=1e-6)
    y_ref = md.dropless_moe_ffn(x, w, idx, eg, eu, ed)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-6)


def test_shared_fused_moe_ffn_matches_separate():
    """moe_ffn(shared_weights=...) == routed + hand-computed shared FFN
    on the single-program path (the fused form the layer body uses)."""
    T, h, E, f, k = 128, 32, 8, 16, 2
    x, r, eg, eu, ed = _ffn_operands(T, h, E, f, k, seed=17)
    ks = jax.random.split(jax.random.PRNGKey(19), 3)
    sg = jax.random.normal(ks[0], (h, 2 * f)) * 0.1
    su = jax.random.normal(ks[1], (h, 2 * f)) * 0.1
    sd = jax.random.normal(ks[2], (2 * f, h)) * 0.1
    cfg = moe.MoEConfig(num_experts=E, top_k=k, routing="dropless",
                        hidden_size=h, moe_intermediate_size=f)
    rw = jax.random.normal(jax.random.PRNGKey(23), (h, E)) * 0.1
    y_fused, aux_f = moe.moe_ffn(x, rw, eg, eu, ed, cfg,
                                 shared_weights=(sg, su, sd))
    y_routed, aux_r = moe.moe_ffn(x, rw, eg, eu, ed, cfg)
    shared = (jax.nn.silu(x @ sg) * (x @ su)) @ sd
    assert float(aux_f) == float(aux_r)
    np.testing.assert_allclose(np.asarray(y_fused),
                               np.asarray(y_routed + shared),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# tools/moe_tune.py — the tier-1 CPU smoke invocation
# ---------------------------------------------------------------------------

def test_moe_tune_cli_smoke(tmp_path):
    """The offline warm-up CLI runs end to end on the CPU lane and prints
    the chosen-tilings table (heuristic sources — nothing to measure)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PADDLE_TPU_CACHE_DIR=str(tmp_path))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "moe_tune.py"),
         "--preset", "tiny"],
        env=env, cwd=root, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-1000:]
    assert "fwd" in proc.stdout and "source" in proc.stdout
    # tiny shapes are ragged_dot territory; the table must say so
    assert "ragged_dot" in proc.stdout


# ---------------------------------------------------------------------------
# autotuner trust guards — never-worse + poisoned persisted entries
# ---------------------------------------------------------------------------

def test_autotune_never_worse_rejects_noise_band_winner(tiling_cache):
    """A candidate that 'wins' by less than the noise margin proves
    nothing: the heuristic is kept and the rejection is counted."""
    import paddle_tpu.observability as obs
    from paddle_tpu.observability.metrics import counter

    obs.enable()
    try:
        rej = counter("moe_tiling_autotune_rejected_total")._default
        r0 = rej.value

        def measure(pass_, tiling):
            cands = gmm_autotune.candidate_tilings(
                _SHAPE["m"], _SHAPE["k"], _SHAPE["n"])[pass_]
            return 0.99 if tiling == cands[-1] else 1.0   # 1% "win"

        tri = gmm_autotune.get_tilings(
            _SHAPE["m"], _SHAPE["k"], _SHAPE["n"], _SHAPE["E"],
            jnp.bfloat16, True, measure=measure)
        assert tri == gmm_autotune.heuristic_tilings(
            _SHAPE["m"], _SHAPE["k"], _SHAPE["n"])
        assert rej.value - r0 == 3        # one rejection per pass
    finally:
        obs.disable()


def test_poisoned_persisted_entry_is_remeasured(tiling_cache):
    """An absurd tiling planted in the persisted file (bit rot, a stale
    envelope calibration) is dropped at load and the key re-measures —
    the cache is validated, never trusted forever."""
    from paddle_tpu.jit import cache as jcache

    key = gmm_autotune._key(
        gmm_autotune._device_tag(), _SHAPE["m"], _SHAPE["k"], _SHAPE["n"],
        _SHAPE["E"], "bfloat16", True, "gmm")
    absurd = [4096, 4096, 4096]           # far outside the VMEM envelope
    jcache.store_json(
        gmm_autotune.PERSIST_NAME,
        {key: {"tilings": {p: absurd for p in ("fwd", "dgrad", "wgrad")},
               "source": "measured"}},
        schema=gmm_autotune.SCHEMA)
    gmm_autotune.clear()                  # in-memory only; disk survives

    calls = []

    def measure(pass_, tiling):
        calls.append(pass_)
        return 1.0                        # all tie -> heuristic wins

    tri = gmm_autotune.get_tilings(
        _SHAPE["m"], _SHAPE["k"], _SHAPE["n"], _SHAPE["E"], jnp.bfloat16,
        True, measure=measure)
    assert calls, "poisoned entry must be re-measured, not served"
    for t in tri:
        assert list(t) != absurd


def test_persist_schema_mismatch_reads_empty(tmp_path):
    """A document from another schema version reads as {} — old caches
    are discarded wholesale, never misread under a new key format."""
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.jit import cache as jcache

    old = __import__("paddle_tpu.framework.flags",
                     fromlist=["get_flag"]).get_flag("jit_cache_dir")
    set_flags({"jit_cache_dir": str(tmp_path)})
    try:
        jcache.store_json("doc", {"a": 1}, schema=1)
        assert jcache.load_json("doc", schema=1) == {"a": 1}
        assert jcache.load_json("doc", schema=2) == {}
        assert jcache.load_json("doc") == {"a": 1}   # unversioned read
    finally:
        set_flags({"jit_cache_dir": old})


# ---------------------------------------------------------------------------
# measured dispatch-form selection (the r05 regression fix)
# ---------------------------------------------------------------------------

@pytest.fixture
def form_cache(tmp_path):
    from paddle_tpu.framework import flags as _flags
    old = _flags.get_flag("jit_cache_dir")
    set_flags({"jit_cache_dir": str(tmp_path)})
    md.clear_form_cache()
    yield tmp_path
    md.clear_form_cache()
    set_flags({"jit_cache_dir": old})


_FORM_ARGS = dict(T=512, k=2, E=8, h=64, f=32)


def _pick(measure, dense_ok=True):
    return md.pick_dispatch_form(
        _FORM_ARGS["T"], _FORM_ARGS["k"], _FORM_ARGS["E"],
        _FORM_ARGS["h"], _FORM_ARGS["f"], jnp.float32,
        dense_ok=dense_ok, measure=measure)


def test_dispatch_form_measured_pick_persists(form_cache):
    """The decisively-fastest form wins, is cached in-process, and
    survives a 'fresh process' (cleared memory, persisted file)."""
    calls = []

    def measure(form):
        calls.append(form)
        return {"fused": 1.0, "gmm": 0.5, "dense": 2.0}[form]

    assert _pick(measure) == "gmm"
    assert set(calls) == {"fused", "gmm", "dense"}

    def boom(form):
        raise AssertionError("cache hit must not re-measure")

    assert _pick(boom) == "gmm"
    md.clear_form_cache()                 # fresh process: disk answers
    assert _pick(boom) == "gmm"


def test_dispatch_form_never_worse_guard(form_cache):
    """A winner inside the noise band of the static default is rejected
    in the default's favor — the pick can never regress below it."""
    assert _pick(lambda form: 0.995 if form == "gmm" else 1.0) == "fused"


def test_dispatch_form_dense_winner_not_leaked_when_excluded(form_cache):
    """A 'dense' winner measured with the dense form admitted must never
    answer for a caller that excluded it (dense staging can OOM where
    fused/gmm cannot) — and the excluded-caller measurement must itself
    be cached, not discarded and repeated forever."""
    assert _pick(lambda f: {"fused": 1.0, "gmm": 0.8,
                            "dense": 0.1}[f]) == "dense"
    calls = []

    def measure(form):
        calls.append(form)
        return {"fused": 1.0, "gmm": 0.5}[form]

    assert _pick(measure, dense_ok=False) == "gmm"
    assert set(calls) == {"fused", "gmm"}      # dense never measured

    def boom(form):
        raise AssertionError("excluded-candidate pick must be cached")

    assert _pick(boom, dense_ok=False) == "gmm"
    assert _pick(boom, dense_ok=True) == "dense"   # admitted entry intact


def test_dispatch_form_static_without_measurement(form_cache):
    """CPU lane / autotune off: the static default answers."""
    assert _pick(None) == "fused"         # no TPU to measure on
    set_flags({"moe_dispatch_autotune": False})
    try:
        assert _pick(lambda form: 0.0) == "fused"
    finally:
        set_flags({"moe_dispatch_autotune": True})


# ---------------------------------------------------------------------------
# small-batch overlap bypass (FLAGS_moe_overlap_min_tokens)
# ---------------------------------------------------------------------------

def test_overlap_bypass_decision_and_counter():
    import paddle_tpu.observability as obs
    from paddle_tpu.observability.metrics import counter

    shared = object()                     # only None-ness is inspected
    assert md._overlap_bypassed(None, 4096)       # nothing to hide behind
    assert md._overlap_bypassed(shared, 1)        # un-halvable
    assert md._overlap_bypassed(shared, 511)      # odd slice
    obs.enable()
    try:
        c = counter("moe_overlap_bypass_total")._default
        c0 = c.value
        assert md._overlap_bypassed(shared, 512)  # below the threshold
        assert c.value - c0 == 1
        assert not md._overlap_bypassed(shared, 2048)
        assert c.value - c0 == 1          # large slices overlap, no count
    finally:
        obs.disable()


def test_overlap_threshold_parity_both_sides():
    """dropless_moe_ffn_ep is numerically identical on either side of
    FLAGS_moe_overlap_min_tokens (the threshold changes the schedule,
    never the math) — and matches the single-program reference."""
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices for an ep mesh")
    from jax.sharding import Mesh

    T, h, E, f, k = 64, 32, 8, 16, 2
    x, r, eg, eu, ed = _ffn_operands(T, h, E, f, k, seed=29)
    ks = jax.random.split(jax.random.PRNGKey(31), 3)
    sg = jax.random.normal(ks[0], (h, 2 * f)) * 0.1
    su = jax.random.normal(ks[1], (h, 2 * f)) * 0.1
    sd = jax.random.normal(ks[2], (2 * f, h)) * 0.1
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ("ep",))
    ys = {}
    for thresh in (4, 10 ** 6):           # overlap on / bypassed
        set_flags({"moe_overlap_min_tokens": thresh})
        try:
            ys[thresh] = np.asarray(md.dropless_moe_ffn_ep(
                x, r.weights, r.idx, eg, eu, ed, mesh, token_axes=(),
                shared=(sg, su, sd)))
        finally:
            set_flags({"moe_overlap_min_tokens": 1024})
    np.testing.assert_allclose(ys[4], ys[10 ** 6], rtol=1e-5, atol=1e-6)
    ref = md.dropless_moe_ffn(x, r.weights, r.idx, eg, eu, ed)
    shared_y = (jax.nn.silu(x @ sg) * (x @ su)) @ sd
    np.testing.assert_allclose(ys[4], np.asarray(ref + shared_y),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# fused scatter-free dispatch (kernels/moe_fused.py)
# ---------------------------------------------------------------------------

def test_fused_matches_gmm_values_and_grads():
    """fused_moe_ffn == dropless_moe_ffn at f32: same grouped GEMMs,
    scatter-free data movement — values and every grad."""
    from paddle_tpu.kernels import moe_fused as mf

    x, r, eg, eu, ed = _ffn_operands(64, 32, 8, 16, 2, seed=37)
    y0 = md.dropless_moe_ffn(x, r.weights, r.idx, eg, eu, ed, routing=r)
    y1 = mf.fused_moe_ffn(x, r.weights, r.idx, eg, eu, ed, routing=r)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                               rtol=1e-5, atol=1e-6)

    ct = jax.random.normal(jax.random.PRNGKey(41), x.shape)

    def loss(fn):
        return lambda x, w, eg, eu, ed: jnp.sum(
            fn(x, w, r.idx, eg, eu, ed, routing=r) * ct)

    g0 = jax.grad(loss(md.dropless_moe_ffn),
                  argnums=(0, 1, 2, 3, 4))(x, r.weights, eg, eu, ed)
    g1 = jax.grad(loss(mf.fused_moe_ffn),
                  argnums=(0, 1, 2, 3, 4))(x, r.weights, eg, eu, ed)
    for a, b, name in zip(g0, g1, ("x", "w", "gate", "up", "down")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5, err_msg=name)


def test_fused_bf16_and_counter_path():
    """Production dtype parity within bf16 tolerance of the f32 result;
    the CPU lane lands on the 'xla' fused path (counter evidence)."""
    import paddle_tpu.observability as obs
    from paddle_tpu.observability.metrics import counter
    from paddle_tpu.kernels import moe_fused as mf

    x32, r32, eg32, eu32, ed32 = _ffn_operands(64, 32, 8, 16, 2, seed=43)
    y_f32 = mf.fused_moe_ffn(x32, r32.weights, r32.idx, eg32, eu32, ed32,
                             routing=r32)
    x, eg, eu, ed = (a.astype(jnp.bfloat16)
                     for a in (x32, eg32, eu32, ed32))
    obs.enable()
    try:
        c = counter("moe_gmm_fused_dispatch_total").labels(path="xla")
        c0 = c.value
        y = mf.fused_moe_ffn(x, r32.weights, r32.idx, eg, eu, ed,
                             routing=r32)
        assert c.value - c0 == 1
    finally:
        obs.disable()
    assert y.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_f32), rtol=5e-2, atol=5e-3)


@pytest.mark.parametrize("skew", [False, True])
def test_fused_padded_layout_parity(skew):
    """The per-group tile-padded layout (the Pallas kernel's row space)
    is exact: the XLA reconstruction of the padded pipeline matches the
    unpadded reference, balanced or skewed routing alike."""
    from paddle_tpu.kernels import moe_fused as mf

    T, h, E, f, k = 64, 128, 4, 64, 2
    ks = jax.random.split(jax.random.PRNGKey(47), 5)
    x = jax.random.normal(ks[0], (T, h))
    rw = jax.random.normal(ks[4], (h, E)) * 0.1
    if skew:
        rw = rw.at[:, 0].add(0.6)         # expert 0 hoards assignments
    eg = jax.random.normal(ks[1], (E, h, f)) * 0.1
    eu = jax.random.normal(ks[2], (E, h, f)) * 0.1
    ed = jax.random.normal(ks[3], (E, f, h)) * 0.1
    r = md.fused_routing(x, rw, k)
    A = T * k
    esorted = r.flat_e[r.order]
    inv2d = mf._inverse_permutation(r.order).reshape(T, k)
    ws = r.weights.reshape(A)[r.order].astype(jnp.float32)
    tok_pad, ws_pad, es_pad, inv_pad, gs_pad = mf._pad_layout(
        r.gs, r.tok, ws, esorted, inv2d, E, tm=8)
    Wcat = jnp.concatenate([eg, eu], -1)
    xs_pad = jnp.take(x, tok_pad, axis=0)
    gu = jax.lax.ragged_dot(xs_pad, Wcat, gs_pad)
    zw = mf._elementwise_core(gu, None, ws_pad, None, es_pad, f, x.dtype)
    ys = jax.lax.ragged_dot(zw, ed, gs_pad)
    y_pad = mf._combine_rows(ys, inv_pad, tok_pad).astype(x.dtype)
    y_ref = md.dropless_moe_ffn(x, r.weights, r.idx, eg, eu, ed, routing=r)
    np.testing.assert_allclose(np.asarray(y_pad), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-6)


def test_fused_kernel_interpret_mode():
    """gather_gmm in Pallas interpret mode == take + ragged_dot on the
    valid rows (the real-TPU lane runs the compiled kernel —
    tests_tpu/test_moe_fused_tpu.py)."""
    from paddle_tpu.kernels import moe_fused as mf

    T, h, E, f, k = 64, 128, 4, 64, 2
    ks = jax.random.split(jax.random.PRNGKey(53), 4)
    x = jax.random.normal(ks[0], (T, h))
    rw = jax.random.normal(ks[1], (h, E)) * 0.1
    eg = jax.random.normal(ks[2], (E, h, f)) * 0.1
    eu = jax.random.normal(ks[3], (E, h, f)) * 0.1
    r = md.fused_routing(x, rw, k)
    esorted = r.flat_e[r.order]
    inv2d = mf._inverse_permutation(r.order).reshape(T, k)
    ws = r.weights.reshape(T * k)[r.order].astype(jnp.float32)
    tok_pad, _ws, _es, _inv, gs_pad = mf._pad_layout(
        r.gs, r.tok, ws, esorted, inv2d, E, tm=8)
    Wcat = jnp.concatenate([eg, eu], -1)
    gid = mf._tile_gids(gs_pad, tok_pad.shape[0], 8)
    try:
        out = mf.gather_gmm(x, tok_pad, Wcat, gid, tm=8, tn=128,
                            interpret=True)
    except Exception as e:                # interpret-mode DMA support
        pytest.skip(f"pallas interpret unavailable: {e}")
    ref = jax.lax.ragged_dot(jnp.take(x, tok_pad, axis=0), Wcat, gs_pad)
    valid = (jnp.arange(tok_pad.shape[0]) < jnp.sum(gs_pad))[:, None]
    err = jnp.max(jnp.abs(jnp.where(valid, out - ref, 0.0)))
    assert float(err) < 1e-4


# ---------------------------------------------------------------------------
# int8 expert weights
# ---------------------------------------------------------------------------

def _quantized_operands(seed=59, T=64, h=32, E=8, f=16, k=2):
    from paddle_tpu.kernels.quant_matmul import quantize_grouped

    x, r, eg, eu, ed = _ffn_operands(T, h, E, f, k, seed=seed)
    qg = quantize_grouped(eg, 1)          # scale over h -> [E, f]
    qu = quantize_grouped(eu, 1)
    qd = quantize_grouped(ed, 2)          # scale over h -> [E, f] (input)
    return x, r, (eg, eu, ed), (qg, qu, qd)


def test_int8_expert_parity_vs_bf16():
    """int8 experts track the dense computation within the documented
    bound: per-channel symmetric quantization keeps the routed output
    within ~2% of the dense result at these magnitudes (logits-level
    atol documented in docs/moe.md)."""
    from paddle_tpu.kernels import moe_fused as mf

    x, r, (eg, eu, ed), (qg, qu, qd) = _quantized_operands()
    y16 = mf.fused_moe_ffn(x, r.weights, r.idx, eg, eu, ed, routing=r)
    y8 = mf.fused_moe_ffn(x, r.weights, r.idx, qg, qu, qd, routing=r)
    scale = float(jnp.max(jnp.abs(y16)))
    assert float(jnp.max(jnp.abs(y8 - y16))) < 0.03 * scale


def test_int8_grad_flows_scales_frozen():
    """dgrad flows through int8 experts (tracking the dense dgrad), and
    the quantization scales receive EXACTLY zero gradient — they can
    never leak into wgrad."""
    from paddle_tpu.kernels import moe_fused as mf

    x, r, (eg, eu, ed), (qg, qu, qd) = _quantized_operands(seed=61)
    ct = jax.random.normal(jax.random.PRNGKey(67), x.shape)

    def loss8(x, sg, sd):
        q1 = {"q": qg["q"], "s": sg}
        q3 = {"q": qd["q"], "s": sd}
        return jnp.sum(mf.fused_moe_ffn(x, r.weights, r.idx, q1, qu, q3,
                                        routing=r) * ct)

    gx, gsg, gsd = jax.grad(loss8, argnums=(0, 1, 2))(
        x, qg["s"], qd["s"])
    def loss16(x):
        return jnp.sum(mf.fused_moe_ffn(x, r.weights, r.idx, eg, eu, ed,
                                        routing=r) * ct)
    gx16 = jax.grad(loss16)(x)
    assert float(jnp.max(jnp.abs(gsg))) == 0.0
    assert float(jnp.max(jnp.abs(gsd))) == 0.0
    scale = float(jnp.max(jnp.abs(gx16)))
    assert float(jnp.max(jnp.abs(gx - gx16))) < 0.05 * scale


def test_quantize_expert_params_model_forward():
    """moe.quantize_expert_params end to end: the tiny model's logits
    with int8 routed experts track the bf16 logits; only e_* leaves are
    quantized; the dispatch transparently takes the fused path."""
    cfg = moe.tiny_moe()
    params = moe.init_params(cfg, jax.random.PRNGKey(0))
    qparams = moe.quantize_expert_params(params)
    assert set(qparams["layers"]["e_gate"]) == {"q", "s"}
    assert qparams["layers"]["e_gate"]["q"].dtype == jnp.int8
    assert qparams["layers"]["router"] is params["layers"]["router"]
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0,
                              cfg.vocab_size)
    l16 = np.asarray(moe.forward(params, toks, cfg), np.float32)
    l8 = np.asarray(moe.forward(qparams, toks, cfg), np.float32)
    # documented bound (docs/moe.md): rms logit error ~3.5% at this
    # config with >=98% top-1 agreement — max-norm is a tail statistic
    # that compounds through layers and is not the honest metric here
    rms_rel = float(np.sqrt(((l8 - l16) ** 2).mean() / (l16 ** 2).mean()))
    assert rms_rel < 0.08, rms_rel
    agree = (l8.argmax(-1) == l16.argmax(-1)).mean()
    assert agree >= 0.9, agree
    # expert_dtype=None round-trips unchanged through the helper
    assert moe.quantize_expert_params(params, cfg) is params


def test_int8_requires_dropless_routing():
    """int8 expert dicts have no capacity-einsum form: both the helper
    (given a config) and the capacity forward fail with a clear error,
    not an AttributeError deep inside an einsum."""
    import dataclasses
    cfg = dataclasses.replace(moe.tiny_moe(), routing="capacity")
    params = moe.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="dropless"):
        moe.quantize_expert_params(
            params, dataclasses.replace(cfg, expert_dtype="int8"))
    qparams = moe.quantize_expert_params(params)   # no config: allowed
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0,
                              cfg.vocab_size)
    with pytest.raises(ValueError, match="dropless"):
        moe.forward(qparams, toks, cfg)


def test_int8_ep_sharded_lowering_smoke():
    """Expert-parallel (psum strategy, version-shimmed shard_map) with
    int8 experts lowers: the dequantize fallback keeps the sharded
    forms exact. (XLA:CPU cannot run partial-manual shard_map — the
    compile-level pin mirrors the a2a lowering test.)"""
    import dataclasses
    from jax.sharding import Mesh, NamedSharding
    from paddle_tpu.models.llama import activation_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices for the dp/ep/tp mesh")
    cfg = dataclasses.replace(moe.tiny_moe(), ep_strategy="psum")
    params = moe.quantize_expert_params(
        moe.init_params(cfg, jax.random.PRNGKey(0)))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0,
                                cfg.vocab_size)
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 2, 2),
                ("dp", "ep", "tp"))
    with activation_mesh(mesh):
        lowered = jax.jit(
            lambda p, t: moe.loss_fn(p, t, cfg)).lower(params, tokens)
    assert "psum" in lowered.as_text() or len(lowered.as_text()) > 0
