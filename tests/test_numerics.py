"""Tier-1 tests for the numerics observatory (observability.numerics):
the disabled-path contract (identical jaxpr, zero device ops, <5%
overhead), quant-error gauge correctness against a hand-computed
reference for all three int8 sites, the per-layer stats ladder, the
NaN-provenance walk (earliest of two bad layers), and the seeded
nan_inject fault proving provenance end-to-end through the resilient
train loop and the flight-recorder post-mortem."""
import dataclasses
import math
import os
import re
import sys
import time

import numpy as np
import pytest

import paddle_tpu  # noqa: F401  (forces the CPU/virtual-device conftest setup)
import jax
import jax.numpy as jnp

import paddle_tpu.observability as obs
from paddle_tpu.framework.flags import set_flags
from paddle_tpu.observability import flight_recorder, numerics
from paddle_tpu.models import llama, moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _jaxpr_str(jx):
    """Jaxpr text with memory addresses normalized: custom_vjp closures
    embed `<function ... at 0x...>` reprs that differ per trace while
    the program is identical."""
    return re.sub(r"0x[0-9a-f]+", "0x", str(jx))


@pytest.fixture
def numerics_on():
    """Enabled obs + numerics over a zeroed registry/ring; restores the
    default-off state afterwards."""
    obs.get_registry().reset()
    flight_recorder.get_recorder().clear()
    numerics.clear()
    obs.enable()
    numerics.enable()
    try:
        yield
    finally:
        numerics.disable()
        obs.disable()
        set_flags({"obs_postmortem_dir": ""})
        numerics.clear()
        obs.get_registry().reset()
        flight_recorder.get_recorder().clear()


def _tiny_cfg():
    return llama.tiny_llama(vocab=64, hidden=32, layers=2, heads=4,
                            kv_heads=2, seq=64, ffn=64)


# -- disabled-path contract -------------------------------------------------
def test_disabled_path_jaxpr_identical_to_uninstrumented():
    """FLAGS_obs_numerics off ⇒ instrumented model fns lower to the
    IDENTICAL jaxpr (zero device ops) — and flipping it on visibly adds
    the probe callbacks, proving the comparison is live."""
    cfg = _tiny_cfg()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    toks = jnp.zeros((2, 16), jnp.int32)

    def fwd():
        # a FRESH callable per trace: jax's tracing cache keys on the
        # function object, and the gate is read at trace time — reusing
        # one fn across the flag flip would replay the cached jaxpr
        # (exactly why the docs say "flip the flag before building the
        # jit")
        return jax.make_jaxpr(
            lambda p, t: llama.hidden_states(p, t, cfg))(params, toks)

    assert not numerics.active()
    off1 = str(fwd())
    obs.enable()
    numerics.enable()
    try:
        on = str(fwd())
    finally:
        numerics.disable()
        obs.disable()
    off2 = str(fwd())
    assert off1 == off2
    assert "callback" not in off1
    assert "callback" in on


def test_disabled_path_jaxpr_identical_moe_and_grad():
    cfg = moe.tiny_moe()
    params = moe.init_params(cfg, jax.random.PRNGKey(0))
    toks = jnp.zeros((2, 17), jnp.int32)

    def lossgrad():
        # fresh callable per trace (see the llama test above)
        return _jaxpr_str(jax.make_jaxpr(
            lambda p, t: jax.value_and_grad(
                lambda q: moe.loss_fn(q, t, cfg))(p))(params, toks))

    off1 = str(lossgrad())
    obs.enable()
    numerics.enable()
    try:
        on = str(lossgrad())
    finally:
        numerics.disable()
        obs.disable()
    assert off1 == str(lossgrad())
    assert "callback" not in off1
    # the ladder rides the scan ys into one top-level outfeed that
    # SURVIVES autodiff (a probe inside the scan body would be dropped)
    assert "callback" in on


def test_engine_prefill_decode_bake_zero_ops_when_off():
    from paddle_tpu.models.llama_served import ServeOpts
    from paddle_tpu.serving.engine import _paged_decode, _paged_prefill

    cfg = dataclasses.replace(_tiny_cfg(), dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    pools = {"k": jnp.zeros((2, 3, 8, 2, 8), jnp.int8),
             "v": jnp.zeros((2, 3, 8, 2, 8), jnp.int8),
             "ks": jnp.zeros((2, 3, 8, 2), jnp.float32),
             "vs": jnp.zeros((2, 3, 8, 2), jnp.float32)}

    def mk(numerics_flag):
        return str(jax.make_jaxpr(
            lambda p, t, b, tl, po, k: _paged_prefill(
                p, t, b, tl, po, jnp.zeros(1), jnp.zeros(1, jnp.int32),
                jnp.ones(1), k, model=cfg.served_model(),
                opts=ServeOpts(kv_int8=True, numerics=numerics_flag)))(
            params, jnp.zeros((1, 8), jnp.int32),
            jnp.zeros((1, 1), jnp.int32), jnp.ones(1, jnp.int32), pools,
            jax.random.PRNGKey(0)))

    obs.enable()
    numerics.enable()
    try:
        assert "callback" not in mk(False)
        assert "callback" in mk(True)
    finally:
        numerics.disable()
        obs.disable()


def test_disabled_overhead_under_5pct():
    """Acceptance guard: with numerics off, the per-step cost of its
    call sites (active() gates + step_mark + a record_stats early
    return) stays under 5% of a decode-step-shaped CPU workload.

    Measured as (per-call instrumentation cost) vs (per-step workload
    cost) rather than two interleaved wall-clock windows: the gate cost
    under test is ~0.4 µs against a ~4 ms step (a 500x margin), and
    window-vs-window comparison flakes on a loaded box long before the
    gates show up in it."""
    numerics.disable()
    obs.disable()
    x = np.random.default_rng(0).standard_normal((256, 256))

    def fake_step(a):
        for _ in range(3):
            a = a @ a
            a = a / np.abs(a).max()
        return a

    fake_step(x)
    step_s = float("inf")
    for _ in range(4):
        t0 = time.perf_counter()
        for _ in range(10):
            fake_step(x)
        step_s = min(step_s, (time.perf_counter() - t0) / 10)

    n = 20000
    t0 = time.perf_counter()
    for _ in range(n):
        numerics.step_mark()
        if numerics.active():               # the model-tap gate
            pass
        numerics.record_stats("bench", x)   # early return while off
    instr_s = (time.perf_counter() - t0) / n

    assert instr_s <= step_s * 0.05, (instr_s, step_s)


# -- stat + quant-error correctness -----------------------------------------
def test_tensor_stats_hand_computed():
    x = jnp.asarray([[1.0, -3.0, float("nan")],
                     [float("inf"), 0.5, 200.0]])
    v = np.asarray(numerics.tensor_stats(x))
    assert v[0] == pytest.approx(200.0)          # absmax (finite only)
    finite = np.asarray([1.0, -3.0, 0.5, 200.0, 0.0, 0.0])
    assert v[1] == pytest.approx(
        math.sqrt(float(np.mean(finite ** 2))), rel=1e-6)
    assert v[2] == 2                             # one nan + one inf
    assert v[3] == pytest.approx(1 / 6)          # only 200 > 127
    assert v[4] == -1.0                          # no quant error slot


def test_quant_error_gauge_matches_reference(numerics_on):
    from paddle_tpu.kernels.quant_matmul import quantize_grouped

    w = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16)) * 0.3
    q = quantize_grouped(w, 1)                   # scale over axis 1
    numerics.record_quant_error("expert_int8", [(w, q["q"], q["s"], 1)])
    numerics.flush()
    deq = np.asarray(q["q"], np.float64) * np.expand_dims(
        np.asarray(q["s"], np.float64), 1)
    ref = math.sqrt(float(np.sum((np.asarray(w, np.float64) - deq) ** 2))
                    / float(np.sum(np.asarray(w, np.float64) ** 2)))
    got = obs.get_registry().gauge("numerics_quant_error").labels(
        site="expert_int8").value
    assert got == pytest.approx(ref, rel=1e-4)
    assert 0 < got < 0.05                        # sane int8 error scale
    row = numerics.latest("expert_int8")
    assert row["nan_inf"] == 0 and row["overflow_frac"] == 0.0


def test_all_three_sites_populate_the_gauge(numerics_on):
    cfg = dataclasses.replace(_tiny_cfg(), dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    # site 1: weight_only (works under the caller's jit too)
    jax.jit(llama.quantize_params)(params)
    # site 2: expert_int8
    moe.quantize_expert_params(
        moe.init_params(moe.tiny_moe(), jax.random.PRNGKey(1)))
    # site 3: kv_int8 through a short int8-KV engine run
    from paddle_tpu.serving import LLMEngine

    eng = LLMEngine(params, cfg, max_slots=2, block_size=8,
                    max_model_len=64, prompt_buckets=[8, 32],
                    kv_dtype="int8")
    rng = np.random.default_rng(0)
    for _ in range(2):
        eng.add_request(rng.integers(1, 64, size=8).tolist(),
                        max_new_tokens=4)
    eng.run()
    numerics.flush()
    g = obs.get_registry().gauge("numerics_quant_error")
    for site in ("weight_only", "expert_int8", "kv_int8"):
        v = g.labels(site=site).value
        assert 0 < v < 0.1, (site, v)
    # events counter saw every site land
    c = obs.get_registry().counter("numerics_events_total")
    assert c.labels(site="kv_int8").value >= 2   # prefill + writeback


# -- ladder + provenance ----------------------------------------------------
def test_ladder_lands_per_layer_rungs_under_grad(numerics_on):
    cfg = _tiny_cfg()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, 16)))
    jax.jit(jax.value_and_grad(
        lambda p: llama.loss_fn(p, toks, cfg)))(params)
    numerics.flush()
    rungs = [r for r in numerics.rows() if r["site"] == "llama.layer"]
    assert [r["layer"] for r in rungs] == [0, 1]
    assert all(r["nan_inf"] == 0 and r["rms"] > 0 for r in rungs)


def test_provenance_picks_earliest_of_two_bad_layers(numerics_on):
    ep = numerics.step_mark()
    ladder = jnp.asarray([[1.0, 0.5, 0.0, 0.0, -1.0],
                          [1.0, 0.5, 3.0, 0.0, -1.0],     # bad: layer 1
                          [1.0, 0.5, 0.0, 0.0, -1.0],
                          [1.0, 0.5, 9.0, 0.0, -1.0]])    # bad: layer 3
    numerics.ladder_record("llama.layer", ladder)
    assert numerics.provenance(ep) == "llama.layer:1"
    # a model-level double poison agrees: NaNs propagate forward, the
    # earliest poisoned layer wins
    from paddle_tpu.distributed.resilience import FaultInjector

    cfg = _tiny_cfg()
    state = llama.init_train_state(cfg, jax.random.PRNGKey(0))
    bad = FaultInjector.poison_layer(
        FaultInjector.poison_layer(state, 1), 0)
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, 16)))
    numerics.clear()
    ep = numerics.step_mark()
    jax.jit(lambda s, t: llama.train_step(s, t, cfg))(bad, toks)
    assert numerics.provenance(ep) == "llama.layer:0"


def test_ladder_offset_covers_moe_dense_head(numerics_on):
    cfg = moe.tiny_moe()
    cfg = dataclasses.replace(cfg, first_dense_layers=1)
    params = moe.init_params(cfg, jax.random.PRNGKey(0))
    toks = jnp.zeros((2, 9), jnp.int32)
    moe.hidden_states_with_aux(params, toks, cfg)
    numerics.flush()
    rungs = [r["layer"] for r in numerics.rows()
             if r["site"] == "moe.layer"]
    assert rungs == [0, 1]        # dense head rung 0, moe tail rung 1


def test_nan_inject_provenance_end_to_end(numerics_on, tmp_path):
    """The seeded nan_inject fault must (a) trigger exactly one
    rollback whose event carries first_bad naming the injected layer,
    (b) recover via retry to a finished run, and (c) leave the verdict
    in the flight-recorder post-mortem."""
    from paddle_tpu.distributed.resilience import (FaultInjector,
                                                   ResilientTrainLoop)

    cfg = _tiny_cfg()
    state = llama.init_train_state(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    batches = [jnp.asarray(rng.randint(0, 64, (2, 16))) for _ in range(4)]
    step = jax.jit(lambda s, t: llama.train_step(s, t, cfg, lr=1e-3))
    loop = ResilientTrainLoop(
        step, state, batches, injector=FaultInjector("nan_inject:1@1"))
    loop.run(len(batches))
    assert loop.step == len(batches)             # retry recovered
    rb = [e for e in loop.events if e["kind"] == "rollback"]
    assert len(rb) == 1
    assert rb[0]["reason"] == "non_finite_loss"
    assert rb[0]["first_bad"] == "llama.layer:1"
    inj_ev = [e for e in loop.events if e["kind"] == "nan_injected"]
    assert inj_ev and inj_ev[0]["layer"] == 1
    # flight event + post-mortem both carry the verdict
    fl = [e for e in flight_recorder.get_recorder().events()
          if e["kind"] == "rollback"]
    assert fl and fl[0]["first_bad"] == "llama.layer:1"
    import json

    path = flight_recorder.dump(str(tmp_path / "pm.json"))
    doc = json.load(open(path))
    assert doc["numerics"]["provenance"] == "llama.layer:1"
    assert any(r["site"] == "llama.layer" for r in doc["numerics"]["rows"])


def test_untargeted_nan_grad_rollback_has_no_provenance(numerics_on):
    """nan_grad poisons the post-step state, not the forward — the
    ladder stays clean and the rollback must NOT invent a layer."""
    from paddle_tpu.distributed.resilience import (FaultInjector,
                                                   ResilientTrainLoop)

    cfg = _tiny_cfg()
    state = llama.init_train_state(cfg, jax.random.PRNGKey(0))
    batches = [jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, 16)))
               for _ in range(3)]
    step = jax.jit(lambda s, t: llama.train_step(s, t, cfg, lr=1e-3))
    loop = ResilientTrainLoop(
        step, state, batches, injector=FaultInjector("nan_grad@1"))
    loop.run(len(batches))
    rb = [e for e in loop.events if e["kind"] == "rollback"]
    assert rb and "first_bad" not in rb[0]


# -- ring / flag plumbing ---------------------------------------------------
def test_capacity_flag_resizes_live_ring(numerics_on):
    try:
        for i in range(8):
            numerics._land("s", np.asarray([1.0, 1.0, 0.0, 0.0, -1.0]), -1)
        assert len(numerics.entries()) == 8
        set_flags({"obs_numerics_capacity": 4})
        assert len(numerics.entries()) == 4      # live-resized, tail kept
        assert numerics.entries()[0]["site"] == "s"
    finally:
        set_flags({"obs_numerics_capacity": 512})


def test_nan_counter_and_rows(numerics_on):
    numerics._land("probe", np.asarray([2.0, 1.0, 3.0, 0.25, -1.0]), -1)
    c = obs.get_registry().counter("numerics_nan_total")
    assert c.labels(site="probe").value == 1
    row = numerics.rows()[0]
    assert row["nan_inf"] == 3 and row["overflow_frac"] == 0.25
    assert row["quant_err"] is None


def test_router_and_routed_out_probes_in_forward(numerics_on):
    """The MoE kernel probes (router logits, fused routed output) land
    in a forward-only program."""
    cfg = dataclasses.replace(moe.tiny_moe(), dispatch="fused")
    params = moe.init_params(cfg, jax.random.PRNGKey(0))
    toks = jnp.zeros((2, 9), jnp.int32)
    jax.jit(lambda p, t: moe.forward(p, t, cfg))(params, toks)
    numerics.flush()
    sites = {r["site"] for r in numerics.rows()}
    assert "moe.router_logits" in sites
    assert "moe.routed_out" in sites


def test_fault_schedule_arg_validation():
    """A ':<arg>' payload is only legal on kinds that take one, and
    nan_inject's arg must be a layer index — a typo'd schedule fails at
    construction, never validates-then-silently-never-fires."""
    from paddle_tpu.distributed.resilience import FaultInjector

    FaultInjector("nan_inject:3@5")              # ok
    FaultInjector([("nan_inject:2", 1)])         # pair schedules too
    with pytest.raises(ValueError, match="takes no"):
        FaultInjector("nan_grad:1@3")
    with pytest.raises(ValueError, match="layer index"):
        FaultInjector("nan_inject:attn@3")
    with pytest.raises(ValueError, match="takes no"):
        FaultInjector([("crash:x", 5)])


def test_poison_layer_rejects_uncovered_targets():
    """An injection that would poison nothing (or the wrong rung) must
    raise instead of logging a drill that never happened."""
    from paddle_tpu.distributed.resilience import FaultInjector

    cfg = _tiny_cfg()
    state = llama.init_train_state(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="no stacked float leaf"):
        FaultInjector.poison_layer(state, 99)    # 2-layer model
    with pytest.raises(ValueError, match=">= 0"):
        FaultInjector.poison_layer(state, -1)


def test_package_keeps_numerics_lazy():
    """A fresh `import paddle_tpu.observability` must NOT load the
    numerics submodule (PEP 562 — the <50ms import-cost guard keeps its
    headroom), while attribute access still resolves it."""
    import importlib

    saved = {m: sys.modules.pop(m) for m in list(sys.modules)
             if m.startswith("paddle_tpu.observability")}
    try:
        mod = importlib.import_module("paddle_tpu.observability")
        assert "paddle_tpu.observability.numerics" not in sys.modules
        assert mod.numerics.STAT_FIELDS[0] == "absmax"   # lazy resolve
        assert "paddle_tpu.observability.numerics" in sys.modules
    finally:
        for m in list(sys.modules):
            if m.startswith("paddle_tpu.observability"):
                del sys.modules[m]
        sys.modules.update(saved)
        import paddle_tpu

        paddle_tpu.observability = saved["paddle_tpu.observability"]


# -- tooling smoke ----------------------------------------------------------
def test_obs_dump_numerics_demo(tmp_path):
    """tools/obs_dump.py --demo numerics: all three quant-error sites
    report, the stats table prints, and the nan_inject provenance names
    the injected layer (subprocess: the demo's global enables must not
    leak into this session)."""
    import subprocess

    tool = os.path.join(REPO, "tools", "obs_dump.py")
    proc = subprocess.run(
        [sys.executable, tool, "--demo", "numerics",
         "--out", str(tmp_path)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, timeout=240,
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    out = proc.stdout.decode(errors="replace")
    assert proc.returncode == 0, out[-2000:]
    assert "first bad layer = llama.layer:1" in out
    for site in ("weight_only", "expert_int8", "kv_int8"):
        assert f"quant-error budget {site}" in out
    assert "quant_err" in out                    # the stats table header
    assert "llama.layer" in out
