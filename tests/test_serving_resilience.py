"""r8 serving survivability: deadlines, admission control / shedding,
preempt-to-host KV swap, and crash recovery under seeded chaos.

Contracts under test:
- deadline eviction (queued AND mid-decode) frees every KV block,
  delivers partial tokens, and lands finish reason deadline_exceeded on
  the request trace;
- admission control sheds reject-newest with a typed ShedError
  (queue_full / rate_limited / pool_pressure) and the shed request's
  trace closes with reason "shed";
- swap-in re-admissions produce token streams IDENTICAL to recompute
  re-admissions (greedy parity, model-dtype and pipelined decode_steps),
  and fall back to recompute when the host pool is full;
- ResilientEngine recovers an injected readback crash: the poisoned
  wave is dropped, in-flight requests re-enqueue from traced state,
  streams stay exactly-once;
- block accounting balances (free + backed + squeezed == pool size,
  no duplicate block ids) after ANY mix of eviction / shed /
  preempt-swap / crash-requeue — the leak regression surface.
"""
import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu  # noqa: F401  (forces the CPU/virtual-device conftest setup)
import jax
import jax.numpy as jnp

from paddle_tpu.distributed.resilience import FaultInjector, SimulatedCrash
from paddle_tpu.serving import (AdmissionConfig, AdmissionController,
                                LLMEngine, Request, ResilientEngine,
                                ShedError)


@pytest.fixture(scope="module")
def model():
    from paddle_tpu.models import llama
    cfg = dataclasses.replace(
        llama.tiny_llama(vocab=64, hidden=32, layers=2, heads=4, kv_heads=2,
                         seq=128, ffn=64),
        dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _prompt(rng, n):
    return rng.integers(1, 64, size=n).tolist()


# the shared 5-term ledger + custody/duplicate/cross-check helper lives
# in tests/conftest.py — one copy, both suites enforce one invariant
from conftest import assert_blocks_balanced as _assert_blocks_balanced  # noqa: E402


# ---------------------------------------------------------------------------
# deadlines
# ---------------------------------------------------------------------------
def test_deadline_evicts_queued_and_active_requests(model):
    cfg, params = model
    rng = np.random.default_rng(0)
    eng = LLMEngine(params, cfg, max_slots=1, block_size=8,
                    max_model_len=64, prompt_buckets=[8])
    a = eng.add_request(_prompt(rng, 6), max_new_tokens=8)
    b = eng.add_request(_prompt(rng, 6), max_new_tokens=8,
                        deadline_s=0.0)       # queued behind a: expires
    streamed = []
    streamed += eng.step()
    streamed += eng.step()                    # a has visible tokens now
    # force a mid-decode expiry on the active request without sleeping
    # (white-box: stamping t_deadline directly bypasses add_request, so
    # the deadline-carrier count must be bumped with it)
    eng.slot_req[0].t_deadline = 0.0
    eng._deadline_live += 1
    while eng.has_work():
        streamed += eng.step()
    assert eng.finish_reasons[a] == "deadline_exceeded"
    assert eng.finish_reasons[b] == "deadline_exceeded"
    assert eng.results[b] == []               # never admitted
    # partial tokens already streamed are delivered, exactly once
    assert eng.results[a] == [t for r, t in streamed if r == a]
    assert len(eng.results[a]) < 8            # evicted before its budget
    _assert_blocks_balanced(eng)
    assert len(eng.free_blocks) == eng.nb - 1


def test_deadline_zero_expires_before_any_admission(model):
    cfg, params = model
    rng = np.random.default_rng(1)
    eng = LLMEngine(params, cfg, max_slots=2, block_size=8,
                    max_model_len=64, prompt_buckets=[8])
    ok = eng.add_request(_prompt(rng, 5), max_new_tokens=4)
    dead = eng.add_request(_prompt(rng, 5), max_new_tokens=4,
                           deadline_s=0.0)
    out = eng.run()
    assert eng.finish_reasons == {ok: "finished",
                                  dead: "deadline_exceeded"}
    assert len(out[ok]) == 4 and out[dead] == []


# ---------------------------------------------------------------------------
# admission control / shedding
# ---------------------------------------------------------------------------
def test_queue_full_sheds_newest_with_typed_error(model):
    cfg, params = model
    rng = np.random.default_rng(2)
    eng = LLMEngine(params, cfg, max_slots=1, block_size=8,
                    max_model_len=64, prompt_buckets=[8],
                    admission=AdmissionConfig(max_queue=2))
    keep = [eng.add_request(_prompt(rng, 4), max_new_tokens=3)
            for _ in range(2)]
    with pytest.raises(ShedError) as ei:
        eng.add_request(_prompt(rng, 4), max_new_tokens=3)
    assert ei.value.reason == "queue_full"
    shed_id = ei.value.req_id
    assert eng.finish_reasons[shed_id] == "shed"
    out = eng.run()
    assert shed_id not in out                 # never served
    for rid in keep:
        assert eng.finish_reasons[rid] == "finished"
        assert len(out[rid]) == 3             # admitted ones unharmed


def test_rate_limit_per_tenant_token_bucket(model):
    cfg, params = model
    rng = np.random.default_rng(3)
    clock = [0.0]
    ctl = AdmissionController(
        AdmissionConfig(max_queue=16, rate_tokens_per_s=10.0,
                        burst_tokens=20.0),
        now_fn=lambda: clock[0])
    eng = LLMEngine(params, cfg, max_slots=2, block_size=8,
                    max_model_len=64, prompt_buckets=[8], admission=ctl)
    p = _prompt(rng, 8)
    eng.add_request(list(p), max_new_tokens=8)        # cost 16 <= burst 20
    with pytest.raises(ShedError) as ei:
        eng.add_request(list(p), max_new_tokens=8)    # bucket dry
    assert ei.value.reason == "rate_limited"
    # a different tenant has its own bucket
    eng.add_request(list(p), max_new_tokens=8, tenant="other")
    # and the original refills with virtual time
    clock[0] = 5.0                                    # +50 tokens
    eng.add_request(list(p), max_new_tokens=8)
    out = eng.run()
    assert sorted(len(v) for v in out.values()) == [8, 8, 8]


def test_pool_pressure_sheds_when_queue_would_only_thrash(model):
    cfg, params = model
    rng = np.random.default_rng(4)
    ctl = AdmissionController(AdmissionConfig(max_queue=16,
                                              shed_free_frac=0.5))
    eng = LLMEngine(params, cfg, max_slots=2, block_size=8,
                    max_model_len=64, num_blocks=4, prompt_buckets=[8],
                    admission=ctl)
    # decode until the growing sequence holds most of the pool
    eng.add_request(_prompt(rng, 8), max_new_tokens=16)
    while len(eng.free_blocks) / (eng.nb - 1) >= 0.5:
        eng.step()
    eng.add_request(_prompt(rng, 8), max_new_tokens=4)   # queued (ok)
    with pytest.raises(ShedError) as ei:
        eng.add_request(_prompt(rng, 8), max_new_tokens=4)
    assert ei.value.reason == "pool_pressure"
    eng.run()
    _assert_blocks_balanced(eng)


# ---------------------------------------------------------------------------
# KV swap: preempt → host tier → restore
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("decode_steps", [1, 3])
def test_swap_in_streams_identical_to_recompute(model, decode_steps):
    """The acceptance parity: same seed, same workload, pool squeezed so
    preemption MUST happen — the engine with a host swap tier produces
    exactly the recompute engine's token streams (greedy, model-dtype
    pools: the restore is bit-exact)."""
    import paddle_tpu.observability as obs

    cfg, params = model
    rng = np.random.default_rng(3)
    p1, p2 = _prompt(rng, 8), _prompt(rng, 8)

    def run(swap_bytes):
        obs.get_registry().reset()
        obs.enable()
        try:
            eng = LLMEngine(params, cfg, max_slots=2, block_size=8,
                            max_model_len=64, num_blocks=5,
                            prompt_buckets=[8], decode_steps=decode_steps,
                            kv_swap_bytes=swap_bytes)
            i1 = eng.add_request(list(p1), max_new_tokens=16)
            i2 = eng.add_request(list(p2), max_new_tokens=16)
            streamed = {i1: [], i2: []}
            while eng.has_work():
                for rid, tok in eng.step():
                    streamed[rid].append(tok)
            reg = obs.get_registry()
            pre = reg.counter("serving_preemptions_total").labels().value
            sw = reg.counter("serving_kv_swap_in_total").labels().value
        finally:
            obs.disable()
            obs.get_registry().reset()
        # exactly-once streaming on both paths
        assert streamed[i1] == eng.results[i1]
        assert streamed[i2] == eng.results[i2]
        _assert_blocks_balanced(eng)
        assert len(eng.free_blocks) == eng.nb - 1
        if eng.swap_pool is not None:
            assert len(eng.swap_pool) == 0
            assert eng.swap_pool.bytes_used == 0
        return (eng.results[i1], eng.results[i2], pre, sw)

    r1, r2, pre_r, sw_r = run(0)
    s1, s2, pre_s, sw_s = run(1 << 20)
    assert pre_r >= 1 and pre_s >= 1, "workload must preempt"
    assert sw_r == 0 and sw_s >= 1, "swap tier must carry the preemption"
    assert (s1, s2) == (r1, r2)
    assert len(s1) == len(s2) == 16


def test_swap_fallback_when_host_pool_full(model):
    """A 1-byte host pool can hold nothing: every preemption falls back
    to recompute, counted, and the streams still complete exactly."""
    import paddle_tpu.observability as obs

    cfg, params = model
    rng = np.random.default_rng(3)
    obs.get_registry().reset()
    obs.enable()
    try:
        eng = LLMEngine(params, cfg, max_slots=2, block_size=8,
                        max_model_len=64, num_blocks=5, prompt_buckets=[8],
                        kv_swap_bytes=1)
        i1 = eng.add_request(_prompt(rng, 8), max_new_tokens=16)
        i2 = eng.add_request(_prompt(rng, 8), max_new_tokens=16)
        out = eng.run()
        reg = obs.get_registry()
        assert reg.counter("serving_kv_swap_fallback_total").labels(
            reason="host_pool_full").value >= 1
        assert reg.counter("serving_kv_swap_in_total").labels().value == 0
    finally:
        obs.disable()
        obs.get_registry().reset()
    assert len(out[i1]) == 16 and len(out[i2]) == 16
    assert eng.swap_pool.bytes_used == 0
    _assert_blocks_balanced(eng)


def test_swap_under_int8_kv_pools_round_trips_bit_exact(model):
    """int8 pools swap the quantized payload AND scales verbatim — the
    swap run completes exactly-once with a balanced ledger (token values
    may differ from recompute, which requantizes a fresh prefill)."""
    import paddle_tpu.observability as obs

    cfg, params = model
    rng = np.random.default_rng(5)
    obs.get_registry().reset()
    obs.enable()
    try:
        eng = LLMEngine(params, cfg, max_slots=2, block_size=8,
                        max_model_len=64, num_blocks=5, prompt_buckets=[8],
                        kv_dtype="int8", kv_swap_bytes=1 << 20)
        ids = [eng.add_request(_prompt(rng, 8), max_new_tokens=16)
               for _ in range(2)]
        streamed = {rid: [] for rid in ids}
        while eng.has_work():
            for rid, tok in eng.step():
                streamed[rid].append(tok)
        assert obs.get_registry().counter(
            "serving_kv_swap_in_total").labels().value >= 1
    finally:
        obs.disable()
        obs.get_registry().reset()
    for rid in ids:
        assert streamed[rid] == eng.results[rid]
        assert len(eng.results[rid]) == 16
    _assert_blocks_balanced(eng)
    assert len(eng.swap_pool) == 0


# ---------------------------------------------------------------------------
# crash recovery (ResilientEngine + injected faults)
# ---------------------------------------------------------------------------
def test_resilient_engine_recovers_injected_readback_crash(model):
    cfg, params = model
    rng = np.random.default_rng(6)
    inj = FaultInjector("readback_fail@3")
    eng = LLMEngine(params, cfg, max_slots=2, block_size=8,
                    max_model_len=64, prompt_buckets=[8], injector=inj)
    ids = [eng.add_request(_prompt(rng, 6), max_new_tokens=10)
           for _ in range(2)]
    reng = ResilientEngine(eng)
    streamed = {rid: [] for rid in ids}
    while reng.has_work():
        for rid, tok in reng.step():
            streamed[rid].append(tok)
    assert reng.recoveries == 1
    assert inj.fired == [("readback_fail", 3)]
    for rid in ids:
        # exactly-once: the poisoned wave's tokens were never visible,
        # the requeued request regenerated them
        assert streamed[rid] == reng.results[rid]
        assert len(reng.results[rid]) == 10
        assert eng.finish_reasons[rid] == "finished"
    _assert_blocks_balanced(eng)


def test_pool_pressure_shed_does_not_charge_rate_bucket():
    """Stateless shed checks run BEFORE the token bucket is charged: a
    request rejected for pool pressure must not drain its tenant's rate
    budget (it never ran — charging it would starve the tenant as
    rate_limited long after the pressure clears)."""
    clock = [0.0]
    ctl = AdmissionController(
        AdmissionConfig(max_queue=16, rate_tokens_per_s=1.0,
                        burst_tokens=20.0, shed_free_frac=0.5),
        now_fn=lambda: clock[0])
    req = Request(req_id=0, prompt=[1] * 10, max_new_tokens=10)  # cost 20
    for _ in range(5):      # repeated pressure sheds: bucket untouched
        assert ctl.check(req, queue_depth=1, free_frac=0.1) \
            == "pool_pressure"
    # pressure clears: the tenant still has its full burst
    assert ctl.check(req, queue_depth=1, free_frac=1.0) is None
    # and is only now rate-limited (the one admitted request drained it)
    assert ctl.check(req, queue_depth=1, free_frac=1.0) == "rate_limited"


def test_resilient_step_salvages_tokens_committed_before_crash(model):
    """A step can raise AFTER a readback in it committed tokens
    host-side. Those tokens are in slot_out (→ generated on requeue, so
    re-admission never re-emits them) — the recovery must deliver them
    to the streaming caller, exactly once. The seeded injector can't
    reach this interleaving (it fires before the first readback), so it
    is forced here: crash after one fully processed record."""
    cfg, params = model
    rng = np.random.default_rng(10)
    eng = LLMEngine(params, cfg, max_slots=2, block_size=8,
                    max_model_len=64, prompt_buckets=[8], decode_steps=2)
    ids = [eng.add_request(_prompt(rng, 6), max_new_tokens=8)
           for _ in range(2)]
    reng = ResilientEngine(eng)
    streamed = {rid: [] for rid in ids}
    orig = eng._process_guarded
    armed = [False]

    def crash_after_commit(rec):
        out = orig(rec)
        if armed[0]:
            raise SimulatedCrash("post-commit crash")
        return out

    eng._process_guarded = crash_after_commit
    for rid, tok in reng.step():              # warm: in-flight record
        streamed[rid].append(tok)
    armed[0] = True
    salvaged = reng.step()
    armed[0] = False
    assert reng.recoveries == 1
    assert salvaged, "committed-then-crashed tokens must be delivered"
    for rid, tok in salvaged:
        streamed[rid].append(tok)
    while reng.has_work():
        for rid, tok in reng.step():
            streamed[rid].append(tok)
    for rid in ids:
        assert streamed[rid] == reng.results[rid]   # exactly-once
        assert len(reng.results[rid]) == 8
    _assert_blocks_balanced(eng)


def test_resilient_engine_crash_budget_reraises(model):
    cfg, params = model
    rng = np.random.default_rng(7)
    inj = FaultInjector(",".join(f"readback_fail@{s}"
                                 for s in range(1, 8)))
    eng = LLMEngine(params, cfg, max_slots=1, block_size=8,
                    max_model_len=64, prompt_buckets=[8], injector=inj)
    eng.add_request(_prompt(rng, 6), max_new_tokens=4)
    reng = ResilientEngine(eng, max_recoveries=2)
    with pytest.raises(SimulatedCrash):
        while reng.has_work():
            reng.step()
    assert reng.recoveries == 3               # 2 recovered + the re-raise


def test_pool_squeeze_fault_releases_and_balances(model):
    """An injected squeeze steals free blocks for two steps: accounting
    stays balanced THROUGH the fault (squeezed bucket) and every block
    returns afterwards."""
    cfg, params = model
    rng = np.random.default_rng(8)
    inj = FaultInjector("pool_squeeze@2")
    eng = LLMEngine(params, cfg, max_slots=2, block_size=8,
                    max_model_len=64, num_blocks=8, prompt_buckets=[8],
                    kv_swap_bytes=1 << 20, injector=inj)
    ids = [eng.add_request(_prompt(rng, 8), max_new_tokens=12)
           for _ in range(2)]
    saw_squeeze = False
    while eng.has_work():
        eng.step()
        acct = eng.block_accounting()
        saw_squeeze |= acct["squeezed"] > 0
        _assert_blocks_balanced(eng)
    assert saw_squeeze
    assert len(eng.free_blocks) == eng.nb - 1
    for rid in ids:
        assert len(eng.results[rid]) == 12


def test_block_accounting_balances_under_mixed_chaos(model):
    """The acceptance mix in-process: crashes + squeezes + expired
    deadlines + sheds + swap, invariant checked at EVERY step boundary,
    every request in exactly one terminal state."""
    cfg, params = model
    rng = np.random.default_rng(9)
    inj = FaultInjector("readback_fail@4,pool_squeeze@3,slow_step@2,"
                        "readback_fail@9,pool_squeeze@8")
    eng = LLMEngine(params, cfg, max_slots=2, block_size=8,
                    max_model_len=64, num_blocks=5, prompt_buckets=[8, 32],
                    kv_swap_bytes=1 << 20,
                    admission=AdmissionConfig(max_queue=3), injector=inj)
    reng = ResilientEngine(eng)
    all_ids, submitted = [], 0
    while reng.has_work() or submitted < 10:
        for _ in range(2):
            if submitted >= 10:
                break
            submitted += 1
            kw = {"deadline_s": 0.0} if submitted % 4 == 0 else {}
            try:
                all_ids.append(eng.add_request(
                    _prompt(rng, int(rng.integers(3, 14))),
                    max_new_tokens=int(rng.integers(6, 16)), **kw))
            except ShedError as e:
                all_ids.append(e.req_id)
        reng.step()
        _assert_blocks_balanced(eng)
    assert set(eng.finish_reasons) == set(all_ids)
    assert set(eng.finish_reasons.values()) <= {
        "finished", "shed", "deadline_exceeded"}
    assert "shed" in eng.finish_reasons.values()
    assert "deadline_exceeded" in eng.finish_reasons.values()
    assert len(eng.free_blocks) == eng.nb - 1
    assert eng.swap_pool.bytes_used == 0


# ---------------------------------------------------------------------------
# tooling (slow lane)
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_chaos_run_serving():
    """tools/chaos_run.py --serving: the CLI harness ends
    finish-or-shed with zero block leaks under its seeded schedule."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "chaos_run.py"),
         "--serving", "--steps", "24", "--seed", "7"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, timeout=600,
        cwd=repo, env=env)
    out = proc.stdout.decode(errors="replace")
    assert proc.returncode == 0, out[-2000:]
    assert "SERVING_CHAOS: OK" in out
    assert "swap_out=" in out and "recoveries=" in out
    # r13 phase: the speculative leg recovered its mid-verify crash
    assert "spec chaos:" in out
