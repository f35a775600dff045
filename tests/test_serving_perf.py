"""Serving-engine orchestration overhead must stay under 10%.

VERDICT r3 measured the serving engine at ~half the fixed-batch decode
rate; the loss was host-side serialization (eager first-token sampling per
admission + a blocking readback between decode calls), not chip math. The
pipelined engine samples first tokens in-program, chains the decode carry
on device, and reads call k's tokens while call k+1 runs.

This test pins that property in a backend-neutral way: at full slots with
no admission churn, `LLMEngine.run()` must be within 10% of driving the
SAME compiled decode program as a bare chained loop (one final readback).
The kernel-for-kernel comparison against `llama.generate_fused` (which
uses a dense cache, so CPU penalizes the paged gather far more than a TPU
does) lives in the real-device lane: tests_tpu/test_serving_tpu.py.
"""
import time

import numpy as np
import pytest

import jax

from paddle_tpu.models import llama
from paddle_tpu.serving import LLMEngine

SLOTS, PROMPT, STEPS, CALLS = 4, 32, 32, 4
NEW = STEPS * CALLS


def _engine(params, cfg):
    # one 192-token block per slot (prompt + NEW + 1 fits): admission backs
    # the whole horizon, so the raw loop never allocates blocks mid-run
    return LLMEngine(params, cfg, max_slots=SLOTS, block_size=192,
                     max_model_len=192, prompt_buckets=[192],
                     decode_steps=STEPS)


@pytest.fixture(scope="module")
def model():
    cfg = llama.LlamaConfig(
        vocab_size=256, hidden_size=128, intermediate_size=256,
        num_layers=2, num_heads=4, num_kv_heads=2, head_dim=32,
        max_seq_len=256, remat=False)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return params, cfg


def _time_engine(params, cfg, prompts):
    eng = _engine(params, cfg)
    for p in prompts:                       # warm: compile prefill + decode
        eng.add_request(p, max_new_tokens=NEW, temperature=0.0)
    eng.run()
    best = float("inf")
    for _ in range(3):
        rids = [eng.add_request(p, max_new_tokens=NEW, temperature=0.0)
                for p in prompts]
        t0 = time.perf_counter()
        out = eng.run()
        dt = time.perf_counter() - t0
        assert all(len(out[r]) == NEW for r in rids)
        best = min(best, dt)
    return SLOTS * NEW / best


def _time_raw(params, cfg, prompts):
    """The engine's own prefill+decode programs driven with zero
    orchestration: admit once, then chain CALLS decode dispatches on the
    device-resident carry and read back once at the end."""
    eng = _engine(params, cfg)

    def run_raw():
        # +1 budget: the admission token consumes one, so CALLS full decode
        # calls stay under budget and every emitted lane is a real token
        for p in prompts:
            eng.add_request(p, max_new_tokens=NEW + 1, temperature=0.0)
        eng._admit()
        active = eng._active_slots()
        eng._back_or_preempt()
        eng._refresh_carry(active)
        import functools

        from paddle_tpu.models.llama_served import ServeOpts
        from paddle_tpu.serving.engine import _paged_decode
        flags = (False, False, False)          # all-greedy workload
        # one bucket for the WHOLE chained run: _prefix_blocks covers a
        # single call's horizon, but this loop chains CALLS calls without
        # re-deriving it, so size for the final lengths up front
        horizon = min(max(int(eng.lengths[i]) for i in active)
                      + CALLS * eng.decode_steps, eng.max_model_len)
        need = max(1, -(-horizon // eng.bs))
        nbk = min(1 << (need - 1).bit_length(), eng.mb)
        tbl = jax.numpy.asarray(eng.table[:, :nbk])
        key = (nbk, flags)
        decode = eng._decode_cache.get(key)
        if decode is None:
            decode = eng._decode_cache[key] = jax.jit(
                functools.partial(_paged_decode, model=eng.model,
                                  n_steps=eng.decode_steps,
                                  sample_flags=flags,
                                  opts=ServeOpts(kv_int8=eng.kv_int8)),
                donate_argnums=(8,))
        grids = []
        for _ in range(CALLS):
            c_last, c_len, c_done, c_rem, c_key = eng._carry
            v_act, v_t, v_k, v_p, v_eos = eng._slot_vecs
            (toks, c_last, c_len, c_done, c_rem, c_key,
             eng.pools, _stats) = decode(
                eng.params, c_last, c_len, c_done, c_rem, c_key, v_act,
                tbl, eng.pools, v_t, v_k, v_p, v_eos)
            eng._carry = (c_last, c_len, c_done, c_rem, c_key)
            grids.append(toks)
        out = np.concatenate([np.asarray(jax.device_get(g)) for g in grids])
        # reset host state so the next trial re-admits cleanly
        for s in list(eng._active_slots()):
            eng._free_slot(s)
        eng._pending_adm = []
        eng._carry = None
        eng.queue.clear()
        return out

    run_raw()                               # warm
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        out = run_raw()
        best = min(best, time.perf_counter() - t0)
        assert (out >= 0).all()             # every lane stayed live
    return SLOTS * NEW / best


@pytest.mark.slow
def test_engine_overhead_within_10pct_of_raw_decode(model):
    params, cfg = model
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 256, size=PROMPT).tolist()
               for _ in range(SLOTS)]
    # shared-CPU noise can collapse one side's whole best-of-3 phase (a
    # co-tenant burst outlives min-of-trials); one re-measure before
    # failing squares the false-alarm probability away
    for attempt in range(2):
        eng_tps = _time_engine(params, cfg, prompts)
        raw_tps = _time_raw(params, cfg, prompts)
        if eng_tps >= 0.9 * raw_tps:
            return
    assert eng_tps >= 0.9 * raw_tps, (
        f"engine {eng_tps:.0f} tok/s < 0.9x raw loop {raw_tps:.0f} tok/s")
