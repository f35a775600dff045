"""A budget's end is no surprise (PR 38): a lane whose last token the host
can count does not drain the pipeline (``LLMEngine._spec_safe``, the
record's ``ends``), on float32 toy engines on the CPU, over the four served
families (the dense one holds no piece and takes the same rule):

- a scripted run of requests that stop on length serves, token for token,
  what the old schedule serves (every foreseeable end drained, forced
  inside the test), a slot reused while the call that holds its ended lane
  is unread included;
- such a run counts no ``may_finish`` drain and counts its carried ends
  (``serving_counted_finishes_total``, ``serving.step``'s ``counted_ends``);
- a request with an ``eos_token_id`` still drains;
- every lane ending drains and no all-done call is dispatched; the
  threshold ``len(ends) * decode_steps > live``;
- a cancel and a deadline expiry of a lane that has ended in flight;
- a reused slot's per-slot state (LFM2) and ring (Mellum2) start clean.
"""
import contextlib
import dataclasses
import functools
import importlib
import time

import numpy as np
import pytest

import paddle_tpu  # noqa: F401  (forces the CPU/virtual-device conftest setup)
import jax
import jax.numpy as jnp

import paddle_tpu.observability as obs
from paddle_tpu.models import llama
from paddle_tpu.serving import LLMEngine
from test_device_starved_ledger import (  # noqa: F401  (obs_on: a fixture)
    _clear, _steps, _until, obs_on)

F32 = jnp.float32
FAMILIES = {"dense": None, "lfm2": "test_lfm2_moe", "mellum": "test_mellum",
            "deepseek_v2": "test_deepseek_v2_served"}
NAMES = sorted(FAMILIES)
N, BS, MML = 3, 8, 128
DRAINS = "serving_pipeline_drains_total"
COUNTED = "serving_counted_finishes_total"


@functools.lru_cache(maxsize=None)
def _engine(name, **kw):
    """One tiny engine a family (and shape), reused from test to test: a
    run leaves it empty. The three chunked families in the shape whose
    pieces carry the decode rows; the dense one whole prompts, two
    programs."""
    kw = dict(kw)
    if name == "dense":
        cfg = dataclasses.replace(
            llama.tiny_llama(vocab=64, hidden=32, layers=2, heads=4,
                             kv_heads=2, seq=MML, ffn=64), dtype=F32)
        eng = LLMEngine(llama.init_params(cfg, jax.random.PRNGKey(0)), cfg,
                        max_slots=kw.pop("max_slots", N), block_size=BS,
                        max_model_len=MML, prompt_buckets=[8, 32, 64], **kw)
        assert not eng._piggyback
        return eng
    t = importlib.import_module(FAMILIES[name])
    cfg = t.FAM.program_config(t.MODEL, max_seq_len=MML, dtype=F32)
    eng = LLMEngine(t._params(), cfg, max_slots=N, block_size=BS,
                    max_model_len=MML, prompt_buckets=[16, 32], seed=0,
                    prefill_chunk=16, decode_kernel="ragged", **kw)
    assert eng._piggyback
    return eng


def _prompts(name, lens, seed=3):
    rng = np.random.default_rng(seed)
    hi = 64 if name == "dense" else 256
    return [rng.integers(1, hi, size=n).tolist() for n in lens]


@contextlib.contextmanager
def _old_schedule(eng):
    """The parent's rule: a lane on its last token drains."""
    eng._spec_safe = lambda: (not eng._inflight["ends"]
                              and LLMEngine._spec_safe(eng))
    try:
        yield
    finally:
        del eng._spec_safe


def _serve(eng, prompts, budgets, **kw):
    ids = [eng.add_request(p, max_new_tokens=k, **kw)
           for p, k in zip(prompts, budgets)]
    res = eng.run()
    acct = eng.block_accounting()
    assert acct["backed"] == 0 and eng._inflight is None, acct
    return [res[i] for i in ids]


def _series(name, label):
    for m in obs.snapshot()["metrics"]:
        if m["name"] == name:
            return {s["labels"][label]: s["value"]
                    for s in m["series"] if label in s["labels"]}
    return {}


# eight callers on three slots; budgets of one token among them (a final
# piece whose first token is its request's last; the dense engine's row
# that joins with nothing left)
SCRIPT = ((9, 6), (23, 9), (40, 5), (5, 1), (30, 7), (12, 4), (33, 1),
          (7, 8))


# -- (a) the same tokens under both schedules ---------------------------------
@pytest.mark.parametrize("name", NAMES)
def test_a_run_serves_the_drained_schedule_s_tokens(name):
    eng = _engine(name)
    prompts = _prompts(name, [n for n, _ in SCRIPT])
    budgets = [k for _, k in SCRIPT]
    # a slot freed BEHIND a dispatch (a carried end), and a row dispatched
    # into it while the call that holds its ended lane is still unread
    freed_behind, reused = {}, []
    free, sent = eng._free_slot, eng._prefill_dispatched

    def free_slot(slot, *a, **k):
        if eng._inflight is not None and not a and not k:
            freed_behind[slot] = eng._inflight["seq"]
        return free(slot, *a, **k)

    def dispatched(row, *a, **k):
        if eng._inflight is not None \
                and freed_behind.pop(row[0], None) == eng._inflight["seq"]:
            reused.append(row[0])
        return sent(row, *a, **k)

    eng._free_slot, eng._prefill_dispatched = free_slot, dispatched
    try:
        served = _serve(eng, prompts, budgets)
    finally:
        del eng._free_slot, eng._prefill_dispatched
    assert [len(s) for s in served] == budgets
    assert reused, "no slot was refilled behind the call that ended its lane"
    with _old_schedule(eng):
        assert _serve(eng, prompts, budgets) == served


def test_a_call_of_several_steps_serves_the_same_tokens():
    """``decode_steps`` 4: a budget ends mid-call, the rest of the call's
    lane-steps and the call behind it emit padding."""
    eng = _engine("dense", decode_steps=4)
    prompts = _prompts("dense", [n for n, _ in SCRIPT])
    budgets = [6, 9, 5, 1, 7, 4, 2, 8]
    served = _serve(eng, prompts, budgets)
    assert [len(s) for s in served] == budgets
    with _old_schedule(eng):
        assert _serve(eng, prompts, budgets) == served
    assert _serve(_engine("dense"), prompts, budgets) == served


# -- (b) what such a run counts ------------------------------------------------
@pytest.mark.parametrize("name", NAMES)
def test_carried_ends_are_counted_and_drain_nothing(name, obs_on):
    eng = _engine(name)
    # two long requests keep two lanes live; four short ones take turns on
    # the third slot, each ending behind a dispatch
    prompts = _prompts(name, (9, 12, 7, 20, 5, 11))
    long_ids = [eng.add_request(p, max_new_tokens=60) for p in prompts[:2]]
    short = [eng.add_request(p, max_new_tokens=k)
             for p, k in zip(prompts[2:], (4, 6, 3, 5))]
    _until(eng, lambda: all(i in eng.finish_reasons for i in short))
    assert not any(i in eng.finish_reasons for i in long_ids)
    drains = _series(DRAINS, "reason")
    assert not drains.get("may_finish"), drains
    assert _series(COUNTED, "drained") == {"no": len(short)}
    steps = _steps()
    carried = [s for s in steps if "counted_ends" in s.attrs]
    assert sum(s.attrs["counted_ends"] for s in carried) == len(short)
    # a carried end drains nothing and starves nothing
    for s in carried:
        assert "drain" not in s.attrs and "starved_ms" not in s.attrs, s.attrs
    res = eng.run()
    assert [len(res[i]) for i in short] == [4, 6, 3, 5]
    assert [len(res[i]) for i in long_ids] == [60, 60]
    # the last lanes end with nothing left to run: read back first
    counted = _series(COUNTED, "drained")
    assert counted["no"] + counted["yes"] == len(short) + 2
    assert counted["yes"] >= 1
    assert _series(DRAINS, "reason")["may_finish"] >= 1


# -- (c) an eos is not the host's to count --------------------------------------
@pytest.mark.parametrize("name", NAMES)
def test_a_request_with_an_eos_still_drains(name, obs_on):
    eng = _engine(name)
    prompts = _prompts(name, (9, 20, 12))
    budgets = (5, 8, 6)
    plain = _serve(eng, prompts, budgets)
    eos = next(t for t in range(1, 64) if not any(t in s for s in plain))
    _clear()
    n0 = len(_steps())
    assert _serve(eng, prompts, budgets, eos_token_id=eos) == plain
    # every record was read back before the next dispatch
    drains = _series(DRAINS, "reason")
    assert _series(COUNTED, "drained").get("no", 0) == 0
    steps = _steps()[n0:]
    assert not any("counted_ends" in s.attrs for s in steps)
    assert drains["may_finish"] == sum(
        s.attrs.get("drain") == "may_finish" for s in steps) > len(budgets)
    # one eos among requests that stop on length: while it lives, their
    # counted ends are read back before a dispatch all the same
    _clear()
    ids = [eng.add_request(p, max_new_tokens=k,
                           **({"eos_token_id": eos} if k == 8 else {}))
           for p, k in zip(prompts, budgets)]
    res = eng.run()
    assert [res[i] for i in ids] == plain
    assert _series(COUNTED, "drained").get("yes", 0) >= 2


# -- (d) every lane ending, and the threshold -------------------------------------
@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("lens", [(9,), (9, 9)], ids=["one", "two"])
def test_every_lane_ending_drains_and_no_all_done_call_runs(name, lens,
                                                            obs_on):
    eng = _engine(name)
    prompts = _prompts(name, lens)
    budgets = [5] * len(lens)
    read = []
    guarded = eng._process_guarded

    def process(rec):
        out = guarded(rec)
        read.append(len(out))
        return out

    eng._process_guarded = process
    try:
        served = _serve(eng, prompts, budgets)
    finally:
        del eng._process_guarded
    # no record came back empty: no call ran with every lane done
    assert read and all(read), read
    counted = _series(COUNTED, "drained")
    assert counted.get("yes", 0) >= 1
    assert counted.get("yes", 0) + counted.get("no", 0) == len(lens)
    last = [s for s in _steps() if "drain" in s.attrs][-1]
    assert last.attrs["drain"] == "may_finish"
    assert "counted_ends" not in last.attrs
    with _old_schedule(eng):
        assert _serve(eng, prompts, budgets) == served


def test_both_lanes_of_a_record_ending_dispatch_nothing(obs_on):
    eng = _engine("dense", max_slots=2)
    prompts = _prompts("dense", (9, 9))
    ids = [eng.add_request(p, max_new_tokens=5) for p in prompts]
    _until(eng, lambda: eng._inflight is not None
           and len(eng._inflight["ends"]) == 2)
    seq = eng._seq
    assert not eng._spec_safe()
    eng.step()
    assert eng._seq == seq and eng._inflight is None
    assert all(i in eng.finish_reasons for i in ids)
    assert _series(COUNTED, "drained") == {"yes": 2}


def test_the_threshold_weighs_lane_steps_against_live_lanes():
    """``len(ends) * decode_steps > live`` drains: the ended slots would
    sit out more lane-steps than the lanes that go on have in a step."""
    for steps, budgets, safe in ((1, (5, 40, 40), True),    # 1 x 1 <= 2
                                 (1, (5, 5, 40), False),    # 2 x 1 >  1
                                 (4, (5, 40, 40), False),   # 1 x 4 >  2
                                 (2, (5, 40, 40), True)):   # 1 x 2 <= 2
        eng = _engine("dense", **({} if steps == 1
                                  else {"decode_steps": steps}))
        prompts = _prompts("dense", (9, 9, 9))
        for p, k in zip(prompts, budgets):
            eng.add_request(p, max_new_tokens=k)
        _until(eng, lambda: eng._inflight is not None
               and eng._inflight["ends"])
        n_end = sum(k == 5 for k in budgets)
        assert len(eng._inflight["ends"]) == n_end
        assert len(eng._decode_slots()) == 3 - n_end
        assert eng._spec_safe() is safe, (steps, budgets)
        res = eng.run()
        assert sorted(len(v) for v in list(res.values())[-3:]) \
            == sorted(budgets)


# -- (e) a lane that has ended in flight is cancelled, or expires ----------------
@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_a_lane_that_ended_in_flight_is_evicted(name, how):
    eng = _engine(name)
    prompts = _prompts(name, (9, 12, 20))
    kw = {"deadline_s": 3600.0} if how == "deadline" else {}

    def scenario():
        a = eng.add_request(prompts[0], max_new_tokens=4, **kw)
        b = eng.add_request(prompts[1], max_new_tokens=20)
        _until(eng, lambda: eng._inflight is not None
               and eng._inflight["ends"])
        (slot,) = eng._inflight["ends"]
        assert eng.slot_req[slot].req_id == a
        c = eng.add_request(prompts[2], max_new_tokens=6)
        if how == "cancel":
            eng.cancel_request(a, reason="client_disconnected")
        else:
            eng.slot_req[slot].t_deadline = time.perf_counter() - 1.0
        eng.step()
        # evicted at the step's boundary: the record's lane is skipped, its
        # last token never delivered, and the record read back first
        assert eng.finish_reasons[a] == ("client_disconnected"
                                         if how == "cancel"
                                         else "deadline_exceeded")
        assert len(eng.results[a]) == 3
        assert eng.slot_req[slot] is not None \
            and eng.slot_req[slot].req_id == c     # the slot, taken again
        res = eng.run()
        assert eng.block_accounting()["backed"] == 0
        return res[a], res[b], res[c]

    got = scenario()
    assert len(got[1]) == 20 and len(got[2]) == 6
    with _old_schedule(eng):
        assert scenario() == got
    # and once its end was carried and read, the request is terminal: a
    # cancellation is a counted no-op
    a = eng.add_request(prompts[0], max_new_tokens=4)
    eng.add_request(prompts[1], max_new_tokens=20)
    _until(eng, lambda: a in eng.finish_reasons)
    assert eng._inflight is not None and eng.finish_reasons[a] == "finished"
    noops = eng.cancel_noops
    eng.cancel_request(a)
    assert eng.cancel_noops == noops + 1
    eng.run()
    assert eng.results[a] == got[0] + eng.results[a][3:] \
        and len(eng.results[a]) == 4


# -- (f) a reused slot starts clean ---------------------------------------------
@pytest.mark.parametrize("name", NAMES)
def test_a_slot_refilled_behind_the_call_starts_clean(name):
    eng = _engine(name)
    pa, pb, pd, pc = _prompts(name, (9, 12, 7, 40), seed=5)
    alone = _serve(eng, [pc], [10])[0]
    a = eng.add_request(pa, max_new_tokens=5)
    for p in (pb, pd):          # every slot taken: C waits for A's
        eng.add_request(p, max_new_tokens=40)
    _until(eng, lambda: eng._inflight is not None and eng._inflight["ends"])
    (slot,) = eng._inflight["ends"]
    assert eng.slot_req[slot].req_id == a
    c = eng.add_request(pc, max_new_tokens=10)
    recycled = None if eng.win is None else eng.win.recycled
    eng.step()                  # the end is carried: read behind a dispatch
    assert a in eng.finish_reasons and eng.slot_req[slot] is None
    assert eng._inflight is not None and slot not in dict(
        eng._inflight["snapshot"])
    if eng.win is not None:
        # the ended lane wrote no block again, and gave its ring back
        assert eng.win.recycled == recycled and eng.win.top[slot] == 0
    held = eng._inflight["seq"]
    eng.step()                  # refilled while that call is unread
    assert eng.slot_req[slot].req_id == c
    assert eng._seq > held
    if name != "dense":
        first = min(16, len(pc))
        assert eng._chunks[slot]["pos"] == first
        if eng.win is not None:
            assert eng.win.top[slot] == -(-first // BS)
        if eng.model.state_entries:
            # the state after C's first piece is what a slot that held
            # nothing before has after it (a fresh engine's slot 0)
            fresh = _engine.__wrapped__(name)
            fresh.add_request(pc, max_new_tokens=10)
            fresh.step()
            for n in eng.model.state_entries:
                got = np.asarray(eng.pools[n][:, slot])
                assert np.abs(got).max() > 0
                np.testing.assert_allclose(
                    got, np.asarray(fresh.pools[n][:, 0]), rtol=0, atol=1e-6)
    res = eng.run()
    assert res[c] == alone and len(res[a]) == 5
