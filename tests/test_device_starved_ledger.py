"""The engine's ledger of the time the device has nothing queued (PR 37):
``serving_device_starved_seconds_total{phase}``,
``serving_engine_no_work_seconds_total``,
``serving_pipeline_drains_total{reason}`` and ``serving.step``'s
``starved_ms`` / ``starved`` / ``drain``, on float32 toy engines on the CPU.

- pipelined steps accrue nothing and carry neither attribute;
- a slot that may trip on its eos drains (``may_finish``; a budget's end
  alone no longer does, PR 38: ``tests/test_counted_ends.py``): the stretch
  from that readback's return to the next dispatch lands on the phases in
  order, their sum is the step's ``starved_ms``, and the counter moved by
  the sum over the steps;
- the sequence rule: a drain behind a lone piece dispatched earlier in the
  step starts nothing; the drain of the record that reads that piece does;
- an engine with no request moves the no-work counter and no phase;
- each drain reason is counted where it happens;
- with observability off no counter moves, no attribute is computed and
  the ledger holds nothing;
- a chunked engine whose pieces carry the decode rows counts its lone
  pieces, and ``piece_lone_share``'s data file reads the share off them.

No wall-clock threshold anywhere: seconds are compared with each other.
"""
import dataclasses
import importlib
import time

import numpy as np
import pytest

import paddle_tpu  # noqa: F401  (forces the CPU/virtual-device conftest setup)
import jax
import jax.numpy as jnp

import paddle_tpu.observability as obs
from paddle_tpu.models import llama
from paddle_tpu.observability import request_trace
from paddle_tpu.serving import LLMEngine

STARVED = "serving_device_starved_seconds_total"
NO_WORK = "serving_engine_no_work_seconds_total"
DRAINS = "serving_pipeline_drains_total"
# the one vocabulary: the spans' names, and the loop around the step
PHASES = {"serving.readback", "serving.housekeeping", "serving.admit",
          "serving.prefill_build", "serving.decode_prepare",
          "serving.prefill", "serving.decode", "serving.step",
          "serving.spec_draft", "between_steps"}
DISPATCH = {"serving.prefill", "serving.decode", "serving.spec_draft"}


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(
        llama.tiny_llama(vocab=64, hidden=32, layers=2, heads=4, kv_heads=2,
                         seq=128, ffn=64),
        dtype=jnp.float32)
    return cfg, llama.init_params(cfg, jax.random.PRNGKey(0))


def _clear():
    obs.get_registry().reset()
    obs.get_tracer().clear()
    request_trace.get_request_tracer().clear()
    request_trace.get_exemplar_store().clear()


@pytest.fixture
def obs_on():
    _clear()
    obs.enable()
    try:
        yield
    finally:
        obs.disable()
        _clear()


def _engine(model, **kw):
    cfg, params = model
    kw.setdefault("max_slots", 2)
    return LLMEngine(params, cfg, block_size=8, max_model_len=128,
                     prompt_buckets=[8, 32], **kw)


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(1, 64, size=n).tolist()


def _series(name, label=None):
    """{label value: counter value} of a family (``""``: the bare child)."""
    for m in obs.snapshot()["metrics"]:
        if m["name"] == name:
            return {s["labels"].get(label, ""): s["value"]
                    for s in m["series"]}
    return {}


def _total(name, label):
    return sum(_series(name, label).values())


def _steps():
    return sorted((s for s in obs.get_tracer().spans()
                   if s.name == "serving.step"), key=lambda s: s.t0)


def _inside(step, name):
    return sorted((s for s in obs.get_tracer().spans() if s.name == name
                   and s.tid == step.tid and step.t0 <= s.t0
                   and s.t1 <= step.t1), key=lambda s: s.t0)


def _until(eng, cond, limit=200):
    for _ in range(limit):
        if cond():
            return
        eng.step()
    raise AssertionError("the scenario never reached its state")


# ---------------------------------------------------------------------------
# pipelined steps, and the drain of a slot on its last token
# ---------------------------------------------------------------------------
def test_pipelined_steps_accrue_nothing(model, obs_on):
    eng = _engine(model)
    for n in (3, 7):
        eng.add_request(_prompt(n, n), max_new_tokens=24)
    # both rows decode and a call is in flight: the pipeline is up
    _until(eng, lambda: eng._inflight is not None
           and len(eng._inflight["snapshot"]) == 2)
    before, n0 = _total(STARVED, "phase"), len(_steps())
    drains = _total(DRAINS, "reason")
    for _ in range(6):
        eng.step()
    steps = _steps()[n0:]
    assert len(steps) == 6
    for s in steps:
        assert "drain" not in s.attrs and "starved_ms" not in s.attrs \
            and "starved" not in s.attrs, s.attrs
    assert _total(STARVED, "phase") == before
    assert _total(DRAINS, "reason") == drains
    assert eng._starved_t is None and eng._starved == {}
    # every record remembers the newest program at its making
    assert eng._inflight["seq"] == eng._seq


def test_an_eos_drains_and_the_stretch_lands_on_the_phases(model, obs_on):
    eng = _engine(model)
    # three requests on two slots: the first to end frees the slot that
    # the third is admitted into, on a drained pipeline (each may trip on
    # its eos at any step, which the host cannot count ahead)
    for n, k in ((3, 6), (7, 9), (20, 5)):
        eng.add_request(_prompt(n, n), max_new_tokens=k, eos_token_id=0)
    eng.run()
    steps = _steps()
    drained = [s for s in steps if s.attrs.get("drain") == "may_finish"]
    assert drained
    assert _series(DRAINS, "reason").get("may_finish") == len(drained)
    summed = 0.0
    refill = 0
    for s in steps:
        st = s.attrs.get("starved")
        if st is None:
            assert "starved_ms" not in s.attrs
            continue
        assert set(st) <= PHASES, set(st) - PHASES
        assert all(v >= 0.0 for v in st.values())
        assert s.attrs["starved_ms"] == pytest.approx(sum(st.values()))
        assert s.attrs["starved_ms"] > 0.0
        summed += s.attrs["starved_ms"] / 1e3
    for s in drained:
        st = s.attrs.get("starved")
        assert st, "a drain with nothing behind it starves the device"
        own = [p for p in st if p != "between_steps"]
        # in order: the record's host work first, then what the step did
        # up to the dispatching call, which ends the stretch
        assert own[0] == "serving.readback", own
        if own[-1] in DISPATCH and "serving.prefill_build" in own:
            refill += 1
            assert own.index("serving.admit") \
                < own.index("serving.prefill_build") \
                < own.index("serving.prefill")
    assert refill, "no freed slot was refilled on a drained pipeline"
    # the counter moved by the sum over the steps, phase by phase
    assert _total(STARVED, "phase") == pytest.approx(summed)
    by_phase = _series(STARVED, "phase")
    for p in by_phase:
        if p:
            assert by_phase[p] == pytest.approx(sum(
                s.attrs["starved"].get(p, 0.0) for s in steps
                if "starved" in s.attrs) / 1e3)


# ---------------------------------------------------------------------------
# the sequence rule
# ---------------------------------------------------------------------------
def test_a_drain_behind_a_lone_piece_starts_nothing(model, obs_on):
    eng = _engine(model, prefill_chunk=8)
    assert not eng._piggyback          # every piece is its own program
    a = eng.add_request(_prompt(5, 1), max_new_tokens=4)
    _until(eng, lambda: eng._inflight is not None)
    # a prompt of four pieces joins while the first request decodes: each
    # step now dispatches a piece BEFORE it looks at the in-flight call
    eng.add_request(_prompt(30, 2), max_new_tokens=6)
    n0 = len(_steps())
    _until(eng, lambda: a in eng.finish_reasons)
    behind = []
    for s in _steps()[n0:]:
        if "drain" not in s.attrs:
            continue
        back = _inside(s, "serving.readback")[0]
        pieces = [p for p in _inside(s, "serving.prefill") if p.t1 <= back.t0]
        if pieces:
            behind.append(s)
            # the record read back is older than the piece: the device
            # still has the piece to run, and nothing starts
            assert "starved_ms" not in s.attrs, s.attrs
    assert behind, "no drain fell behind a piece dispatched in its step"
    assert eng._starved_t is None      # the piece's record is in flight
    eng.run()
    # ... until the record that reads that piece is back with nothing
    # dispatched behind it
    later = [s for s in _steps()
             if s.t0 > behind[-1].t1 and "starved_ms" in s.attrs]
    assert later and all("serving.readback" in s.attrs["starved"]
                         or "between_steps" in s.attrs["starved"]
                         for s in later)


def test_a_readback_of_an_older_program_is_told_by_its_number(model, obs_on):
    eng = _engine(model)
    eng.add_request(_prompt(3), max_new_tokens=8)
    _until(eng, lambda: eng._inflight is not None)
    rec = eng._inflight
    assert rec["seq"] == eng._seq
    eng._device_get(rec["toks"], rec["seq"] - 1)      # an older program's
    assert eng._starved_t is None
    eng._device_get(rec["toks"])                      # first tokens: none
    assert eng._starved_t is None
    eng._device_get(rec["toks"], rec["seq"])          # the newest
    assert eng._starved_t is not None and eng._phase == "serving.readback"
    n = eng._seq
    assert eng._dispatched() == n + 1 and eng._starved_t is None
    assert set(eng._starved) == {"serving.readback"}


# ---------------------------------------------------------------------------
# an empty engine is not starving
# ---------------------------------------------------------------------------
def test_an_engine_with_no_request_moves_no_work_and_no_phase(model, obs_on):
    eng = _engine(model)
    eng.add_request(_prompt(3), max_new_tokens=3)
    eng.run()
    assert not eng.has_work() and eng._idle and eng._starved_t is not None
    phases = dict(_series(STARVED, "phase"))
    flushed = _total(NO_WORK, None)
    t0 = time.perf_counter()
    time.sleep(0.02)
    waited = time.perf_counter() - t0
    eng.add_request(_prompt(4), max_new_tokens=3)
    # the wait went to no work, and to no phase, before any step ran
    assert eng._no_work_s >= waited and eng._starved == {}
    assert not eng._idle
    eng.run()
    assert _total(NO_WORK, None) - flushed >= waited
    after = _series(STARVED, "phase")
    # the second request's admission is starvation again (the device is
    # empty and there is work): phases moved, but by less than the wait
    # would have added had it been counted
    moved = sum(after.values()) - sum(phases.values())
    assert moved > 0.0
    first = [s for s in _steps() if s.t0 >= t0][0]
    assert "between_steps" in first.attrs["starved"]
    assert first.attrs["starved"]["between_steps"] / 1e3 < waited


# ---------------------------------------------------------------------------
# the drains, by why
# ---------------------------------------------------------------------------
def _drain_may_finish(model):
    eng = _engine(model)
    eng.add_request(_prompt(3), max_new_tokens=4, eos_token_id=0)
    eng.run()
    return eng


def _drain_no_active(model):
    eng = _engine(model)
    eng.add_request(_prompt(3), max_new_tokens=12)
    _until(eng, lambda: eng._inflight is not None)
    # the record's slot is alive and far from its end, yet no slot decodes
    # (as after a re-admission into chunks): the step's third site
    eng._decode_slots = lambda: []
    eng.step()
    del eng._decode_slots
    eng.run()
    return eng


def _drain_backing(model):
    # four usable blocks for two rows that each grow to three
    eng = _engine(model, num_blocks=5)
    for seed in (1, 2):
        eng.add_request(_prompt(7, seed), max_new_tokens=14)
    eng.run()
    return eng


def _drain_spec_wave(model):
    cfg, params = model
    eng = _engine(model, draft_params=params, draft_config=cfg,
                  spec_tokens=2)
    # a sampled request keeps the normal, pipelined path
    a = eng.add_request(_prompt(3), max_new_tokens=24, temperature=0.8)
    _until(eng, lambda: eng._inflight is not None)
    # it is cancelled and a greedy one admitted in ONE step: the wave finds
    # the sampled request's call still in flight
    eng.cancel_request(a)
    eng.add_request(_prompt(5, 5), max_new_tokens=4)
    eng.run()
    assert eng.spec_waves >= 1
    return eng


def _drain_run_end(model):
    eng = _engine(model)
    eng.add_request(_prompt(3), max_new_tokens=12)
    _until(eng, lambda: eng._inflight is not None)
    eng.has_work = lambda: False       # run() has nothing to step ...
    eng.run()                          # ... and drains what is in flight
    del eng.has_work
    assert eng._inflight is None
    eng.run()
    return eng


@pytest.mark.parametrize("scenario", [
    _drain_may_finish, _drain_no_active, _drain_backing, _drain_spec_wave,
    _drain_run_end], ids=lambda f: f.__name__[len("_drain_"):])
def test_each_drain_reason_is_counted_at_its_site(model, obs_on, scenario):
    reason = scenario.__name__[len("_drain_"):]
    scenario(model)
    counted = _series(DRAINS, "reason")
    assert counted.get(reason, 0) >= 1, counted
    on_spans = [s.attrs["drain"] for s in _steps() if "drain" in s.attrs]
    if reason == "run_end":
        # outside any step: counted, on no span
        assert counted[reason] == 1 and reason not in on_spans
    else:
        # a step's span names its FIRST drain; here no step has two
        assert on_spans.count(reason) == counted[reason]
    assert sum(counted.values()) == len(on_spans) + counted.get("run_end", 0)


# ---------------------------------------------------------------------------
# observability off
# ---------------------------------------------------------------------------
def test_with_observability_off_the_step_does_no_ledger_work(model):
    _clear()
    assert not obs.enabled()
    eng = _engine(model)
    clock = []
    real = eng._mark.__func__

    def mark(self, phase):
        clock.append(self._starved_t)
        return real(self, phase)

    eng._mark = mark.__get__(eng)
    for n, k in ((3, 6), (7, 4), (20, 5)):
        eng.add_request(_prompt(n, n), max_new_tokens=k)
    eng.run()
    # the marks ran and none of them found a stretch open: no clock read,
    # no add; the programs were numbered all the same
    assert clock and all(t is None for t in clock)
    assert eng._seq > 0 and eng._starved_t is None
    assert eng._starved == {} and eng._no_work_s == 0.0
    assert eng._step_drain is None
    assert obs.get_tracer().spans() == []
    for name, lab in ((STARVED, "phase"), (NO_WORK, None), (DRAINS, "reason")):
        assert not any(_series(name, lab).values()), name


# ---------------------------------------------------------------------------
# the lone pieces of an engine whose pieces carry the decode rows
# ---------------------------------------------------------------------------
def test_piece_lone_share_reads_the_pieces_that_carried_nothing(obs_on):
    t = importlib.import_module("test_lfm2_moe")
    cfg = t.FAM.program_config(t.MODEL, max_seq_len=128, dtype=jnp.float32)
    eng = LLMEngine(t._params(), cfg, max_slots=3, block_size=8,
                    max_model_len=128, prompt_buckets=[16, 32], seed=0,
                    prefill_chunk=16, decode_kernel="ragged")
    assert eng._piggyback
    rng = np.random.default_rng(3)
    snap_open = obs.snapshot()
    # one short prompt alone: its one piece finds no row to carry
    eng.add_request(rng.integers(0, 256, size=9).tolist(), max_new_tokens=24)
    _until(eng, lambda: eng._inflight is not None
           and len(eng._inflight["snapshot"]) == 1)
    # two prompts of three pieces each join it: a step's first piece runs
    # lone, its last carries the decoding row
    for _ in range(2):
        eng.add_request(rng.integers(0, 256, size=40).tolist(),
                        max_new_tokens=3)
    eng.run()
    rec = {"snap_open": snap_open, "snap_close": obs.snapshot()}
    programs = _series("serving_prefill_programs_total", "carried")
    assert programs["none"] == 4 and programs["rows"] == 3, programs
    from benchmark import manifest
    from benchmark.readers import counter
    spec = manifest.Manifest().metric_spec("piece_lone_share")
    assert spec["reader"] == "counter"
    assert counter.read(rec, **spec["args"]) == pytest.approx(100 * 4 / 7)
    # and the same run through the other two data files
    steps = len(_steps())
    for name, want in (
            ("pipeline_drains_per_step",
             _total(DRAINS, "reason") / steps),
            ("device_starved_ms_per_step",
             1e3 * _total(STARVED, "phase") / steps)):
        spec = manifest.Manifest().metric_spec(name)
        assert counter.read(rec, **spec["args"]) == pytest.approx(want)
        assert want > 0.0
