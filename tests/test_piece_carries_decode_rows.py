"""A step's last prefill piece carries the decode rows in ONE program
(``serving/engine.py`` ``_paged_prefill(dec=)``, ``LLMEngine._piggyback``),
float32 on the CPU with the walk kernels interpreted, over the four
families that chunk their prefill (LFM2, Mellum2, DeepSeek-V2 and
Ling-3.0-flash, whose matrix state the programs advance in place, through
the one interface: ``prefill_mix`` / ``decode_mix`` / ``ffn``):

- the program against today's pair, a piece's program and then a decode
  step, run one behind the other from the same pools: the piece's first
  token, the slots' tokens, the carry, every pool, the per-slot state and
  the window's ring equal; with ``active`` all false it is the lone piece;
- the engine: the greedy outputs of a mixed run (long and short prompts,
  more callers than slots, a pool too small: a preemption and a
  re-admission inside it) equal, token for token, those of an engine that
  takes the two-program path; a slot whose final piece rode with the decode
  rows decodes from the next step with the right length, budget and first
  token;
- the spans and the counters that say which program ran what.
"""
import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.observability as obs
from paddle_tpu.models.llama_served import ServeOpts
from paddle_tpu.serving import LLMEngine, engine as eng_mod

F32 = jnp.float32
GREEDY = (False, False, False)
FAMILIES = {"lfm2": "test_lfm2_moe", "mellum": "test_mellum",
            "deepseek_v2": "test_deepseek_v2_served",
            "ling_hybrid": "test_ling_hybrid", "afmoe": "test_afmoe"}
# (top-k, expert layers) of the tiny models whose experts are ALL held
ALL_HELD = {"lfm2": (2, 2), "mellum": (2, 4)}
N, BS, MML, CHUNK = 3, 8, 128, 16


@functools.lru_cache(maxsize=None)
def _family(name):
    """(FAM, MODEL, params) of a family's own test module."""
    t = importlib.import_module(FAMILIES[name])
    return t.FAM, t.MODEL, t._params()


def _engine(name, pairs: bool = False, **kw):
    """The tiny engine in the shape the rule sends to ONE program: pieces
    of 16 between decode steps, one step a call, the ragged walk. ``pairs``:
    the same engine on the two-program path (the same kernels, so the tokens
    must be equal to the last one)."""
    fam, model, params = _family(name)
    cfg = fam.program_config(model, max_seq_len=MML, dtype=F32)
    eng = LLMEngine(params, cfg, max_slots=N, block_size=BS,
                    max_model_len=MML, prompt_buckets=[16, 32], seed=0,
                    prefill_chunk=CHUNK, decode_kernel="ragged", **kw)
    assert eng._piggyback
    if pairs:
        eng._piggyback = False
    return eng


def _prompts(lens, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, size=n).tolist() for n in lens]


# -- the rule -----------------------------------------------------------------
@pytest.mark.parametrize("kw,why", [
    (dict(prefill_chunk=0), "no pieces between decode steps"),
    (dict(decode_steps=2), "a decode call of two steps"),
    (dict(decode_kernel="bucketed"), "a table sliced to a bucket")],
    ids=["no-chunking", "two-steps", "bucketed"])
def test_the_rule_is_read_from_the_engine_and_nothing_sets_it(kw, why):
    fam, model, params = _family("lfm2")
    cfg = fam.program_config(model, max_seq_len=MML, dtype=F32)
    base = dict(prefill_chunk=CHUNK, decode_kernel="ragged")
    eng = LLMEngine(params, cfg, max_slots=N, block_size=BS,
                    max_model_len=MML, prompt_buckets=[16, 32], seed=0,
                    **{**base, **kw})
    assert not eng._piggyback, why
    import inspect
    assert "piggyback" not in inspect.signature(LLMEngine.__init__).parameters


def test_a_model_without_the_split_keeps_its_two_programs():
    from paddle_tpu.models import llama

    cfg = llama.LlamaConfig(vocab_size=64, hidden_size=32,
                            intermediate_size=64, num_layers=1, num_heads=2,
                            num_kv_heads=1, head_dim=16, dtype=F32)
    eng = LLMEngine(llama.init_params(cfg, jax.random.PRNGKey(0)), cfg,
                    max_slots=2, block_size=8, max_model_len=64,
                    prompt_buckets=[16, 32], prefill_chunk=16,
                    decode_kernel="ragged")
    assert not eng._piggyback


# -- the program against the pair ---------------------------------------------
@functools.lru_cache(maxsize=None)
def _mid_run(name):
    """An engine on the two-program path, stopped where slots 0 and 1
    decode and slot 2's FINAL piece (8 tokens behind a history of 64, past
    Mellum2's window) is due: ``(engine, row, pools, dec operands)`` with
    the pipeline drained, so the host's state is exact."""
    eng = _engine(name, pairs=True)
    prompts = _prompts((9, 23, 72))
    for p in prompts:
        eng.add_request(p, max_new_tokens=24)
    while not (2 in eng._chunks and eng._chunks[2]["pos"] == 64):
        eng.step()
    if eng._inflight is not None:
        eng._process_inflight()
    assert eng._decode_slots() == [0, 1]
    row = (2, eng.slot_req[2], prompts[2], 64, 8, True)
    last = np.zeros(N, np.int32)
    for i in (0, 1):
        last[i] = eng.slot_out[i][-1]
    dec = [jnp.asarray(last), jnp.asarray(eng.lengths, jnp.int32),
           jnp.zeros(N, bool), jnp.asarray([5, 7, 0], jnp.int32),
           jax.random.PRNGKey(1), jnp.asarray([True, True, False]),
           jnp.asarray(eng.table), jnp.zeros(N, F32), jnp.zeros(N, jnp.int32),
           jnp.ones(N, F32), jnp.full(N, -1, jnp.int32)]
    if eng.win is not None:
        dec.append(eng._wtable_dev)
    return eng, row, jax.tree_util.tree_map(jnp.copy, eng.pools), dec


def _programs(eng, pnbk):
    """(the piece's lone program, the decode program, the ONE program),
    jitted without donation so that one set of pools serves all three."""
    opts = ServeOpts(ragged=True)
    pre = functools.partial(eng_mod._paged_prefill, model=eng.model,
                            opts=opts, sample_flags=GREEDY, prefix_nbk=pnbk)
    dec = functools.partial(eng_mod._paged_decode, model=eng.model, n_steps=1,
                            opts=opts, sample_flags=GREEDY)
    return jax.jit(pre), jax.jit(dec), jax.jit(pre)


def _piece_operands(eng, row):
    bucket, _flags, pnbk, args = eng._prefill_operands(row)
    kw = {}
    if eng.model.state_entries:
        kw["slot"] = jnp.asarray([row[0]], jnp.int32)
    if eng.win is not None:
        kw["win"] = eng._window_operands(row, bucket, pnbk)
    return pnbk, args, kw


def _assert_pools_equal(got, want, state_entries):
    """Every pool but its trash block (block 0 takes the pad tail and the
    idle slots' rows, two writers in no order), every slot's state but the
    trash row. Equal to float32's last digits: a matmul over the rows of
    both kinds sums in another order than one over the piece's alone (read
    while writing this: 1.4e-6 at most; a lost state or a missed write-back
    moves whole units)."""
    assert set(got) == set(want)
    for name in want:
        keep = (slice(None), slice(0, N)) if name in state_entries \
            else (slice(None), slice(1, None))
        np.testing.assert_allclose(
            np.asarray(got[name][keep]), np.asarray(want[name][keep]),
            rtol=0, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_one_program_equals_the_pair_from_the_same_pools(name):
    eng, row, pools, dec = _mid_run(name)
    pnbk, args, kw = _piece_operands(eng, row)
    lone, decode, one = _programs(eng, pnbk)
    args[4] = pools
    tok_a, pools_a, stats_a = lone(*args, **kw)
    wt = dec[11:]
    (emit_a, last_a, lens_a, done_a, rem_a, _key, pools_a,
     stats_d) = decode(eng.params, *dec[:7], pools_a, *dec[7:11], *wt)
    (tok_b, emit_b, last_b, lens_b, done_b, rem_b, _key, pools_b,
     stats_b) = one(*args, **kw, dec=tuple(dec))
    assert int(tok_a[0]) == int(tok_b[0])
    for a, b in ((emit_a, emit_b), (last_a, last_b), (lens_a, lens_b),
                 (done_a, done_b), (rem_a, rem_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.asarray(emit_b)[0, :2].min() >= 0 and int(emit_b[0, 2]) == -1
    assert np.asarray(lens_b).tolist() == [int(eng.lengths[0]) + 1,
                                           int(eng.lengths[1]) + 1, 64]
    assert np.asarray(rem_b).tolist() == [4, 6, 0]
    _assert_pools_equal(pools_b, pools_a, eng.model.state_entries)
    # ONE set of counts for the whole program: the pair's, summed (the
    # fullest expert's share is of each call's own rows, and the row tiles
    # follow each call's layout)
    np.testing.assert_array_equal(np.asarray(stats_b[:2]),
                                  np.asarray((stats_a + stats_d)[:2]))
    if name in ALL_HELD:
        k, layers = ALL_HELD[name]
        assert float(stats_b[1]) == (8 + 2) * k * layers


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_with_no_slot_active_it_is_the_lone_piece(name):
    eng, row, pools, dec = _mid_run(name)
    pnbk, args, kw = _piece_operands(eng, row)
    lone, _decode, one = _programs(eng, pnbk)
    args[4] = pools
    tok_a, pools_a, stats_a = lone(*args, **kw)
    idle = list(dec)
    idle[5] = jnp.zeros(N, bool)
    (tok_b, emit, last, lens, done, rem, _key, pools_b,
     stats_b) = one(*args, **kw, dec=tuple(idle))
    assert int(tok_a[0]) == int(tok_b[0])
    assert np.asarray(emit).tolist() == [[-1] * N]
    for got, want in ((last, dec[0]), (lens, dec[1]), (done, dec[2]),
                      (rem, dec[3])):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    _assert_pools_equal(pools_b, pools_a, eng.model.state_entries)
    np.testing.assert_array_equal(np.asarray(stats_b), np.asarray(stats_a))


# -- the engine ---------------------------------------------------------------
LENS, NEW = (5, 50, 23, 70, 9, 40), 20


def _counter(snap, name, **labels):
    return sum(s["value"] for m in snap["metrics"] if m["name"] == name
               for s in m["series"]
               if all(s["labels"].get(k) == v for k, v in labels.items()))


@functools.lru_cache(maxsize=None)
def _mixed_run(name):
    """Six callers on three slots over a pool too small for them (a
    preemption and its re-admission inside the run), through the engine
    whose pieces carry the decode rows, with the registry and the tracer on:
    ``(served tokens, spans, counters moved)``."""
    prompts = _prompts(LENS)
    obs.enable()
    try:
        obs.get_tracer().clear()
        before = obs.snapshot()
        eng = _engine(name, num_blocks=16)
        ids = [eng.add_request(p, max_new_tokens=NEW) for p in prompts]
        res = eng.run()
        after = obs.snapshot()
        spans = [(s.name, s.t0, s.t1, dict(s.attrs))
                 for s in obs.get_tracer().spans()]
    finally:
        obs.disable()
    assert eng.block_accounting()["backed"] == 0
    moved = lambda n, **lb: _counter(after, n, **lb) - _counter(before, n,
                                                                **lb)
    return [res[i] for i in ids], spans, moved


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_a_mixed_run_serves_the_two_program_engine_s_tokens(name):
    served, _spans, moved = _mixed_run(name)
    assert moved("serving_preemptions_total") >= 1
    assert moved("serving_prefill_programs_total", carried="rows") >= 3
    eng = _engine(name, pairs=True, num_blocks=16)
    ids = [eng.add_request(p, max_new_tokens=NEW) for p in _prompts(LENS)]
    res = eng.run()
    assert [res[i] for i in ids] == served
    assert all(len(s) == NEW for s in served)


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_the_spans_and_counters_say_which_program_ran_what(name):
    _served, spans, moved = _mixed_run(name)
    pre = [a for n, _t0, _t1, a in spans if n == "serving.prefill"]
    dec = [a for n, _t0, _t1, a in spans if n == "serving.decode"]
    rode = [a for a in pre if a["decode_slots"]]
    assert rode and dec and len(rode) < len(pre)
    # the counter's two labels sum to the pieces dispatched
    assert moved("serving_prefill_programs_total", carried="rows") \
        == len(rode)
    assert moved("serving_prefill_programs_total", carried="none") \
        == len(pre) - len(rode)
    assert moved("serving_decode_steps_total", program="piece") == len(rode)
    assert moved("serving_decode_steps_total", program="decode") == len(dec)
    for a in pre:
        # the piece's span is the piece's: one row, its own request
        assert a["batch"] == 1 and len(a["request_ids"]) == 1
        assert {"tokens", "start", "walk_blocks", "kv_bytes"} <= set(a)
        assert (a["kv_bytes"] > 0) == bool(a["decode_slots"])
    # no step has both: a step's decode rows ran in ONE program
    steps = [(t0, t1) for n, t0, t1, _a in spans if n == "serving.step"]
    inside = lambda t, step: step[0] <= t <= step[1]
    for step in steps:
        ran = [n for n, t0, _t1, a in spans if inside(t0, step)
               and (n == "serving.decode"
                    or n == "serving.prefill" and a["decode_slots"])]
        assert len(ran) <= 1, ran
    # ONE set of the model's counts a program, on the span that ran it
    assert all("expert_rows" in a and "experts_hit" in a for a in pre + dec)
    assert moved("serving_moe_assigned_total") == sum(
        a["expert_rows"] for a in pre + dec)
    if name in ALL_HELD:
        k, layers = ALL_HELD[name]
        for a in rode:
            assert a["expert_rows"] == (a["tokens"][0] + a["decode_slots"]) \
                * k * layers
        for a in dec:
            assert a["expert_rows"] == a["slots"] * k * layers


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_a_final_piece_that_rode_joins_the_decode_rows_a_step_later(name):
    """Slot 0 decodes; slot 1's prompt of 40 is pieces of 16, 16 and 8, each
    the step's last piece, so the final one rides with slot 0's decode row.
    Its first token is read back with that record; the NEXT dispatch has the
    slot among the decode rows at its context's length, one token of its
    budget spent, the token in the carry."""
    eng = _engine(name)
    a, b = _prompts((9, 40))
    ra = eng.add_request(a, max_new_tokens=12)
    for _ in range(3):
        eng.step()
    rb = eng.add_request(b, max_new_tokens=12)
    out = {ra: [], rb: []}
    while not eng._joining:
        assert eng.has_work()
        for rid, tok in eng.step():
            out[rid].append(tok)
    (slot, rid, tok_dev), = eng._joining
    assert rid == rb and out[rb] == []
    assert slot not in {s for s, _ in eng._inflight["snapshot"]}
    assert (slot, rid, tok_dev) in eng._inflight["adm"]
    first = int(tok_dev[0])
    for rid_, tok in eng.step():
        out[rid_].append(tok)
    assert out[rb][:1] == [first]             # read back with ITS record
    assert (slot, rb) in eng._inflight["snapshot"] and not eng._joining
    assert eng._inflight["rem_start"][slot] == 12 - 1
    last, lens, done, rem, _key = (np.asarray(x) for x in eng._carry)
    assert lens[slot] == 40 + 1 and rem[slot] == 12 - 2 and not done[slot]
    res = eng.run()
    assert res[rb][0] == first and len(res[rb]) == 12 and len(res[ra]) == 12
    pairs = _engine(name, pairs=True)
    ids = [pairs.add_request(p, max_new_tokens=12) for p in (a, b)]
    want = pairs.run()
    assert [res[ra], res[rb]] == [want[i] for i in ids]
