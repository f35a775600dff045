"""The window kind's rings (``serving/window_ledger.py``) alone and inside
``LLMEngine`` with Mellum's two kinds of cache entry: a slot owns its ring
for good, blocks are written again in place, both ledgers' invariants hold
after every step of a random schedule, admission never waits for the
window kind and preemption (the full kind ran dry) gives the ring up too."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest, weights
from paddle_tpu.serving import LLMEngine
from paddle_tpu.serving.window_ledger import WindowLedger

FAM = manifest.load_family("mellum")
PUBLISHED = manifest.Manifest().config("mellum2-12b-a2.5b-serve")
BASE = {k: PUBLISHED[k] for k in (
    "family", "kind", "attention_bias", "tie_word_embeddings",
    "use_sliding_window", "norm_topk_prob", "rms_norm_eps",
    "rope_parameters")}
MODEL = {**BASE, **FAM.tiny(BASE), "sliding_window": 32}
W, BS = 32, 8
RING = W // BS + 1
F32 = jnp.float32


@functools.lru_cache(maxsize=None)
def _params():
    return jax.jit(lambda k: FAM.make_params(MODEL, k, F32))(
        weights.seed_key(11))


def _engine(max_slots=3, **kw):
    cfg = FAM.program_config(MODEL, max_seq_len=256, dtype=F32)
    kw.setdefault("prompt_buckets", [16, 32])
    return LLMEngine(_params(), cfg, max_slots=max_slots, block_size=BS,
                     max_model_len=256, seed=0, **kw)


def _balanced(eng):
    acc = eng.block_accounting()
    assert acc["free"] + acc["backed"] + acc["cached"] + acc["squeezed"] \
        + acc["in_flight"] == acc["total"]
    win = acc["window"]
    assert win["free"] + win["backed"] == win["total"] == eng.N * RING
    # a window slot never holds more than ceil(W / bs) + 1 blocks, and an
    # empty slot holds none
    held = np.minimum(eng.win.top, RING)
    assert win["backed"] == int(held.sum())
    assert all(held[s] == 0 for s in range(eng.N) if eng.slot_req[s] is None)
    return acc


# -- the rings alone ----------------------------------------------------------
def test_a_ring_of_ceil_w_over_bs_plus_one_columns_a_slot():
    led = WindowLedger(slots=2, window=W, block_size=BS)
    assert (led.width, led.nb) == (RING, 2 * RING + 1)
    assert led.table.tolist() == [[1, 2, 3, 4, 5], [6, 7, 8, 9, 10]]
    assert led.accounting() == {"total": 10, "free": 10, "backed": 0}
    led.note_written(0, 3)
    assert led.accounting() == {"total": 10, "free": 7, "backed": 3}
    led.note_written(0, 9)                       # round the ring: in place
    led.note_written(1, 1)
    assert led.accounting() == {"total": 10, "free": 4, "backed": 6}
    led.release(0)
    assert led.accounting() == {"total": 10, "free": 9, "backed": 1}
    assert led.table.tolist() == [[1, 2, 3, 4, 5], [6, 7, 8, 9, 10]]
    with pytest.raises(ValueError, match="a window of 0"):
        WindowLedger(slots=1, window=0, block_size=BS)


@pytest.mark.parametrize("slots,window,bs", [(1, 32, 8), (3, 33, 8),
                                             (32, 1024, 16)])
def test_no_block_belongs_to_two_slots_and_none_is_the_trash_block(
        slots, window, bs):
    led = WindowLedger(slots=slots, window=window, block_size=bs)
    assert led.width == -(-window // bs) + 1
    ids = led.table.reshape(-1).tolist()
    assert sorted(ids) == list(range(1, led.nb))     # each block once
    # whatever a slot writes or reads lies in its own ring
    for s in (0, slots - 1):
        own = set(led.table[s].tolist())
        for b0 in (0, 3, led.width + 2, 7 * led.width - 1):
            assert set(led.write_ids(s, b0, led.width + 3, led.width + 4)
                       .tolist()) <= own | {0}
            assert set(led.history(s, b0 * bs + 5)[0].tolist()) <= own | {0}


def test_blocks_written_again_in_place_are_counted_once():
    led = WindowLedger(slots=1, window=W, block_size=BS)
    led.note_written(0, 3)
    assert led.recycled == 0                 # the ring is not round yet
    led.note_written(0, RING)
    assert led.recycled == 0
    led.note_written(0, RING + 2)            # two blocks past the ring
    assert led.recycled == 2
    led.note_written(0, RING + 2)            # the same horizon again
    led.note_written(0, RING + 1)            # an older one
    assert led.recycled == 2
    led.note_written(0, 20)
    assert led.recycled == 20 - RING
    led.release(0)                           # the next request starts over
    led.note_written(0, RING + 1)
    assert led.recycled == 20 - RING + 1


def test_the_targets_of_a_pieces_scatter_and_its_history():
    led = WindowLedger(slots=1, window=W, block_size=BS)
    ring = led.table[0].copy()
    # a piece of four blocks at logical block 6: columns 6, 7, 8, 9 mod 5
    ids = led.write_ids(0, 6, 4, 6)
    assert list(ids) == [ring[1], ring[2], ring[3], ring[4], 0, 0]
    # a piece longer than the ring keeps its last five blocks only, so
    # that no two rows of one scatter name one block
    ids = led.write_ids(0, 0, 8, 8)
    assert list(ids[:3]) == [0, 0, 0] and len(set(ids[3:])) == RING
    assert list(ids[3:]) == [ring[b % RING] for b in range(3, 8)]
    # the history of a piece starting at 48: positions [17, 48): blocks
    # 2..5 in order, then the trash block
    tbl, start = led.history(0, 48)
    assert start == 16
    assert list(tbl) == [ring[2], ring[3], ring[4], ring[0], 0]
    assert led.history(0, 0)[1] == 0 and not led.history(0, 0)[0].any()
    # what a decode step walks: never more than the ring
    assert [led.walk_blocks(n) for n in (0, 1, 8, 9, 31, 32, 33, 40, 200)] \
        == [0, 1, 1, 2, 4, 4, 5, 4, 4]
    assert max(led.walk_blocks(n) for n in range(400)) == RING


# -- inside the engine --------------------------------------------------------
def test_the_window_pools_have_their_own_block_count():
    eng = _engine(max_slots=3, num_blocks=40)
    assert eng.win.nb - 1 == 3 * RING                      # a ring a slot
    assert {n: p.shape[1] for n, p in eng.pools.items()} == {
        "kvf0": 41, "kvw0": 16, "kvw1": 16, "kvw2": 16}
    assert eng.model.window_entries == ("kvw0", "kvw1", "kvw2")
    # the window kind's count is the slots', whatever the full kind's is
    assert _engine(max_slots=3, num_blocks=10).win.nb - 1 == 3 * RING
    # the per-token bytes are the full kind's, the window kind's a slot's
    assert eng._pool_block_bytes() == BS * 2 * 2 * 64 * 4          # one layer
    assert eng._pool_block_bytes(window=True) == 3 * BS * 2 * 2 * 64 * 4
    # the device's copy of the ring table is made once
    assert np.array_equal(np.asarray(eng._wtable_dev), eng.win.table)


@pytest.mark.parametrize("seed", [0, 1])
def test_both_ledgers_balance_after_every_step_of_a_random_schedule(seed):
    """200 steps of arrivals of random lengths into three slots with a
    full kind's pool that runs dry now and then: the two ledgers balance,
    a slot's ring never grows past its width, and every request ends."""
    rng = np.random.default_rng(seed)
    eng = _engine(max_slots=3, num_blocks=26, prefill_chunk=16)
    sent, steps = [], 0
    while steps < 200 or eng.has_work():
        if steps < 200 and len(eng.queue) < 2 and rng.random() < 0.4:
            n = int(rng.choice([3, 9, 20, 33, 50, 90]))
            sent.append(eng.add_request(
                rng.integers(0, 256, size=n).tolist(),
                max_new_tokens=int(rng.integers(2, 40))))
        eng.step()
        _balanced(eng)
        steps += 1
        assert steps < 3000
    acc = _balanced(eng)
    assert acc["backed"] == 0 and acc["window"]["backed"] == 0
    assert sorted(eng.results) == sorted(sent)
    assert eng.win.recycled > 0                  # contexts crossed the ring


def test_admission_never_waits_for_the_window_kind():
    """Three slots, three prompts of twelve ring-widths each: all are
    admitted in one step where the full kind has room (each slot has its
    ring), and the one for which the full kind has none waits for it."""
    rng = np.random.default_rng(5)
    p = lambda n: rng.integers(0, 256, size=n).tolist()
    eng = _engine(max_slots=3, num_blocks=70)
    ids = [eng.add_request(p(60), max_new_tokens=4) for _ in range(3)]
    eng.step()
    assert all(r is not None for r in eng.slot_req) and not eng.queue
    assert set(eng.run()) == set(ids)
    _balanced(eng)
    eng = _engine(max_slots=3, num_blocks=20)    # 60 tokens: 8 blocks each
    ids = [eng.add_request(p(60), max_new_tokens=4) for _ in range(3)]
    eng.step()
    assert sum(r is not None for r in eng.slot_req) == 2 and len(eng.queue) == 1
    res = eng.run()
    assert set(res) == set(ids) and all(len(res[i]) == 4 for i in ids)
    _balanced(eng)


def test_preemption_gives_the_ring_up_with_the_full_kinds_blocks():
    """Two requests grow in decode until the full kind's pool runs dry:
    the newest is preempted, its ring counts as free again, it is
    admitted again and every token is delivered once."""
    eng = _engine(max_slots=2, num_blocks=11)
    rng = np.random.default_rng(6)
    ids = [eng.add_request(rng.integers(0, 256, size=n).tolist(),
                           max_new_tokens=40) for n in (12, 14)]
    seen = False
    while eng.has_work():
        eng.step()
        _balanced(eng)
        seen |= bool(eng.queue) and any(r.generated for r in eng.queue)
    assert seen                                    # someone was preempted
    assert [len(eng.results[i]) for i in ids] == [40, 40]
    acc = _balanced(eng)
    assert acc["backed"] == 0 and acc["window"]["backed"] == 0
