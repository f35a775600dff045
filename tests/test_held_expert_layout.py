"""The row layout of the held experts' pairs (``kernels/moe_dispatch.
held_expert_ffn``), float32 on the CPU, where the grouped matmul is
``ragged_dot`` and the row tile a nominal 8:

- a prefill call (over ``_HELD_SMALL_ROWS`` pairs a pass) lays every held
  expert's rows out from a tile boundary and gives each token its pairs'
  rows back by a gather; whatever the routing it equals the dense sum
  ``sum_e gate_e * Expert_e(x)``, and the fifth count is the row tiles
  reckoned by hand;
- a decode call keeps its pairs packed, exactly M rows;
- the engine carries the fifth count to ``serving_moe_row_tiles_total`` and,
  as ``expert_tiles``, onto the span of the program that produced it.
"""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

md = importlib.import_module("paddle_tpu.kernels.moe_dispatch")
F32 = jnp.float32
H, F, E, TILE = 32, 16, 8, 8


def _weights(seed=0, experts=E):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(experts, H, 2 * F)) * 0.2, F32),
            jnp.asarray(rng.normal(size=(experts, F, H)) * 0.2, F32))


def _dense(x, gates, idx, valid, e_gu, e_down, first):
    """sum_e gate_e * Expert_e(x) over the held experts, every expert run
    over every token."""
    y = jnp.zeros_like(x)
    for e in range(e_gu.shape[0]):
        gu = x @ e_gu[e]
        out = (jax.nn.silu(gu[:, :F]) * gu[:, F:]) @ e_down[e]
        w = jnp.sum(jnp.where((idx == first + e) & valid[:, None], gates,
                              0.0), axis=1)
        y = y + out * w[:, None]
    return y


def _tiles_by_hand(idx, valid, first, experts, M, aligned):
    """Row tiles of TILE rows the grouped matmul visits: the sorted held
    pairs cut into passes of M; aligned, a pass's cut of a group takes
    ceil(rows / TILE) tiles of its own; packed, a group visits every tile
    it touches."""
    local = np.asarray(idx) - first
    held = (local >= 0) & (local < experts) & np.asarray(valid)[:, None]
    gs = np.bincount(local[held], minlength=experts)
    ends = np.cumsum(gs)
    tiles = 0
    for lo in range(0, max(int(ends[-1]), 1), M):
        cut = np.clip(ends, lo, lo + M)
        g = cut - np.concatenate([[min(lo, ends[-1])], cut[:-1]])
        if aligned:
            tiles += int(np.sum(-(-g // TILE)))
        else:
            end = np.cumsum(g)
            tiles += int(sum(-(-b // TILE) - a // TILE
                             for a, b in zip(end - g, end) if b > a))
    return tiles


def _even(T, k):
    return (np.arange(T)[:, None] + np.arange(k)[None, :]) % E


def _case(name):
    """(idx [T, k], valid [T], first, held experts, tiles where the case
    gives them outright)"""
    T, k = 512, 4
    rng = np.random.default_rng(11)
    valid = np.ones((T,), bool)
    if name == "even":
        # 256 rows an expert: 32 tiles each
        return _even(T, k), valid, 0, E, 8 * 32
    if name == "one-expert":
        return np.full((T, k), 3), valid, 0, E, 2048 // TILE
    if name == "empty-experts":
        idx = np.asarray([0, 2, 5, 7])[rng.integers(0, 4, size=(T, k))]
        return idx, valid, 0, E, None
    if name == "exactly-one-tile":
        # expert 1 has exactly TILE rows, the rest go to 4..7
        idx = rng.integers(4, 8, size=(T, k))
        idx[:TILE, 0] = 1
        return idx, valid, 0, E, None
    if name == "pad-rows":
        return rng.integers(0, E, size=(T, k)), np.arange(T) < 300, 0, E, None
    if name == "foreign-pairs":
        # a router over 32 experts, 8..15 held: three quarters go elsewhere
        return rng.integers(0, 32, size=(T, k)), valid, 8, E, None
    if name == "two-passes":
        # 9,216 pairs: four passes of 2,304 sorted pairs
        T = 2304
        idx = rng.integers(0, E, size=(T, k))
        idx[:, 0] = np.where(np.arange(T) % 3 == 0, 2, idx[:, 0])
        return idx, np.arange(T) < 2200, 0, E, None
    if name == "two-passes-foreign":
        # a share of one eighth in a wide wave: one pass in four runs
        T = 2304
        return rng.integers(0, 64, size=(T, k)), np.ones((T,), bool), 16, E, \
            None
    raise KeyError(name)


@pytest.mark.parametrize("name", [
    "even", "one-expert", "empty-experts", "exactly-one-tile", "pad-rows",
    "foreign-pairs", "two-passes", "two-passes-foreign"])
def test_the_aligned_layout_equals_the_dense_sum(name):
    idx, valid, first, experts, tiles = _case(name)
    T, k = idx.shape
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(T, H)), F32)
    gates = jnp.asarray(rng.uniform(0.1, 1.0, size=(T, k)), F32)
    e_gu, e_down = _weights(experts=experts)
    idx_j, valid_j = jnp.asarray(idx, jnp.int32), jnp.asarray(valid)
    M = T * k if T <= md._HELD_PASS_TOKENS else T
    assert M > md._HELD_SMALL_ROWS                     # the prefill regime
    with jax.default_matmul_precision("highest"):
        y, counts = jax.jit(md.held_expert_ffn, static_argnums=6)(
            x, gates, idx_j, valid_j, e_gu, e_down, first)
        want = _dense(x, gates, idx_j, valid_j, e_gu, e_down, first)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5)
    assert float(jnp.abs(y[~valid]).max(initial=0.0)) == 0.0
    local = idx - first
    held = (local >= 0) & (local < experts) & valid[:, None]
    gs = np.bincount(local[held], minlength=experts)
    assert [float(c) for c in counts[:4]] == [
        valid.sum() * k, held.sum(), (gs > 0).sum(), gs.max() * experts]
    by_hand = _tiles_by_hand(idx, valid, first, experts, M, aligned=True)
    assert float(counts[4]) == by_hand
    if tiles is not None:
        assert by_hand == tiles
    if name == "exactly-one-tile":
        # a full tile is one tile: no row of padding follows it
        assert by_hand == 1 + sum(-(-g // TILE) for g in gs[4:])


def _operations(fn, *args):
    """(primitive, shape of its first result) of every equation of ``fn``,
    the bodies of its conds and calls included."""
    seen = set()

    def walk(jaxpr):
        for eq in jaxpr.eqns:
            if eq.outvars:
                seen.add((eq.primitive.name,
                          tuple(eq.outvars[0].aval.shape)))
            for sub in jax.core.jaxprs_in_params(eq.params):
                walk(sub)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return seen


def _gathered_rows(fn, *args, width=H):
    """Leading dimensions of every gather of rows of ``width`` in ``fn``."""
    return {shape[0] for name, shape in _operations(fn, *args)
            if name == "gather" and len(shape) == 2 and shape[1] == width}


def _chip_specs(T, k, experts, h, f):
    spec = jax.ShapeDtypeStruct
    return (spec((T, h), jnp.bfloat16), spec((T, k), F32),
            spec((T, k), jnp.int32), spec((T,), jnp.bool_),
            spec((experts, h, 2 * f), jnp.bfloat16),
            spec((experts, f, h), jnp.bfloat16))


@pytest.mark.parametrize("T,k,experts,first,chip", [
    (64, 4, 8, 0, (32, 2048, 1792)), (24, 6, 8, 16, (20, 5120, 1536))],
    ids=["64x4-all-held", "24x6-a-share"])
def test_a_decode_call_keeps_its_pairs_packed(monkeypatch, T, k, experts,
                                              first, chip):
    """The regime follows from the pairs of the call: a decode step gathers
    exactly its M rows (256 at the chip's row tile for both served
    families), no E x tile rows more, and adds them back by rows; the
    fifth count is the packed layout's (a group visits every tile it
    touches)."""
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(T, H)), F32)
    gates = jnp.asarray(rng.uniform(0.1, 1.0, size=(T, k)), F32)
    idx = rng.integers(0, 64 if first else experts, size=(T, k))
    valid = np.arange(T) < T - 3
    e_gu, e_down = _weights(experts=experts)
    fn = lambda *a: md.held_expert_ffn(*a, first)
    args = (x, gates, jnp.asarray(idx, jnp.int32), jnp.asarray(valid), e_gu,
            e_down)
    M = -(-T * k // TILE) * TILE
    assert _gathered_rows(fn, *args) == {M}
    with jax.default_matmul_precision("highest"):
        y, counts = fn(*args)
        want = _dense(x, gates, args[2], args[3], e_gu, e_down, first)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5)
    assert float(counts[4]) == _tiles_by_hand(idx, valid, first, experts, M,
                                              aligned=False)
    # at the chip's tile of 128 and the family's widths (the kernel traced,
    # not run): 256 rows
    monkeypatch.setattr(md, "_mosaic", lambda: True)
    held, h, f = chip
    assert _gathered_rows(lambda *a: md.held_expert_ffn(*a, first),
                          *_chip_specs(T, k, held, h, f), width=h) == {256}


def test_a_piece_lays_its_experts_out_on_tile_boundaries(monkeypatch):
    """At the chip's tile a piece of 1,024 tokens x 4 gathers 4,096 +
    32 x 128 rows of x for the kernel, and each token takes its 4 rows of
    the result back (a gather of [1024, 4] rows, no scatter-add)."""
    monkeypatch.setattr(md, "_mosaic", lambda: True)
    seen = _operations(lambda *a: md.held_expert_ffn(*a, 0),
                       *_chip_specs(1024, 4, 32, 2048, 1792))
    assert ("gather", (8192, 2048)) in seen
    assert ("gather", (1024, 4, 2048)) in seen
    assert not any(name.startswith("scatter") and shape == (1024, 2048)
                   for name, shape in seen)


@pytest.mark.parametrize("T,k,experts,h,f", [
    (1056, 8, 64, 2304, 896), (1088, 4, 32, 2048, 1792),
    (1048, 6, 20, 5120, 1536)],
    ids=["mellum2-1024+32", "lfm2-1024+64", "deepseek-v2-1024+24"])
def test_a_piece_with_the_decode_rows_is_one_aligned_pass(monkeypatch, T, k,
                                                          experts, h, f):
    """A piece of 1,024 tokens with the engine's slots riding in its
    program: the one-pass bound is on the call's TOKENS, so Mellum2's 1,056
    x 8 = 8,448 pairs (over the old bound of 8,192 pairs, which stood
    exactly at its piece) still gather at once, on tile boundaries, under
    no ``cond``; and each token takes its k rows of the result back."""
    monkeypatch.setattr(md, "_mosaic", lambda: True)
    seen = _operations(lambda *a: md.held_expert_ffn(*a, 0),
                       *_chip_specs(T, k, experts, h, f))
    rows = -(-T * k // 128) * 128 + experts * 128
    assert {shape[0] for name, shape in seen if name == "gather"
            and len(shape) == 2 and shape[1] == h} == {rows}
    assert ("gather", (T, k, h)) in seen
    assert not any(name == "cond" for name, _ in seen)
    assert not any(name.startswith("scatter") and shape == (T, h)
                   for name, shape in seen)


def test_a_decode_step_of_sixty_four_experts_stays_packed(monkeypatch):
    """Mellum2's decode call, 32 slots x 8: 256 packed rows, as LFM2's 64
    x 4 and DeepSeek-V2's 24 x 6 (above)."""
    monkeypatch.setattr(md, "_mosaic", lambda: True)
    assert _gathered_rows(lambda *a: md.held_expert_ffn(*a, 0),
                          *_chip_specs(32, 8, 64, 2304, 896),
                          width=2304) == {256}


def test_the_engine_carries_the_tiles_to_a_counter_and_the_spans():
    """A tiny LFM2 engine whose one prompt is a prefill call in the aligned
    regime (600 tokens x top-2 in a bucket of 640: 1,280 pairs) and whose
    decode steps are packed: the served tokens agree with the reference,
    ``expert_tiles`` stands beside ``expert_rows`` on both spans and the
    counter moves by their sum."""
    import paddle_tpu.observability as obs
    import test_lfm2_moe as t

    cfg = t.FAM.program_config(t.MODEL, max_seq_len=640, dtype=F32)
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, 256, size=600).tolist()
    obs.enable()
    try:
        obs.get_tracer().clear()     # an earlier file's spans in this worker
        before = obs.snapshot()
        from paddle_tpu.serving import LLMEngine
        eng = LLMEngine(t._params(), cfg, max_slots=2, block_size=8,
                        max_model_len=640, prompt_buckets=[640], seed=0)
        rid = eng.add_request(prompt, max_new_tokens=6)
        served = eng.run()[rid]
        after = obs.snapshot()
        spans = [s for s in obs.get_tracer().spans()
                 if s.name in ("serving.prefill", "serving.decode")
                 and "expert_tiles" in s.attrs]
    finally:
        obs.disable()
    assert t._reference_gaps([prompt], [served]).max() <= t.GAP_LIMIT
    layers = 2                                  # expert layers of the tiny
    pre = [s for s in spans if s.name == "serving.prefill"]
    dec = [s for s in spans if s.name == "serving.decode"]
    assert len(pre) == 1 and dec
    rows, tiles = pre[0].attrs["expert_rows"], pre[0].attrs["expert_tiles"]
    assert rows == 600 * 2 * layers
    # aligned: under one tile of padding an expert a layer, none shared
    assert rows / TILE <= tiles <= rows / TILE + 8 * layers
    for s in dec:                               # one token: 2 pairs a layer
        assert s.attrs["expert_rows"] == 2 * layers
        assert 1 * layers <= s.attrs["expert_tiles"] <= 2 * layers
    moved = (t._counter(after, "serving_moe_row_tiles_total")
             - t._counter(before, "serving_moe_row_tiles_total"))
    assert moved == sum(s.attrs["expert_tiles"] for s in spans)
