"""``flash_partial``'s tile (PR 43): a grid step takes a KV head's whole
query group, and computes a mask only where a mask cuts the tile. The
kernel, interpreted, against the plain softmax over the same mask; the
tile's classification and ``flash_tile_counts`` against a brute-force
count over the mask itself; the rule that chooses the tile's sizes. And
``latent_history_partial`` (PR 44): the same partial over a history of
LATENT rows, a head's keys and values made a key tile at a time inside
the kernel."""
import itertools

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import pallas_attention as fl
from paddle_tpu.kernels.pallas_attention import (combine_partials,
                                                 flash_partial,
                                                 flash_tile_counts,
                                                 flash_tiles,
                                                 latent_history_partial,
                                                 latent_history_tiles)

BQ, BKV = 16, 32            # unequal, so that rows and columns cannot swap
# a KV head a length: none, one key, a multiple of the key tile, one short
# of it, every key
LENGTHS = lambda T: np.asarray([0, 1, 2 * BKV, 2 * BKV - 1, T])


def _mask(S, T, kv_len, lo, causal):
    r, c = np.arange(S)[:, None], np.arange(T)[None, :]
    keep = np.broadcast_to(c < kv_len, (S, T)).copy()
    if causal:
        keep &= c <= r
    if lo is not None:
        keep &= c >= r + lo
    return keep


def _check(q, k, v, o, lse, kv_len, lo, causal, scale, atol=1e-4):
    """o and lse of every head against the plain softmax over its mask;
    rows with no key read -1e30 and ``combine_partials`` ignores them."""
    G, S, _ = q.shape
    Gk, T, _ = k.shape
    groups = G // Gk
    for g in range(G):
        gk = g // groups
        keep = _mask(S, T, kv_len[gk], None if lo is None else lo[gk],
                     causal)
        s = np.where(keep, q[g] @ k[gk].T * scale, -np.inf)
        has = keep.any(axis=1)
        top = np.where(has, s.max(-1), 0.0)
        p = np.exp(s - top[:, None])
        want = (p / np.maximum(p.sum(-1, keepdims=True), 1e-30)) @ v[gk]
        got, got_lse = np.asarray(o[g]), np.asarray(lse[g])
        assert np.abs(want - got)[has].max(initial=0.0) < atol, g
        want_lse = top + np.log(np.maximum(p.sum(-1), 1e-30))
        assert np.abs(want_lse - got_lse)[has].max(initial=0.0) < atol, g
        assert (got_lse[~has] <= -1e29).all(), g
    # a partial with no key for a row leaves the other partial as it is
    other = jnp.ones_like(o)
    both = combine_partials(o, lse, other, jnp.zeros_like(lse))
    none = np.asarray(lse) <= -1e29
    assert np.all(np.asarray(both)[none] == 1.0)


def _operands(seed, G, Gk, S, T, Dk, Dv=None):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((G, S, Dk)).astype(np.float32)
    k = rng.standard_normal((Gk, T, Dk)).astype(np.float32)
    v = None if Dv is None else rng.standard_normal(
        (Gk, T, Dv)).astype(np.float32)
    return q, k, v


@pytest.mark.parametrize("mode", ["neither", "causal", "banded", "both"])
@pytest.mark.parametrize("groups", [1, 4, 6, 8])
def test_the_stacked_tile_against_the_plain_softmax(groups, mode):
    """Five KV heads, each with a length of its own, under ``groups``
    query heads a KV head: a query group rides in one tile, and the band
    leaves some rows no key."""
    causal = mode in ("causal", "both")
    Gk, S, D = 5, 64, 128
    T = S if causal else 96
    q, k, v = _operands(groups, Gk * groups, Gk, S, T, D, D)
    kv_len = LENGTHS(T)
    lo = {"neither": None, "causal": None,
          # a window over keys that start 40 before the queries; a band
          # wider than everything; one that starts past a short length;
          # one below every row; one past every key (no row sees a key)
          "banded": np.asarray([40 - 24 + 1, -100, 40, -5, 300]),
          "both": np.full((Gk,), 1 - 24)}[mode]
    o, lse = flash_partial(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), scale=0.1,
        causal=causal, kv_len=jnp.asarray(kv_len),
        band_lo=None if lo is None else jnp.asarray(lo),
        block_q=BQ, block_kv=BKV)
    _check(q, k, v, o, lse, kv_len, lo, causal, 0.1)


# a latent family at the tests' size: nope 32 and rope 16 in a query row of
# 128, rank 128 and the roped key in a pool row of 256, values of 32
DN, DR, R, DV, DQ, W = 32, 16, 128, 32, 128, 256


def _expand(rows, w_uk, w_uv):
    """Latent rows [T, W] as every head's keys [H, T, DN + DR] and values
    [H, T, DV]: the published expansion, in float32."""
    lat, k_r = rows[:, :R], rows[:, R:R + DR]
    k_nope = np.einsum("tc,hdc->htd", lat, w_uk)
    return (np.concatenate([k_nope, np.broadcast_to(
        k_r, k_nope.shape[:2] + (DR,))], -1),
        np.einsum("tc,hcd->htd", lat, w_uv))


def _latent_operands(seed, H, S, T):
    """q [H, S, DQ] and the history [1, T, W], zeros past the rope as the
    model pads them, and a head's ``w_uk`` [DN, R] and ``w_uv`` [R, DV]."""
    rng = np.random.default_rng(seed)
    f = lambda *shape: rng.standard_normal(shape).astype(np.float32)
    q, hist = np.zeros((H, S, DQ), np.float32), np.zeros((1, T, W), np.float32)
    q[..., :DN + DR], hist[..., :R + DR] = f(H, S, DN + DR), f(1, T, R + DR)
    return q, hist, f(H, DN, R) / R ** 0.5, f(H, R, DV) / R ** 0.5


@pytest.mark.parametrize("groups,D,heads", [(1, 256, 1), (4, 256, 4),
                                            (4, 64, None), (8, 64, None)],
                         ids=["latent-rows", "latent-rows-grouped",
                              "head-dim-64-of-4", "head-dim-64-of-8"])
def test_the_stacked_tile_at_other_widths(groups, D, heads):
    """A history of latent rows, expanded a head inside the kernel, under
    two lengths; and heads of 64 as they are, causal and then over a
    history with a length."""
    Gk, S, T = 2, 32, 64
    kv_len = np.asarray([T - 1, 33])
    if heads:
        q, hist, w_uk, w_uv = _latent_operands(7, heads, S, T)
        k, v = _expand(hist[0], w_uk, w_uv)
        for n in kv_len:
            o, lse = latent_history_partial(
                *map(jnp.asarray, (q, hist, w_uk, w_uv)), scale=0.07,
                kv_len=jnp.asarray([n]), block_q=BQ, block_kv=BKV)
            _check(q[..., :DN + DR], k, v, o, lse, np.full((heads,), n),
                   None, False, 0.07)
        return
    q, k, v = _operands(7, Gk * groups, Gk, S, T, D, D)
    kw = dict(scale=0.07, block_q=BQ, block_kv=BKV)
    o, lse = flash_partial(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                           kv_len=jnp.asarray(kv_len), **kw)
    _check(q, k, v, o, lse, kv_len, None, False, 0.07)
    o, lse = flash_partial(jnp.asarray(q), jnp.asarray(k[:, :S]),
                           jnp.asarray(v[:, :S]), causal=True, **kw)
    _check(q, k[:, :S], v[:, :S], o, lse, np.full((Gk,), S), None, True,
           0.07)


@pytest.mark.parametrize("heads", [4, 32])
@pytest.mark.parametrize("n_hist", [0, BKV + 1, 2 * BKV, 3 * BKV],
                         ids=["none", "one-past-a-tile", "whole-tiles",
                              "the-table-s-width"])
def test_a_latent_piece_against_the_plain_softmax(n_hist, heads):
    """A piece as the latent family runs it: the chunk's own keys expanded
    before the call (``flash_partial``, causal), the history's inside
    ``latent_history_partial``, one softmax joining them: against the plain
    float32 softmax over [history ; chunk], and against the ABSORBED form
    of the history (queries carried into the latent's coordinates, the rows
    as keys and values, ``W_UV`` after) on the same operands."""
    S, T, scale = 32, 3 * BKV, 0.11
    q, hist, w_uk, w_uv = _latent_operands(n_hist + heads, heads, S, T)
    own = _latent_operands(1, heads, S, S)[1][0]    # the chunk's own rows
    (k_h, v_h), (k_c, v_c) = (_expand(x, w_uk, w_uv) for x in (hist[0], own))
    pad = lambda x: np.concatenate(
        [x, np.zeros(x.shape[:-1] + (DQ - DN - DR,), np.float32)], -1)
    o_c, lse_c = flash_partial(jnp.asarray(q), jnp.asarray(pad(k_c)),
                               jnp.asarray(v_c), scale=scale, causal=True,
                               block_q=BQ, block_kv=BQ)
    o_h, lse_h = latent_history_partial(
        *map(jnp.asarray, (q, hist, w_uk, w_uv)), scale=scale,
        kv_len=jnp.asarray([n_hist]), block_q=BQ, block_kv=BKV)
    got = np.asarray(combine_partials(o_c, lse_c, o_h, lse_h))
    # the plain softmax over the history's real keys and then the chunk's
    qq = q[..., :DN + DR]
    keep = np.concatenate([np.broadcast_to(np.arange(T) < n_hist, (S, T)),
                           np.tril(np.ones((S, S), bool))], 1)
    s = np.where(keep, np.einsum("hsd,htd->hst", qq, np.concatenate(
        [k_h, k_c], 1)) * scale, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("hst,htd->hsd", p / p.sum(-1, keepdims=True),
                     np.concatenate([v_h, v_c], 1))
    np.testing.assert_allclose(got, want, atol=2e-5)
    # the absorbed history: scores in the latent's coordinates
    if n_hist:
        q_abs = np.concatenate([np.einsum("hsd,hdc->hsc", q[..., :DN], w_uk),
                                q[..., DN:DN + DR]], -1)
        s_a = np.einsum("hsw,tw->hst", q_abs, hist[0, :n_hist, :R + DR]) \
            * scale
        p_a = np.exp(s_a - s_a.max(-1, keepdims=True))
        o_a = np.einsum("hsc,hcd->hsd", np.einsum(
            "hst,tc->hsc", p_a / p_a.sum(-1, keepdims=True),
            hist[0, :n_hist, :R]), w_uv)
        np.testing.assert_allclose(np.asarray(o_h), o_a, atol=2e-5)
        np.testing.assert_allclose(
            np.asarray(lse_h), s_a.max(-1) + np.log(p_a.sum(-1)), atol=2e-5)
    else:
        assert (np.asarray(lse_h) <= -1e29).all()


def test_the_tile_the_rule_chooses_gives_what_any_other_gives():
    """No block named: the sizes follow from the shapes, and the result
    is the one the tests' small tiles give."""
    q, k, v = _operands(9, 8, 2, 256, 384, 128, 128)
    n = jnp.asarray([300, 129])
    args = (jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    o1, l1 = flash_partial(*args, scale=0.1, kv_len=n)
    o2, l2 = flash_partial(*args, scale=0.1, kv_len=n, block_q=BQ,
                           block_kv=BKV)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-6)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=2e-6)


CASES = [(S, T, n, lo, causal, bq, bkv)
         for (S, T), (bq, bkv) in itertools.product(
             [(64, 64), (64, 160), (128, 96)], [(16, 32), (32, 16), (64, 32)])
         for causal in (False, True) if not causal or S == T
         for n in (0, 1, 32, 31, 47, T)
         for lo in (None, -200, -23, 0, 17, 300)]


def test_a_tile_s_kind_and_the_counts_against_the_mask_itself():
    """``whole`` (the unmasked branch) exactly where the mask cuts nothing
    of the tile; ``run`` wherever the mask leaves anything of it (a tile
    that runs with nothing left is masked to nothing: correct, only
    wasted); the counts are those of the tiles."""
    seen = set()
    for S, T, n, lo, causal, bq, bkv in CASES:
        keep = _mask(S, T, n, lo, causal)
        interior = edge = 0
        for q0, c0 in itertools.product(range(0, S, bq), range(0, T, bkv)):
            tile = keep[q0:q0 + bq, c0:c0 + bkv]
            run, whole = fl._tile_kind(q0, c0, bq, bkv, n, lo, causal)
            assert bool(whole) == tile.all(), (S, T, n, lo, causal, q0, c0)
            assert bool(run) or not tile.any(), (S, T, n, lo, causal, q0, c0)
            if lo is None:
                assert bool(run) == tile.any()
            interior += bool(run and whole)
            edge += bool(run and not whole)
        tiles = (S // bq) * (T // bkv)
        got = flash_tile_counts(S, T, n, lo, causal, bq=bq, bkv=bkv)
        assert got == (interior, edge, tiles - interior - edge)
        seen.add((interior > 0, edge > 0, tiles > interior + edge))
    assert len(seen) >= 6        # every kind met, alone and together


def test_the_counts_at_the_published_shapes():
    """What the issue expects of the unmasked branch: a full history of 8k
    in a table of 34,816 keys, a window's 4,608 gathered keys of which
    4,095 are real, a 1,024-token chunk."""
    assert flash_tile_counts(1024, 34816, 8192, bq=256, bkv=512) == (
        4 * 16, 0, 4 * 52)
    assert flash_tile_counts(1024, 34816, 8000, bq=256, bkv=512) == (
        4 * 15, 4, 4 * 52)
    assert flash_tile_counts(1024, 4608, 4095, 0, bq=256, bkv=512) == (
        22, 8, 6)
    assert flash_tile_counts(1024, 1024, 1024, None, True, bq=512,
                             bkv=512) == (1, 2, 1)


@pytest.mark.parametrize("groups,S,T,Dk,Dv", [
    (6, 1024, 1024, 128, 128), (6, 1024, 4608, 128, 128),
    (6, 1024, 34816, 128, 128), (8, 1024, 33792, 128, 128),
    (8, 1024, 1536, 128, 128), (4, 1024, 9216, 64, 64),
    (1, 1024, 1024, 256, 128), (6, 1024, 2048, 128, 128),
    (2, 32, 48, 128, 128), (1, 64, 96, 64, 64)])
def test_the_tile_follows_from_the_shapes(groups, S, T, Dk, Dv):
    """The rule's tile divides both sides, is a multiple of the MXU's 128
    wherever the side allows one, and fits the kernel's fast memory by the
    rule's own reckoning (the described-topology compiles hold it to
    Mosaic's at the published shapes)."""
    bq, bkv = flash_tiles(groups, S, T, Dk, Dv)
    assert S % bq == 0 and T % bkv == 0
    assert bkv % 128 == 0 or T % 128
    assert bq % 16 == 0
    if S % 128 == 0 and T % 128 == 0:
        assert fl._flash_footprint(groups, bq, bkv, Dk, Dv, 2) \
            <= fl._FLASH_VMEM


def test_a_latent_history_s_tile_follows_from_the_shapes():
    """A head's whole piece by the widest key tile of at most 512 that
    divides the table and lines up with the piece; sides that no 128
    divides take ``_pick_block``'s answer."""
    assert latent_history_tiles(1024, 18432) == (1024, 512)
    assert latent_history_tiles(1024, 25600) == (1024, 512)
    assert latent_history_tiles(2048, 18432) == (1024, 512)
    assert latent_history_tiles(256, 1280) == (256, 256)
    assert latent_history_tiles(64, 4096) == (64, 512)
    assert latent_history_tiles(32, 96) == (32, 96)


# -- the counter ---------------------------------------------------------------
def test_the_engine_counts_a_piece_s_tiles_from_what_it_holds(monkeypatch):
    """A tiny Mellum2 engine (a window of 32 over blocks of 8, pieces of
    16): ``serving_flash_tiles_total`` moves by what the model says of
    each piece, the model's steps a kernel are the grids of the calls its
    programs trace (names, KV heads, the gathered widths), and a window
    layer's length and bound are the ledger's own."""
    import paddle_tpu.observability as obs
    import test_mellum as t
    from test_lfm2_moe import _counter
    from paddle_tpu.models import flat_kv_attention as fka
    from paddle_tpu.models.window_kv import history_pad

    calls, pieces = set(), []
    real = fka.flash_partial

    def traced(q, k, v=None, **kw):
        calls.add((kw["name"], k.shape[0], q.shape[1], k.shape[1]))
        return real(q, k, v, **kw)

    monkeypatch.setattr(fka, "flash_partial", traced)
    obs.enable()
    try:
        before = obs.snapshot()
        eng = t._engine(max_slots=1, prefill_chunk=16)
        said = eng.model.piece_flash_tiles
        monkeypatch.setattr(
            eng.model, "piece_flash_tiles", lambda *a: pieces.append(
                (a, said(*a))) or pieces[-1][1], raising=False)
        eng.add_request(t._prompts([72])[0], max_new_tokens=2)
        eng.run()
        after = obs.snapshot()
    finally:
        obs.disable()
    c = eng.model.config
    assert [a[:2] for a, _ in pieces] == [(16, h) for h in (0, 16, 32, 48, 64)]
    kinds = ("interior", "edge", "skipped")
    for i, kind in enumerate(kinds):
        for kernel in ("mellum_prefill_chunk", "mellum_history_full",
                       "mellum_history_window"):
            moved = (_counter(after, "serving_flash_tiles_total",
                              kernel=kernel, kind=kind)
                     - _counter(before, "serving_flash_tiles_total",
                                kernel=kernel, kind=kind))
            assert moved == sum(said.get(kernel, (0, 0, 0))[i]
                                for _a, said in pieces), (kernel, kind)
    # the grids of the calls the programs traced
    layers = {"mellum_prefill_chunk": 4, "mellum_history_full": 1,
              "mellum_history_window": 3}
    shapes = {name: (gk, S, T) for name, gk, S, T in calls}
    assert len(shapes) == len(calls) == 3
    assert shapes["mellum_history_window"][2] == history_pad(
        eng.win.width * t.BS)
    (S, hist, pnbk, bs), said = pieces[-1]
    for name, (gk, S_, T) in shapes.items():
        bq, bkv = flash_tiles(c.num_heads // c.num_kv_heads, S_, T,
                              c.head_dim, c.head_dim)
        assert sum(said[name]) == layers[name] * gk * (S_ // bq) * (T // bkv)
    # the window layers' length and bound: the ledger's history of the slot
    for (S, hist, pnbk, bs), said in pieces[1:]:
        tbl, start = eng.win.history(0, hist)
        n_win = hist - start
        want = fl.flash_call_tiles(
            c.num_heads // c.num_kv_heads, S, history_pad(len(tbl) * bs),
            c.head_dim, c.head_dim, kv_len=n_win, band_lo=n_win - t.W + 1)
        assert said["mellum_history_window"] == tuple(
            3 * c.num_kv_heads * n for n in want)
    # interior + edge + skipped of a history is its whole grid, whatever
    # the length; the first piece has no history to count
    assert set(pieces[0][1]) == {"mellum_prefill_chunk"}


@pytest.mark.parametrize("family", ["lfm2_moe", "deepseek_v2", "afmoe",
                                    "ling_hybrid"])
def test_every_chunked_family_says_what_its_pieces_tile(family):
    """The other chunked families' counts: every kernel a piece runs, by
    the name a trace shows, with the whole grid accounted for."""
    import importlib

    t = importlib.import_module({
        "lfm2_moe": "test_lfm2_moe", "deepseek_v2": "test_deepseek_v2_served",
        "afmoe": "test_afmoe", "ling_hybrid": "test_ling_hybrid"}[family])
    model = t.FAM.program_config(t.MODEL, max_seq_len=256,
                                 dtype=jnp.float32).served_model()
    first = model.piece_flash_tiles(64, 0, 0, 8)
    later = model.piece_flash_tiles(64, 100, 512, 8)
    names = {"lfm2_moe": ("lfm2_prefill_chunk", "lfm2_prefill_history"),
             "deepseek_v2": ("mla_prefill_chunk", "mla_prefill_history"),
             "ling_hybrid": ("mla_prefill_chunk", "mla_prefill_history"),
             "afmoe": ("afmoe_prefill_chunk", "afmoe_history_full",
                       "afmoe_history_window")}[family]
    assert set(first) == {names[0]} and set(later) == set(names)
    assert first[names[0]] == later[names[0]]
    for counts in later.values():
        assert len(counts) == 3 and sum(counts) > 0
        assert all(n >= 0 and n == int(n) for n in counts)
    # a history 100 tokens long in a table of 4,096: most of it is skipped
    assert later[names[1]][2] > 0
    if names[1] == "mla_prefill_history":
        # the latent history's own grid: a head a step by key tiles, in
        # every latent layer, of which the 100 real keys fill one tile
        mla = getattr(model, "_mla", model)
        bq, bkv = latent_history_tiles(64, 4096)
        heads = mla.num_layers * mla.config.num_heads
        assert later[names[1]] == (0, heads, heads * (4096 // bkv - 1))
        assert model.piece_flash_tiles(64, 2 * bkv, 512, 8)[names[1]] == (
            2 * heads, 0, heads * (4096 // bkv - 2))
