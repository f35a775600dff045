"""Head dim 64 on the walk and on flash (interpret mode on the CPU): a
token's heads side by side in one pool row, values then keys, against a
dense gather, flash at heads of 64 as they are, and the head-dim-128
kernels' results unchanged, bit for bit, from the parent's (``tests/data/kernels_hd128_parent_pr31.npz``, recorded by running
the inputs below through commit a4f03f8's kernels)."""
import importlib
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pa = importlib.import_module("paddle_tpu.kernels.paged_attention")
fl = importlib.import_module("paddle_tpu.kernels.pallas_attention")
F32 = jnp.float32
DATA = os.path.join(os.path.dirname(__file__), "data",
                    "kernels_hd128_parent_pr31.npz")


def _r(i, shape):
    return jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(64), i),
                             shape, F32)


def _dense(q, k, v, n, scale, causal_from=None):
    """softmax(q . k^T) v over the first ``n`` keys of each KV head, GQA:
    q [Hq, S, D], k/v [Hkv, T, D]."""
    Hq, Hkv = q.shape[0], k.shape[0]
    k, v = (jnp.repeat(a, Hq // Hkv, axis=0) for a in (k, v))
    s = jnp.einsum("hsd,htd->hst", q, k) * scale
    keep = jnp.arange(k.shape[1])[None, None, :] < n
    if causal_from is not None:
        keep &= (jnp.arange(q.shape[1])[None, :, None] + causal_from
                 >= jnp.arange(k.shape[1])[None, None, :])
    return jnp.einsum("hst,htd->hsd",
                      jax.nn.softmax(jnp.where(keep, s, -1e30), -1), v)


def test_pack_queries_scores_are_the_head_s_own():
    q, k = _r(0, (5, 8, 64)), _r(1, (5, 4, 64))      # 8 query, 4 KV heads
    qp = pa.pack_queries(q, 4)
    assert qp.shape == (5, 8, 256)
    got = jnp.einsum("nhc,nc->nh", qp, k.reshape(5, 256))
    want = jnp.einsum("nhd,nhd->nh", q, jnp.repeat(k, 2, axis=1))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    o = _r(2, (5, 8, 256))
    own = np.asarray(pa.unpack_outputs(o, 4))
    for h in range(8):
        np.testing.assert_array_equal(
            own[:, h], np.asarray(o)[:, h, 64 * (h // 2):64 * (h // 2) + 64])


@pytest.mark.parametrize("heads", [(8, 2), (32, 8), (4, 4)],
                         ids=["8q-2kv", "32q-8kv", "no-groups"])
def test_the_flat_walk_against_a_dense_gather(heads):
    """All of a token's heads of 64 in one row, values then keys: the
    walk's partials against softmax over a dense gather."""
    Hq, Hkv = heads
    N, D, NB, BS, MB = 3, 64, 12, 8, 4
    q = _r(3, (N, Hq, D))
    k, v = _r(4, (2, NB, BS, Hkv, D)), _r(5, (2, NB, BS, Hkv, D))
    tbl = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [6, 7, 8, 9]], jnp.int32)
    lens = jnp.asarray([19, 9, 32], jnp.int32)
    flat = lambda x: x.reshape(2, NB, BS, Hkv * D)
    acc, m, l = pa.flat_decode_partial(
        q, jnp.concatenate([flat(v), flat(k)], -1), tbl, lens, n_kv=Hkv,
        layer=1)
    assert acc.shape == (N, Hkv, Hq // Hkv, D)
    got = (acc / l[..., None]).reshape(N, Hq, D)
    for n in range(N):
        kd = k[1][tbl[n]].reshape(MB * BS, Hkv, D).swapaxes(0, 1)
        vd = v[1][tbl[n]].reshape(MB * BS, Hkv, D).swapaxes(0, 1)
        want = _dense(q[n][:, None], kd, vd, lens[n], 1 / math.sqrt(D))[:, 0]
        np.testing.assert_allclose(np.asarray(got[n]), np.asarray(want),
                                   atol=2e-6)


def test_a_slot_of_length_zero_gives_the_combine_s_identity():
    q = _r(6, (2, 8, 64))
    pool = _r(7, (1, 4, 8, 256))
    acc, m, l = pa.flat_decode_partial(
        q, pool, jnp.zeros((2, 2), jnp.int32),
        jnp.asarray([0, 0], jnp.int32), n_kv=2)
    assert float(jnp.abs(acc).max()) == 0 and float(l.max()) == 0
    assert float(m.max()) == float(np.float32(-1e30))


def test_flash_at_head_dim_64_against_dense():
    """A piece's own tokens, causal, and a history with a runtime length,
    joined by one softmax, at heads of 64 as they are."""
    Hq, Hkv, S, T, D = 8, 2, 32, 48, 64
    q, k, v = _r(8, (Hq, S, D)), _r(9, (Hkv, S, D)), _r(10, (Hkv, S, D))
    hk, hv = _r(11, (Hkv, T, D)), _r(12, (Hkv, T, D))
    n_hist, scale = 29, 1 / math.sqrt(D)
    o1, l1 = fl.flash_partial(q, k, v, scale=scale, causal=True)
    o2, l2 = fl.flash_partial(q, hk, hv, scale=scale, kv_len=jnp.asarray(
        [n_hist] * Hkv, jnp.int32))
    got = fl.combine_partials(o1, l1, o2, l2)
    # dense: keys = [history[:n_hist] ; piece], causal inside the piece
    kk = jnp.concatenate([hk[:, :n_hist], k], 1)
    vv = jnp.concatenate([hv[:, :n_hist], v], 1)
    want = _dense(q, kk, vv, n_hist + S, scale, causal_from=n_hist)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-6)


def test_the_latent_walk_the_flat_walk_runs_through():
    """DeepSeek-V2's walk over one pool of latent rows, which the flat
    walk calls as it is, gives what a dense gather gives (its own tests
    hold it to the reference)."""
    N, Hq, W, vc = 2, 4, 256, 128
    q, pool = _r(13, (N, Hq, W)), _r(14, (1, 6, 8, W))
    tbl = jnp.asarray([[1, 2, 3], [4, 5, 0]], jnp.int32)
    lens = jnp.asarray([20, 11], jnp.int32)
    acc, m, l = pa.latent_decode_partial(q, pool, tbl, lens, v_cols=vc,
                                         sm_scale=0.07)
    for n in range(N):
        rows = pool[0][tbl[n]].reshape(-1, W)[:lens[n]]
        p = jax.nn.softmax((q[n] @ rows.T) * 0.07, -1)
        np.testing.assert_allclose(np.asarray(acc[n] / l[n][:, None]),
                                   np.asarray(p @ rows[:, :vc]), atol=2e-6)


def _hd128_inputs():
    k = jax.random.PRNGKey(128)
    r = lambda i, shape: jax.random.normal(jax.random.fold_in(k, i), shape,
                                           F32)
    N, Hq, Hkv, D, NB, BS = 3, 8, 2, 128, 9, 8
    tbl = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [6, 7, 8, 0]], jnp.int32)
    lens = jnp.asarray([19, 9, 24], jnp.int32)
    walk = (r(0, (N, Hq, D)), r(1, (2, NB, BS, Hkv, D)),
            r(2, (2, NB, BS, Hkv, D)), tbl, lens)
    flash = (r(3, (4, 32, D)), r(4, (2, 48, D)), r(5, (2, 48, D)),
             jnp.asarray([40, 17], jnp.int32))
    return walk, flash


@pytest.mark.parametrize("kernel", ["walk", "flash"])
def test_head_dim_128_results_are_the_parent_s_bit_for_bit(kernel):
    want = np.load(DATA)
    walk, flash = _hd128_inputs()
    if kernel == "walk":
        acc, m, l = pa.ragged_decode_partial(*walk, layer=1)
        got = {"walk_acc": acc, "walk_m": m, "walk_l": l}
    else:
        q, k, v, n = flash
        o1, lse1 = fl.flash_partial(q, k[:, :32], v[:, :32], scale=0.09,
                                    causal=True)
        o2, lse2 = fl.flash_partial(q, k, v, scale=0.09, kv_len=n)
        got = {"flash_o": o1, "flash_lse": lse1, "hist_o": o2,
               "hist_lse": lse2}
    for name, val in got.items():
        np.testing.assert_array_equal(np.asarray(val), want[name], name)


def test_the_refusal_says_what_runs_on_the_chip():
    assert pa.ragged_tpu_refusal(128, False) is None
    assert "aligned to tiling (128), but is 64" in pa.ragged_tpu_refusal(
        64, False)
    assert pa.ragged_tpu_refusal(128, True) == pa.RAGGED_INT8_KV_TPU_REFUSAL
    assert "flat_decode_partial" in pa.ragged_tpu_refusal.__doc__
