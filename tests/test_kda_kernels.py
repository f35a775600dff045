"""``kernels/kda.py`` on the CPU, float32: the chunked scan (its XLA form
and its Mosaic form in the Pallas interpreter) and the one-token state
update against the recurrence token by token.

Tolerances: both forms are exact algebra of the recurrence at ``highest``
precision, so they differ from it by float32 summation order alone: the
outputs (of size ~0.1) by under 1e-6, a state (entries up to ~1.5) by a
few 1e-6 after 128 tokens. A chunk's factored form that left float32 (an
exponent past e^88) would read inf or nan, and a wrong decay, mask or
carried state moves the outputs by their own size."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import kda

F32 = jnp.float32
O_TOL, S_TOL = 2e-6, 2e-5


def recurrence(q, k, v, g, beta, S):
    """Token by token. q, k, v, g [T, H, d], beta [T, H], S [H, d, d]."""
    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = S * jnp.exp(g_t)[..., None]
        u = (v_t - jnp.einsum("hkv,hk->hv", S, k_t,
                              precision="highest")) * b_t[:, None]
        S = S + k_t[..., None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t, precision="highest")

    S, o = jax.lax.scan(step, S, (q, k, v, g, beta))
    return o, S


def operands(T, H, d, seed, g_lo=-5.0, g_hi=0.0, state=0.1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (T, H, d), F32)) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (T, H, d), F32))
    v = jax.random.normal(ks[2], (T, H, d), F32)
    g = jax.random.uniform(ks[3], (T, H, d), F32, g_lo, g_hi)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (T, H), F32))
    S = jax.random.normal(ks[5], (H, d, d), F32) * state
    return q, k, v, g, beta, S


@pytest.mark.parametrize("interpret", [False, True], ids=["xla", "mosaic"])
@pytest.mark.parametrize("T,g_lo,g_hi,state", [
    (128, -5.0, 0.0, 0.0),      # two whole chunks from zero
    (128, -5.0, 0.0, 0.1),      # ... and from a carried state
    (100, -5.0, -4.9, 0.1),     # every decay at the gate's lower end: a
    #                             sub-block's factors reach e^75
    (70, -0.01, 0.0, 0.1),      # every decay at the upper end: nothing is
    #                             forgotten, the triangular solve is full
    (37, -5.0, 0.0, 0.1)],      # a piece shorter than a chunk
    ids=["from-zero", "carried", "decay-low", "decay-high", "short"])
def test_the_chunked_scan_is_the_recurrence(T, g_lo, g_hi, state, interpret):
    q, k, v, g, beta, S = operands(T, 2, 128, T, g_lo, g_hi, state)
    want_o, want_S = recurrence(q, k, v, g, beta, S)
    o, S1 = jax.jit(lambda *a: kda.kda_chunk(*a, interpret=interpret))(
        q, k, v, g, beta, S)
    assert np.isfinite(np.asarray(o)).all()
    assert float(jnp.abs(o - want_o).max()) <= O_TOL
    assert float(jnp.abs(S1 - want_S).max()) <= S_TOL


@pytest.mark.parametrize("interpret", [False, True], ids=["xla", "mosaic"])
@pytest.mark.parametrize("cuts", [(50, 77, 1), (64, 64), (16, 3, 90, 19)],
                         ids=["off-chunk", "on-chunk", "ragged"])
def test_pieces_cut_anywhere_carry_the_state(cuts, interpret):
    """A sequence cut into pieces at boundaries that are no multiple of 64
    (or of 16), each padded to a bucket of 128 rows with ``true_len`` its
    real tokens: every piece begins from what the one before left after its
    last REAL token, and the pad rows move nothing."""
    T = sum(cuts)
    q, k, v, g, beta, S = operands(T, 2, 128, 11)
    want_o, want_S = recurrence(q, k, v, g, beta, S)
    pad = lambda x, lo, n: jnp.pad(x[lo:lo + n], ((0, 128 - n),) + (
        (0, 0),) * (x.ndim - 1), constant_values=0.37)   # junk, not zeros
    fn = jax.jit(lambda *a: kda.kda_chunk(*a, interpret=interpret))
    lo, outs = 0, []
    for n in cuts:
        # the pad rows' log-decays are junk inside the gate's range
        junk_g = jnp.full((128 - n, 2, 128), -1.3, F32)
        o, S = fn(pad(q, lo, n), pad(k, lo, n), pad(v, lo, n),
                  jnp.concatenate([g[lo:lo + n], junk_g]), pad(beta, lo, n),
                  S, jnp.int32(n))
        outs.append(o[:n])
        lo += n
    assert float(jnp.abs(jnp.concatenate(outs) - want_o).max()) <= O_TOL
    assert float(jnp.abs(S - want_S).max()) <= S_TOL


def test_a_piece_of_pad_rows_only_leaves_the_state_bit_for_bit():
    q, k, v, g, beta, S = operands(32, 2, 128, 5)
    for interpret in (False, True):
        _o, S1 = kda.kda_chunk(q, k, v, g, beta, S, jnp.int32(0),
                               interpret=interpret)
        assert (np.asarray(S1) == np.asarray(S)).all()


@pytest.mark.parametrize("interpret", [False, True], ids=["xla", "mosaic"])
def test_the_step_advances_the_active_slots_and_no_other_row(interpret):
    N, H, d = 5, 8, 128
    q, k, v, g, beta, _ = operands(N, H, d, 3)
    entry = jax.random.normal(jax.random.PRNGKey(9), (1, N + 2, H, d, d),
                              F32) * 0.1
    act = jnp.asarray([True, False, True, True, False])
    want_o, want_S = jax.vmap(
        lambda S, *x: recurrence(*(a[None] for a in x), S))(
        entry[0, :N], q, k, v, g, beta)
    o, out = jax.jit(lambda *a: kda.kda_step(*a, interpret=interpret))(
        q, k, v, g, beta, entry, act)
    on = np.asarray(act)
    assert float(jnp.abs(o - want_o[:, 0])[on].max()) <= O_TOL
    assert float(jnp.abs(out[0, :N] - want_S)[on].max()) <= S_TOL
    # an inactive slot's matrices, and the rows past the slots (the trash
    # row, which the inactive slots' programs visit), bit for bit
    assert (np.asarray(out[0, :N])[~on] == np.asarray(entry[0, :N])[~on]
            ).all()
    assert (np.asarray(out[0, N:]) == np.asarray(entry[0, N:])).all()


def test_steps_after_a_piece_continue_the_recurrence():
    """The two kernels hand the same state on: a piece of 40 tokens through
    the chunked scan, then 8 tokens one at a time through the step."""
    H, d = 4, 128
    q, k, v, g, beta, S = operands(48, H, d, 21, state=0.0)
    want_o, want_S = recurrence(q, k, v, g, beta, S)
    _o, S = kda.kda_chunk(q[:40], k[:40], v[:40], g[:40], beta[:40], S)
    entry = jnp.zeros((1, 2, H, d, d), F32).at[0, 0].set(S)
    for t in range(40, 48):
        o, entry = kda.kda_step(q[t:t + 1], k[t:t + 1], v[t:t + 1],
                                g[t:t + 1], beta[t:t + 1], entry,
                                jnp.asarray([True]), interpret=True)
        assert float(jnp.abs(o[0] - want_o[t]).max()) <= O_TOL
    assert float(jnp.abs(entry[0, 0] - want_S).max()) <= S_TOL
