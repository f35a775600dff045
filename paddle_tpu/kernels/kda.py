"""Kimi Delta Attention (KDA, arXiv:2510.26692): the two kernels that move a
linear-attention layer's state on a TPU.

A head's whole memory of its context is a matrix ``S`` in R^{dk x dv}
(float32; zero where a context starts). One token moves it by a decay a
key channel and a delta-rule write::

    S' = Diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T          o_t = S_t^T q_t

with ``g_t`` in (LOWER, 0) a channel (the safe gate: ``LOWER`` is the
configuration's ``kda_lower_bound``, -5), ``beta_t`` in (0, 1) a head,
``k_t`` of unit length and ``q_t`` of length ``dk^-1/2`` (the caller norms
them). Nothing here approximates the recurrence.

``kda_step``   a decode step: N slots, one token each. The state ENTRY
               ``[1, rows, H, dk, dv]`` (a row a slot and a trash row) is
               aliased in and out: a live slot's matrices are read once and
               written once where they lie, a slot where ``act`` is false
               is not touched (its program visits the trash row and copies
               it onto itself). The matrix-vector products are the VPU's
               (a column of ``dk`` values against the rows of ``S``, summed
               over the rows): on the MXU a [1, dk] x [dk, dv] product
               loads ``S`` as the stationary operand for one row, several
               times the 0.16 us that reading a head's 64 KB takes.
``kda_chunk``  a prefill piece: T tokens of one row from a carried state
               to the state after its last REAL token, in chunks of 64.
               Within a chunk, with ``G`` the running sum of ``g`` and
               ``A_ij = sum_c k_ic k_jc exp(G_ic - G_jc)`` (j < i), the
               writes ``u`` solve the unit lower-triangular system ``(I +
               Diag(beta) A) U = Diag(beta) (V - (K . exp G) S_0)``: the
               UT / WY form of the Kimi Linear report. ``exp(G_i - G_j)``
               is never formed from ``exp(-G_j)`` over a whole chunk (64
               steps of -5 are e^320): each sub-block of 16 rows takes its
               own reference row, so that one factor is at most 1 and the
               other at most e^75 (15 steps of -5), inside float32. The
               triangular inverse is exact products: within a 16-block
               ``sum_k (-L)^k`` as ``(I+X)(I+X^2)(I+X^4)(I+X^8)``, across
               the four blocks the same for the block-nilpotent part. All
               products at ``highest`` precision.

Each has an XLA form for a CPU (the same chunk mathematics under
``lax.scan``; einsums for the step) and a Mosaic form, named in a trace by
the caller (``name=``). ``_mosaic()`` says which runs; ``interpret=True``
runs the Mosaic form in the Pallas interpreter (the CPU tests).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["kda_chunk", "kda_step", "CHUNK", "SUB", "LOWER_BOUND"]

CHUNK = 64          # tokens a chunk
SUB = 16            # rows of a sub-block: SUB - 1 steps of LOWER_BOUND stay
LOWER_BOUND = -5.0  # under float32's e^88; a gate below it is refused
_EXP_CAP = 80.0     # masked entries' exponents are cut here, never a real one
_HP = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


def _mosaic() -> bool:
    """Whether the kernels lower to Mosaic (a TPU)."""
    return jax.default_backend() == "tpu"


def _dot(a, b, dims=((1,), (0,))):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HP,
                               preferred_element_type=_F32)


def _iota2(n, m):
    return (jax.lax.broadcasted_iota(jnp.int32, (n, m), 0),
            jax.lax.broadcasted_iota(jnp.int32, (n, m), 1))


def _column(row):
    """A [1, d] row as a [d, 1] column: the diagonal of its broadcast,
    summed over the lanes (a relayout every backend takes)."""
    d = row.shape[1]
    ri, ci = _iota2(d, d)
    return jnp.sum(jnp.where(ri == ci, jnp.broadcast_to(row, (d, d)), 0.0),
                   axis=1, keepdims=True)


def _neumann(x, eye, terms: int):
    """``sum_{k < terms} x^k`` for a nilpotent ``x`` (``x^terms = 0``),
    ``terms`` a power of two: ``(I + x)(I + x^2)(I + x^4)...``."""
    out, xp = eye + x, x
    for _ in range(int(math.log2(terms)) - 1):
        xp = _dot(xp, xp)
        out = out + _dot(out, xp)
    return out


def _chunk_math(q, k, kb, vb, G, S):
    """One chunk of one head. q, k, kb (= beta k), vb (= beta v), G (the
    running sum of the log-decays within the chunk) [C, d] float32; S
    [dk, dv] the state before the chunk. Returns (o [C, dv], the state
    after the chunk)."""
    C, d = q.shape
    assert C % SUB == 0 and (C // SUB) & (C // SUB - 1) == 0, C
    # A_ij = (beta_i k_i . exp(G_i - G_j) k_j), P_ij = (q_i . exp(G_i -
    # G_j) k_j), a sub-block of rows at a time about that block's first row
    rows_a, rows_p = [], []
    for lo in range(0, C, SUB):
        ref = G[lo:lo + 1]
        rowf = jnp.exp(G[lo:lo + SUB] - ref)                    # <= 1
        kk = k * jnp.exp(jnp.minimum(ref - G, _EXP_CAP))        # <= e^75
        rows_a.append(_dot(kb[lo:lo + SUB] * rowf, kk, ((1,), (1,))))
        rows_p.append(_dot(q[lo:lo + SUB] * rowf, kk, ((1,), (1,))))
    ri, ci = _iota2(C, C)
    A = jnp.where(ri > ci, jnp.concatenate(rows_a, 0), 0.0)
    P = jnp.where(ri >= ci, jnp.concatenate(rows_p, 0), 0.0)
    # T = (I + A)^-1: the diagonal blocks, then the blocks below them
    eye = (ri == ci).astype(_F32)
    diag = jnp.where(ri // SUB == ci // SUB, A, 0.0)
    dinv = _neumann(-diag, eye, SUB)
    if C > SUB:
        below = _dot(dinv, A - diag)
        T = _dot(_neumann(-below, eye, C // SUB), dinv)
    else:
        T = dinv
    gam = jnp.exp(G)
    U = _dot(T, vb) - _dot(_dot(T, kb * gam), S)                # [C, dv]
    o = _dot(q * gam, S) + _dot(P, U)
    last = G[C - 1:C]
    S = S * _column(jnp.exp(last)) + _dot(k * jnp.exp(last - G), U,
                                          ((0,), (0,)))
    return o, S


def _chunk_kernel(q_ref, k_ref, kb_ref, vb_ref, g_ref, s_ref, o_ref,
                  s_out_ref):
    @pl.when(pl.program_id(1) == 0)
    def _():
        s_out_ref[...] = s_ref[...]

    o, S = _chunk_math(q_ref[...], k_ref[...], kb_ref[...], vb_ref[...],
                       g_ref[...], s_out_ref[0])
    o_ref[...] = o
    s_out_ref[0] = S


def kda_chunk(q, k, v, g, beta, state, true_len=None, *,
              name: str = "kda_chunk", interpret: bool = False):
    """A piece of one row. q, k, v [T, H, d] (q and k normed by the
    caller), g [T, H, d] the log-decays in [``LOWER_BOUND``, 0], beta
    [T, H], state [H, d, d] float32 (``S[dk, dv]`` a head), ``true_len``
    the piece's real tokens (all of them where None). Returns (o [T, H, d]
    float32, the state after token ``true_len - 1``); outputs at and past
    ``true_len`` mean nothing."""
    T, H, d = q.shape
    if true_len is not None:
        real = (jnp.arange(T) < true_len)[:, None]
        g = jnp.where(real[..., None], g, 0.0)     # no decay, no write:
        beta = jnp.where(real, beta, 0.0)          # the state stands still
    # a short piece (the CPU tests') takes one chunk of 16 or 32 rows
    C = min(CHUNK, max(SUB, 1 << (T - 1).bit_length()))
    Tp = -(-T // C) * C
    f = lambda x: jnp.pad(x.astype(_F32), ((0, Tp - T),) + ((0, 0),) * (
        x.ndim - 1))
    q, k, v, g, beta = f(q), f(k), f(v), f(g), f(beta)
    G = jnp.cumsum(g.reshape(Tp // C, C, H, d), axis=1).reshape(Tp, H, d)
    kb, vb = k * beta[..., None], v * beta[..., None]
    state = state.astype(_F32)
    if _mosaic() or interpret:
        flat = lambda x: x.reshape(Tp, H * d)
        tok = pl.BlockSpec((C, d), lambda h, c: (c, h))
        mat = pl.BlockSpec((1, d, d), lambda h, c: (h, 0, 0))
        o, state = pl.pallas_call(
            _chunk_kernel, grid=(H, Tp // C),
            in_specs=[tok] * 5 + [mat], out_specs=[tok, mat],
            out_shape=[jax.ShapeDtypeStruct((Tp, H * d), _F32),
                       jax.ShapeDtypeStruct((H, d, d), _F32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret, name=name,
        )(flat(q), flat(k), flat(kb), flat(vb), flat(G), state)
        return o.reshape(Tp, H, d)[:T], state

    def one_chunk(S, xs):
        o, S = jax.vmap(_chunk_math, in_axes=(1, 1, 1, 1, 1, 0),
                        out_axes=(1, 0))(*xs, S)
        return S, o

    chunks = lambda x: x.reshape(Tp // C, C, H, d)
    state, o = jax.lax.scan(one_chunk, state,
                            tuple(map(chunks, (q, k, kb, vb, G))))
    return o.reshape(Tp, H, d)[:T], state


# -- the decode step ----------------------------------------------------------
# heads a program: at 32 heads of 128 the four columns a head fill the 128
# lanes exactly (no padded operand) and a slot's 2 MB of matrices come and
# go as one block, double-buffered in 8 MB of VMEM
_STEP_HEADS = 32


def _step_kernel(rows_ref, act_ref, cols_ref, v_ref, s_ref, o_ref, s_out_ref,
                 *, hb: int):
    live = act_ref[pl.program_id(0)] != 0

    @pl.when(live)
    def _():
        cols = cols_ref[0, 0]                                  # [dk, 4 hb]
        for j in range(hb):
            a, k, kb, q = (cols[:, i * hb + j:i * hb + j + 1]
                           for i in range(4))
            S = s_ref[0, 0, j].astype(_F32) * a                # [dk, dv]
            u = v_ref[0, j:j + 1] - jnp.sum(S * k, axis=0, keepdims=True)
            S = S + kb * u
            s_out_ref[0, 0, j] = S.astype(s_out_ref.dtype)
            o_ref[0, j:j + 1] = jnp.sum(S * q, axis=0, keepdims=True)

    @pl.when(jnp.logical_not(live))
    def _():
        s_out_ref[...] = s_ref[...]          # the trash row, onto itself
        o_ref[...] = jnp.zeros_like(o_ref)


def kda_step(q, k, v, g, beta, entry, act, *, name: str = "kda_step",
             interpret: bool = False):
    """One token a slot. q, k, v [N, H, d] (q and k normed by the caller),
    g [N, H, d] log-decays, beta [N, H], ``entry`` [1, rows, H, d, d]
    (float32 in every served configuration; the arithmetic is float32
    whatever the entry holds) with slot n's matrices in row n and ``rows >
    N`` (the last is a trash row), act [N] bool. Returns (o [N, H, d]
    float32, the entry with the rows of the slots where ``act`` advanced;
    every other row as it was, bit for bit). On a TPU the entry is aliased
    in and out."""
    N, H, d = q.shape
    rows = entry.shape[1]
    assert entry.shape == (1, rows, H, d, d) and rows > N, entry.shape
    q, k, v, g = (x.astype(_F32) for x in (q, k, v, g))
    kb = k * beta.astype(_F32)[..., None]
    if not (_mosaic() or interpret):
        S0 = entry[0, :N]
        S = S0.astype(_F32) * jnp.exp(g)[..., None]
        u = v - jnp.einsum("nhkv,nhk->nhv", S, k, precision=_HP)
        S = S + kb[..., None] * u[..., None, :]
        o = jnp.einsum("nhkv,nhk->nhv", S, q, precision=_HP)
        S = jnp.where(act[:, None, None, None], S.astype(S0.dtype), S0)
        return o, entry.at[0, :N].set(S)
    hb = math.gcd(H, _STEP_HEADS)
    # the four columns a head ([dk] each: decay, k, beta k, q) with dk on
    # the sublanes, as the rows of S lie: [N, H / hb, dk, 4 hb]
    cols = jnp.stack([jnp.exp(g), k, kb, q], axis=1)          # [N, 4, H, d]
    cols = cols.reshape(N, 4, H // hb, hb, d).transpose(0, 2, 4, 1, 3)
    cols = cols.reshape(N, H // hb, d, 4 * hb)
    act = act.astype(jnp.int32)
    where = jnp.where(act != 0, jnp.arange(N, dtype=jnp.int32), rows - 1)
    mat = pl.BlockSpec((1, 1, hb, d, d),
                       lambda n, h, w, a: (0, w[n], h, 0, 0))
    vec = pl.BlockSpec((1, hb, d), lambda n, h, w, a: (n, h, 0))
    o, entry = pl.pallas_call(
        functools.partial(_step_kernel, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(N, H // hb),
            in_specs=[pl.BlockSpec((1, 1, d, 4 * hb),
                                   lambda n, h, w, a: (n, h, 0, 0)),
                      vec, mat],
            out_specs=[vec, mat]),
        out_shape=[jax.ShapeDtypeStruct((N, H, d), _F32),
                   jax.ShapeDtypeStruct(entry.shape, entry.dtype)],
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=(4 * hb * d * d * entry.dtype.itemsize
                              + (8 << 20))),
        interpret=interpret, name=name,
    )(where, act, cols, v, entry)
    return o, entry
