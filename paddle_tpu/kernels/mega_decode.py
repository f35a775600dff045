"""Persistent fused decode megakernel (arXiv 2512.22219 / 2512.12949).

One ``pallas_call`` covers an ENTIRE decode step: the grid iterates the
layer axis (sequential on TPU, so VMEM scratch carries the hidden state
across layers) and each grid step fuses, for its layer,

- the r12 ragged paged-attention block walk (``kernels/paged_attention``'s
  online-softmax / flash-partial machinery, inlined — per-slot true-length
  walks, double-buffered block DMA, int8 KV streamed unconverted with the
  scale folding of ``attn_qk``/``attn_pv``),
- the in-call KV ring write (the decode step's per-layer KV writeback:
  the fresh K/V row is appended to the HBM ring at the step index via the
  ``paged_append_token`` DMA idiom — the ring rides the call as an
  aliased in/out operand, and the end-of-call ring→pool scatter stays the
  XLA code shared verbatim with the ragged/bucketed paths, where the
  valid-count depends on post-sampling ``done`` evolution),
- the full FFN (gate/up/down) plus both RMS norms and RoPE, with every
  weight matrix STREAMED from HBM in double-buffered column tiles — int8
  weights feed the MXU unconverted and their per-output-channel scales
  multiply the f32 accumulator (the ``quant_matmul`` idiom, tiled), so
  VMEM residency is bounded by the tile budget, not the model size.

The ragged path launches ``n_steps × L`` attention kernels per decode
call and round-trips the hidden state through HBM at every layer's XLA
FFN boundary; the mega path launches ``n_steps`` kernels and the hidden
state never leaves VMEM (serving/engine.py wires it as
``decode_kernel="mega"``). It has only ever run in the Pallas interpreter:
the TPU compiler refuses it (``MEGA_TPU_REFUSAL`` below), so the engine
selects it only by name and only off-TPU.

Second fusion target (``mega_decode_loop``): the speculative DRAFT wave's
``k`` sequential tiny steps run as ONE persistent launch — the grid grows
a leading step axis, and the greedy epilogue (final norm, a streamed
lm_head with a running tile argmax, the embedding-row DMA for the next
step's input, and the lens/done/budget bookkeeping mirrored from
``serving.engine._paged_decode``) runs in-kernel at the last layer of
each step. Greedy only: the target path keeps sampling (temperature /
top-k / top-p, PRNG) in the XLA epilogue, which is also what keeps the
compile-variant contract at ONE variant per sampling-flag set.

Parity contract (test-enforced): greedy token streams through the mega
path match the ragged path bit-for-bit on decisive-argmax workloads —
the math mirrors ``_paged_decode`` op for op (f32 norm statistics, dtype
cast points, the flash combine over [pool prefix ; raw-dtype ring]), but
matmul tilings differ, so the contract is stream identity, not logit
bitwise equality.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import _interpret
from .quant_matmul import is_quantized_weight

__all__ = ["mega_decode_step", "mega_decode_loop", "mega_supported",
           "MEGA_VMEM_BUDGET", "MEGA_TPU_REFUSAL"]

_MATS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")

# What the TPU compiler says to this kernel (jax 0.9.0 / libtpu 0.0.34,
# tests/test_aot_chip_compile.py) once its per-layer vector blocks and its
# value-level scatters are repaired: the ring combine's einsums carry two
# batch dims (slot, kv head). The kernel was only ever run interpreted;
# making Mosaic take it means re-laying-out its attention half, so the
# serving engine no longer selects it on a TPU.
MEGA_TPU_REFUSAL = ("'tpu.matmul' op Not implemented: Up to 1 batch dim "
                    "supported")

# same screening precedent as paged_decode_attention's staging-buffer
# gate: past ~12 MiB the working set can't coexist in the ~16 MiB VMEM,
# so the engine counts the fallback instead of hitting an opaque Mosaic
# allocation error at serving time
MEGA_VMEM_BUDGET = 12 * 1024 * 1024
_WTILE_BYTES = 4 * 1024 * 1024          # double-buffered weight tiles
_HTILE_BYTES = 2 * 1024 * 1024          # double-buffered lm_head tiles


def _tile_cols(k: int, itemsize: int, budget: int) -> int:
    """Column-tile width for streaming a [K, M] weight through a
    (2, K, tile) VMEM buffer within ``budget`` bytes: a lane-aligned
    multiple of 128, floored at one lane tile."""
    t = budget // max(1, 2 * k * itemsize)
    return min(2048, max(128, (t // 128) * 128))


def _head_mode(params, config) -> str:
    if getattr(config, "tie_embeddings", False):
        return "tied"
    return "int8" if isinstance(params.get("lm_head"), dict) else "dense"


def mega_supported(params, config, *, n_slots: int, n_steps: int,
                   block_size: int, kv_int8: bool,
                   multi_step: bool = False, mesh=None):
    """(ok, reason) eligibility screen for the mega decode kernel — the
    engine's counted-fallback gate (serving_mega_fallback_total{reason}).
    Estimates the kernel's VMEM scratch envelope (weight tiles, ring
    buffers, walk blocks, hidden-state carry) against the ~12 MiB budget
    the paged_decode_attention screening established. A tp mesh bows
    out with reason "mesh": GSPMD cannot partition the fused single
    launch (the ragged path shard_maps instead), so the engine falls
    back counted rather than raising."""
    if mesh is not None and dict(getattr(mesh, "shape", {})).get("tp", 1) > 1:
        return False, "mesh"
    lay = params["layers"]
    mats = [lay[k] for k in _MATS]
    quant = [is_quantized_weight(m) for m in mats]
    if any(quant) and not all(quant):
        return False, "mixed_weights"
    w_int8 = quant[0]
    wk0 = mats[0]["q"] if w_int8 else mats[0]
    dt = jnp.dtype(config.dtype)
    h = wk0.shape[1]
    Hkv, D = config.num_kv_heads, config.head_dim
    kmax = max((m["q"] if w_int8 else m).shape[1] for m in mats)
    wsize = 1 if w_int8 else dt.itemsize
    tw = _tile_cols(kmax, wsize, _WTILE_BYTES)
    psize = 1 if kv_int8 else dt.itemsize
    bytes_ = 2 * kmax * tw * wsize                       # wbuf
    bytes_ += 2 * n_slots * n_steps * Hkv * D * dt.itemsize   # ring bufs
    bytes_ += 2 * 2 * block_size * Hkv * D * psize       # walk blocks
    if kv_int8:
        bytes_ += 2 * 2 * block_size * Hkv * 4           # walk scales
    bytes_ += 2 * n_slots * h * dt.itemsize              # xs + staging
    bytes_ += n_slots * config.num_heads * (D + 2) * 4   # walk partials
    if multi_step:
        emb = params["embed"]
        bytes_ += n_slots * h * jnp.dtype(emb.dtype).itemsize   # ebuf
        mode = _head_mode(params, config)
        hsize = (jnp.dtype(emb.dtype).itemsize if mode == "tied"
                 else 1 if mode == "int8"
                 else jnp.dtype(params["lm_head"].dtype).itemsize)
        tv = _tile_cols(h, hsize, _HTILE_BYTES)
        bytes_ += 2 * h * tv * hsize                     # hbuf
    if bytes_ > MEGA_VMEM_BUDGET:
        return False, "vmem"
    return True, "ok"


# ---------------------------------------------------------------------------
# kernel body
# ---------------------------------------------------------------------------
def _mega_kernel(*refs, meta):
    """Grid (S, L) — sequential on TPU, so the VMEM scratch ``xs``
    (hidden state) and the draft bookkeeping persist across grid steps.
    ``meta`` (dict of static shapes/flags) fixes the *refs layout; see
    the builder below for the exact operand order."""
    (n_kv, G, D, bs, MB, S, N, h, L, TW, eps, sm_scale, dt, kv_int8,
     w_int8, multi, head_mode, TV, V) = (
        meta["n_kv"], meta["G"], meta["D"], meta["bs"], meta["MB"],
        meta["S"], meta["N"], meta["h"], meta["L"], meta["TW"],
        meta["eps"], meta["sm_scale"], meta["dt"], meta["kv_int8"],
        meta["w_int8"], meta["multi"], meta["head_mode"], meta["TV"],
        meta["V"])

    it = iter(refs)

    def take(k=1):
        out = [next(it) for _ in range(k)]
        return out[0] if k == 1 else out

    # scalar prefetch (SMEM)
    (t0_ref, table_ref, wl_ref, lens_ref, act_ref, last_ref, rem_ref,
     eos_ref) = take(8)
    # inputs
    x0_ref, freq_ref, an_ref, mn_ref = take(4)
    w_refs = take(7)
    s_refs = take(7) if w_int8 else [None] * 7
    if multi:
        fn_ref = take()
        emb_ref = take()
        head_ref = emb_ref if head_mode == "tied" else take()
        hs_ref = take() if head_mode == "int8" else None
    ring_k_ref, ring_v_ref, k_pool_ref, v_pool_ref = take(4)
    ks_pool_ref, vs_pool_ref = take(2) if kv_int8 else (None, None)
    # outputs
    if multi:
        emit_ref, state_out_ref = take(2)
    else:
        x_out_ref = take()
    # the ring rides the call as aliased in/out ANY operands; ALL
    # in-kernel traffic goes through the OUTPUT refs (on TPU the pair is
    # one buffer; in interpret mode the output copy is seeded from the
    # input and carries this call's earlier writes — the input copy
    # would not)
    rko_ref, rvo_ref = take(2)
    # scratch
    xs, rkb, rvb, kbuf, vbuf = take(5)
    ksbuf, vsbuf = take(2) if kv_int8 else (None, None)
    wbuf, ms, ls, accs = take(4)
    ring_sem, rout_sem, walk_sem, w_sem = take(4)
    if multi:
        state, ebuf, hbuf, h_sem, e_sem = take(5)

    s_idx = pl.program_id(0)
    lyr = pl.program_id(1)
    t = t0_ref[0] + s_idx

    # -- per-call init: hidden state + (draft) bookkeeping ---------------
    @pl.when((s_idx == 0) & (lyr == 0))
    def _():
        xs[...] = x0_ref[...]
        if multi:
            for c, ref in enumerate((last_ref, lens_ref, None, rem_ref)):
                col = (jnp.zeros((N,), jnp.int32) if ref is None else
                       jnp.stack([ref[i] for i in range(N)]))
                state[:, c:c + 1] = col.reshape(N, 1)

    # the in-call ring plane streams in while the QKV matmuls run
    rin = [pltpu.make_async_copy(rko_ref.at[lyr], rkb, ring_sem.at[0]),
           pltpu.make_async_copy(rvo_ref.at[lyr], rvb, ring_sem.at[1])]
    for cp in rin:
        cp.start()

    x = xs[...]                                          # [N, h] dt

    def rms(xv, w_row):
        xf = xv.astype(jnp.float32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        return ((xf * jax.lax.rsqrt(var + eps)).astype(xv.dtype)
                * w_row.astype(xv.dtype))

    def stream_mm(xv, w_ref, s_ref):
        """xv [N, K] @ w_ref[lyr] ([K, M], HBM) via double-buffered
        column tiles -> [N, M] f32 (int8: per-output-channel scale
        already applied — the weight_only_matmul idiom, tiled)."""
        K, M = w_ref.shape[1], w_ref.shape[2]
        nt = -(-M // TW)

        def cp(ti):
            a, tw = ti * TW, min(TW, M - ti * TW)
            return pltpu.make_async_copy(
                w_ref.at[lyr, :, a:a + tw],
                wbuf.at[ti % 2, 0:K, 0:tw], w_sem.at[ti % 2])

        cp(0).start()
        outs = []
        for ti in range(nt):
            if ti + 1 < nt:
                cp(ti + 1).start()
            cp(ti).wait()
            a, tw = ti * TW, min(TW, M - ti * TW)
            wt = wbuf[ti % 2, 0:K, 0:tw]
            acc = jax.lax.dot_general(
                xv, wt, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if s_ref is not None:
                acc = acc * s_ref[0, a:a + tw].astype(jnp.float32)[None]
            outs.append(acc)
        return outs[0] if nt == 1 else jnp.concatenate(outs, -1)

    # rope angles from the CURRENT lengths (the draft advances them
    # in-kernel; the target feeds each step's carry via scalar prefetch)
    if multi:
        lens_col = state[:, 1:2].astype(jnp.float32)
    else:
        lens_col = jnp.stack(
            [lens_ref[i] for i in range(N)]).reshape(N, 1) \
            .astype(jnp.float32)
    ang = lens_col * freq_ref[...].reshape(1, D // 2)    # [N, D/2]

    def rope(tv):                                        # [N, H, D]
        d2 = tv.shape[-1] // 2
        t1, t2 = tv[..., :d2], tv[..., d2:]
        cc = jnp.cos(ang)[:, None, :].astype(tv.dtype)
        ss = jnp.sin(ang)[:, None, :].astype(tv.dtype)
        return jnp.concatenate([t1 * cc - t2 * ss, t2 * cc + t1 * ss],
                               -1)

    # -- attention ------------------------------------------------------
    h1 = rms(x, an_ref[0])
    q = stream_mm(h1, w_refs[0], s_refs[0]).astype(dt) \
        .reshape(N, n_kv * G, D)
    kk = stream_mm(h1, w_refs[1], s_refs[1]).astype(dt) \
        .reshape(N, n_kv, D)
    vv = stream_mm(h1, w_refs[2], s_refs[2]).astype(dt) \
        .reshape(N, n_kv, D)
    q, kk = rope(q), rope(kk)
    qg = q.reshape(N, n_kv, G, D)

    # ring write (the per-layer KV writeback): the fresh row lands in
    # the VMEM plane, then DMA-appends to the aliased HBM ring at t —
    # earlier entries (j < t) were already resident for the scores
    for cp in rin:
        cp.wait()
    rkb[:, pl.ds(t, 1)] = kk[:, None]
    rvb[:, pl.ds(t, 1)] = vv[:, None]
    rout = [pltpu.make_async_copy(rkb.at[:, pl.ds(t, 1)],
                                  rko_ref.at[lyr, :, pl.ds(t, 1)],
                                  rout_sem.at[0]),
            pltpu.make_async_copy(rvb.at[:, pl.ds(t, 1)],
                                  rvo_ref.at[lyr, :, pl.ds(t, 1)],
                                  rout_sem.at[1])]
    for cp in rout:
        cp.start()

    # true-length block walk over the pool prefix — the r12 kernel's
    # per-slot program, inlined with fori-carried partials
    def copies(n, b, slot):
        blk = table_ref[n, b]
        cps = [pltpu.make_async_copy(k_pool_ref.at[lyr, blk],
                                     kbuf.at[slot], walk_sem.at[0, slot]),
               pltpu.make_async_copy(v_pool_ref.at[lyr, blk],
                                     vbuf.at[slot], walk_sem.at[1, slot])]
        if kv_int8:
            cps += [pltpu.make_async_copy(
                        ks_pool_ref.at[lyr, blk], ksbuf.at[slot],
                        walk_sem.at[2, slot]),
                    pltpu.make_async_copy(
                        vs_pool_ref.at[lyr, blk], vsbuf.at[slot],
                        walk_sem.at[3, slot])]
        return cps

    # per-slot online-softmax partials live in VMEM scratch, one plane per
    # slot, updated per kv head by ref stores — the ragged kernel's
    # pattern (value-level ``.at[].set`` is a scatter, which Mosaic does
    # not lower)
    ms[...] = jnp.full(ms.shape, -1e30, jnp.float32)
    ls[...] = jnp.zeros(ls.shape, jnp.float32)
    accs[...] = jnp.zeros(accs.shape, jnp.float32)
    for n in range(N):                        # static slot unroll
        ln = wl_ref[n]
        nblk = jnp.minimum((ln + bs - 1) // bs, MB)
        qn = qg[n]                                       # [Hkv, G, D]

        @pl.when(nblk > 0)
        def _(n=n):
            for cp in copies(n, 0, 0):
                cp.start()

        def walk(b, _, n=n, ln=ln, nblk=nblk, qn=qn):
            sl = jax.lax.rem(b, 2)

            @pl.when(b + 1 < nblk)
            def _():
                for cp in copies(n, b + 1, 1 - sl):
                    cp.start()

            for cp in copies(n, b, sl):
                cp.wait()
            col = (jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
                   + b * bs)
            live = col < ln
            for kh_i in range(n_kv):
                qh = qn[kh_i]                            # [G, D]
                kh = kbuf[sl][:, kh_i]                   # [bs, D]
                if kv_int8:
                    kh = kh.astype(qh.dtype)
                sc = jax.lax.dot_general(
                    qh, kh, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * sm_scale
                if kv_int8:
                    sc = sc * ksbuf[sl][:, kh_i][None, :]
                sc = jnp.where(live, sc, jnp.float32(-1e30))
                m_prev = ms[n, kh_i]
                m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(sc - m_new[:, None])
                ls[n, kh_i] = ls[n, kh_i] * alpha + jnp.sum(p, axis=-1)
                vh = vbuf[sl][:, kh_i]
                if kv_int8:
                    p = p * vsbuf[sl][:, kh_i][None, :]
                    vh = vh.astype(jnp.float32)
                else:
                    p = p.astype(vh.dtype)
                pv = jax.lax.dot_general(
                    p, vh, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                accs[n, kh_i] = accs[n, kh_i] * alpha[:, None] + pv
                ms[n, kh_i] = m_new
            return 0

        jax.lax.fori_loop(0, nblk, walk, 0)
    m_p = ms[...]                                        # [N, Hkv, G]
    l_p = ls[...]
    acc_p = accs[...]                                    # [N, Hkv, G, D]

    # flash-decoding combine with the raw-dtype ring (j <= t live) —
    # _paged_decode's merge, verbatim
    s_rng = jnp.einsum("nhgd,nshd->nhgs", qg, rkb[...],
                       preferred_element_type=jnp.float32) * sm_scale
    scol = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, S), 3)
    s_rng = jnp.where(scol <= t, s_rng, jnp.float32(-1e30))
    m_tot = jnp.maximum(m_p, jnp.max(s_rng, axis=-1))
    corr = jnp.exp(m_p - m_tot)
    p_rng = jnp.exp(s_rng - m_tot[..., None])
    l_tot = l_p * corr + jnp.sum(p_rng, axis=-1)
    acc_tot = (acc_p * corr[..., None]
               + jnp.einsum("nhgs,nshd->nhgd", p_rng, rvb[...],
                            preferred_element_type=jnp.float32))
    att = (acc_tot / l_tot[..., None]).reshape(N, n_kv * G * D) \
        .astype(dt)

    x = x + stream_mm(att, w_refs[3], s_refs[3]).astype(dt)

    # -- FFN ------------------------------------------------------------
    hn = rms(x, mn_ref[0])
    gate = jax.nn.silu(stream_mm(hn, w_refs[4], s_refs[4]).astype(dt))
    up = stream_mm(hn, w_refs[5], s_refs[5]).astype(dt)
    x = x + stream_mm(gate * up, w_refs[6], s_refs[6]).astype(dt)
    xs[...] = x
    if not multi:
        x_out_ref[...] = x

    # -- draft epilogue: greedy argmax + embed DMA + bookkeeping ---------
    if multi:
        @pl.when(lyr == L - 1)
        def _():
            xf = rms(xs[...], fn_ref[0])                 # [N, h]
            nt = -(-V // TV)
            best = jnp.full((N, 1), -jnp.inf, jnp.float32)
            bidx = jnp.zeros((N, 1), jnp.int32)

            def hcp(ti):
                a, tv = ti * TV, min(TV, V - ti * TV)
                if head_mode == "tied":                  # [tv, h] rows
                    return pltpu.make_async_copy(
                        head_ref.at[a:a + tv, :],
                        hbuf.at[ti % 2, 0:tv, :], h_sem.at[ti % 2])
                return pltpu.make_async_copy(            # [h, tv] cols
                    head_ref.at[:, a:a + tv],
                    hbuf.at[ti % 2, :, 0:tv], h_sem.at[ti % 2])

            hcp(0).start()
            for ti in range(nt):
                if ti + 1 < nt:
                    hcp(ti + 1).start()
                hcp(ti).wait()
                a, tv = ti * TV, min(TV, V - ti * TV)
                if head_mode == "tied":
                    wt = hbuf[ti % 2, 0:tv, :].astype(dt)
                    lg = jax.lax.dot_general(
                        xf, wt, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
                else:
                    wt = hbuf[ti % 2, :, 0:tv]
                    lg = jax.lax.dot_general(
                        xf, wt, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    if head_mode == "int8":
                        lg = lg * hs_ref[0, a:a + tv] \
                            .astype(jnp.float32)[None]
                # the XLA head matmul rounds through the model dtype
                # before the f32 argmax — mirror for tie exactness
                lg = lg.astype(dt).astype(jnp.float32)
                tmax = jnp.max(lg, axis=-1, keepdims=True)
                tcol = jax.lax.broadcasted_iota(jnp.int32, (N, tv), 1)
                targ = jnp.min(jnp.where(lg >= tmax, tcol, V),
                               axis=-1, keepdims=True) + a
                take_t = tmax > best
                best = jnp.where(take_t, tmax, best)
                bidx = jnp.where(take_t, targ, bidx)

            nxt = bidx                                   # [N, 1] i32
            act_col = jnp.stack(
                [act_ref[i] for i in range(N)]).reshape(N, 1)
            eos_col = jnp.stack(
                [eos_ref[i] for i in range(N)]).reshape(N, 1)
            act = (act_col != 0) & (state[:, 2:3] == 0)
            emit_ref[...] = jnp.where(act, nxt, -1)
            lens2 = state[:, 1:2] + act.astype(jnp.int32)
            rem2 = state[:, 3:4] - act.astype(jnp.int32)
            done2 = ((state[:, 2:3] != 0)
                     | (act & (eos_col >= 0) & (nxt == eos_col))
                     | (act & (rem2 <= 0))).astype(jnp.int32)
            last2 = jnp.where(act, nxt, state[:, 0:1])
            state[:, 0:1] = last2
            state[:, 1:2] = lens2
            state[:, 2:3] = done2
            state[:, 3:4] = rem2
            state_out_ref[...] = jnp.concatenate(
                [last2, lens2, done2, rem2], axis=1)

            # next step's input row: embed[last] — astype(dt) after the
            # gather matches astype-then-gather (same elements)
            def ecp(n):
                return pltpu.make_async_copy(
                    emb_ref.at[last2[n, 0]], ebuf.at[n], e_sem.at[n])
            for n in range(N):
                ecp(n).start()
            for n in range(N):
                ecp(n).wait()
            xs[...] = ebuf[...].astype(dt)

    for cp in rout:
        cp.wait()


# ---------------------------------------------------------------------------
# call builder
# ---------------------------------------------------------------------------
def _mega_call(params, config, *, x0, t0, block_table, walk_lens, lens,
               active, last0, budgets, eos_ids, ring_k, ring_v, k_pool,
               v_pool, ks_pool=None, vs_pool=None, multi_step, n_steps):
    lay = params["layers"]
    mats = [lay[k] for k in _MATS]
    w_int8 = is_quantized_weight(mats[0])
    kv_int8 = k_pool.dtype == jnp.int8
    dt = jnp.dtype(config.dtype)
    N, h = x0.shape
    L = config.num_layers
    Hkv, D = k_pool.shape[3], k_pool.shape[4]
    G = config.num_heads // config.num_kv_heads
    bs = k_pool.shape[2]
    MB = block_table.shape[1]
    S = ring_k.shape[2]
    wdt = jnp.dtype(jnp.int8) if w_int8 else jnp.dtype(mats[0].dtype)
    kmax = max((m["q"] if w_int8 else m).shape[1] for m in mats)
    TW = _tile_cols(kmax, wdt.itemsize, _WTILE_BYTES)
    head_mode = _head_mode(params, config) if multi_step else "none"

    ci = [0]

    def nxt_idx(k=1):
        ci[0] += k
        return ci[0] - k

    nxt_idx(8)                               # scalar prefetch operands
    freq = (config.rope_theta
            ** (-jnp.arange(0, D, 2, jnp.float32) / D)).reshape(1, -1)
    # per-layer vectors ride as [L, 1, width] with the layer dim squeezed:
    # Mosaic wants a block's last two dims tile-aligned or whole, and a
    # (1, width) block of an [L, width] array is neither
    def layer_row(v):
        return (v.reshape(L, 1, -1),
                pl.BlockSpec((None, 1, v.shape[-1]),
                             lambda s, l, *_: (l, 0, 0)))

    (an, an_spec), (mn, mn_spec) = (layer_row(lay["attn_norm"]),
                                    layer_row(lay["mlp_norm"]))
    inputs = [x0, freq, an, mn]
    in_specs = [
        pl.BlockSpec((N, h), lambda s, l, *_: (0, 0)),
        pl.BlockSpec((1, D // 2), lambda s, l, *_: (0, 0)),
        an_spec, mn_spec,
    ]
    nxt_idx(4)
    for m in mats:
        inputs.append(m["q"] if w_int8 else m)
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    nxt_idx(7)
    if w_int8:
        for m in mats:
            sc, sc_spec = layer_row(m["s"])
            inputs.append(sc)
            in_specs.append(sc_spec)
        nxt_idx(7)
    V = TV = 0
    if multi_step:
        emb = params["embed"]
        V = emb.shape[0]
        inputs += [params["final_norm"].reshape(1, h), emb]
        in_specs += [pl.BlockSpec((1, h), lambda s, l, *_: (0, 0)),
                     pl.BlockSpec(memory_space=pl.ANY)]
        nxt_idx(2)
        if head_mode == "tied":
            hdt, TV = jnp.dtype(emb.dtype), _tile_cols(
                h, jnp.dtype(emb.dtype).itemsize, _HTILE_BYTES)
        elif head_mode == "int8":
            hq = params["lm_head"]["q"]
            hdt, TV = jnp.dtype(jnp.int8), _tile_cols(
                h, 1, _HTILE_BYTES)
            inputs.append(hq)
            in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
            nxt_idx()
            inputs.append(params["lm_head"]["s"].reshape(1, V))
            in_specs.append(pl.BlockSpec(
                (1, V), lambda s, l, *_: (0, 0)))
            nxt_idx()
        else:
            hw = params["lm_head"]
            hdt, TV = jnp.dtype(hw.dtype), _tile_cols(
                h, jnp.dtype(hw.dtype).itemsize, _HTILE_BYTES)
            inputs.append(hw)
            in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
            nxt_idx()
    ring_pos = nxt_idx(2)
    inputs += [ring_k, ring_v]
    in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
    inputs += [k_pool, v_pool]
    in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
    nxt_idx(2)
    if kv_int8:
        inputs += [ks_pool.astype(jnp.float32),
                   vs_pool.astype(jnp.float32)]
        in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
        nxt_idx(2)

    if multi_step:
        out_shape = [jax.ShapeDtypeStruct((N, n_steps), jnp.int32),
                     jax.ShapeDtypeStruct((N, 4), jnp.int32),
                     jax.ShapeDtypeStruct(ring_k.shape, ring_k.dtype),
                     jax.ShapeDtypeStruct(ring_v.shape, ring_v.dtype)]
        out_specs = [
            pl.BlockSpec((N, 1), lambda s, l, *_: (0, s)),
            pl.BlockSpec((N, 4), lambda s, l, *_: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY)]
        aliases = {ring_pos: 2, ring_pos + 1: 3}
    else:
        out_shape = [jax.ShapeDtypeStruct((N, h), dt),
                     jax.ShapeDtypeStruct(ring_k.shape, ring_k.dtype),
                     jax.ShapeDtypeStruct(ring_v.shape, ring_v.dtype)]
        out_specs = [pl.BlockSpec((N, h), lambda s, l, *_: (0, 0)),
                     pl.BlockSpec(memory_space=pl.ANY),
                     pl.BlockSpec(memory_space=pl.ANY)]
        aliases = {ring_pos: 1, ring_pos + 1: 2}

    scratch = [pltpu.VMEM((N, h), dt),                     # xs
               pltpu.VMEM((N, S, Hkv, D), ring_k.dtype),   # rkb
               pltpu.VMEM((N, S, Hkv, D), ring_v.dtype),   # rvb
               pltpu.VMEM((2, bs, Hkv, D), k_pool.dtype),  # kbuf
               pltpu.VMEM((2, bs, Hkv, D), v_pool.dtype)]  # vbuf
    if kv_int8:
        scratch += [pltpu.VMEM((2, bs, Hkv), jnp.float32),
                    pltpu.VMEM((2, bs, Hkv), jnp.float32)]
    scratch += [pltpu.VMEM((2, kmax, TW), wdt),            # wbuf
                pltpu.VMEM((N, Hkv, G), jnp.float32),      # ms
                pltpu.VMEM((N, Hkv, G), jnp.float32),      # ls
                pltpu.VMEM((N, Hkv, G, D), jnp.float32),   # accs
                pltpu.SemaphoreType.DMA((2,)),             # ring_sem
                pltpu.SemaphoreType.DMA((2,)),             # rout_sem
                pltpu.SemaphoreType.DMA((4 if kv_int8 else 2, 2)),
                pltpu.SemaphoreType.DMA((2,))]             # w_sem
    if multi_step:
        hshape = (2, TV, h) if head_mode == "tied" else (2, h, TV)
        scratch += [pltpu.VMEM((N, 4), jnp.int32),         # state
                    pltpu.VMEM((N, h), params["embed"].dtype),
                    pltpu.VMEM(hshape, hdt),               # hbuf
                    pltpu.SemaphoreType.DMA((2,)),         # h_sem
                    pltpu.SemaphoreType.DMA((N,))]         # e_sem

    meta = dict(n_kv=Hkv, G=G, D=D, bs=bs, MB=MB, S=S, N=N, h=h, L=L,
                TW=TW, eps=config.rms_eps,
                sm_scale=1.0 / math.sqrt(D), dt=dt, kv_int8=kv_int8,
                w_int8=w_int8, multi=multi_step, head_mode=head_mode,
                TV=TV, V=V)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=(n_steps if multi_step else 1, L),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    z = jnp.zeros((N,), jnp.int32)
    scalars = [jnp.asarray(t0, jnp.int32).reshape(1),
               block_table.astype(jnp.int32),
               walk_lens.astype(jnp.int32),
               lens.astype(jnp.int32),
               (active.astype(jnp.int32) if active is not None else z),
               (last0.astype(jnp.int32) if last0 is not None else z),
               (budgets.astype(jnp.int32) if budgets is not None else z),
               (eos_ids.astype(jnp.int32) if eos_ids is not None else z)]
    return pl.pallas_call(
        functools.partial(_mega_kernel, meta=meta),
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=_interpret(),
    )(*scalars, *inputs)


def mega_decode_step(params, config, *, x0, t, block_table, walk_lens,
                     lens, ring_k, ring_v, k_pool, v_pool, ks_pool=None,
                     vs_pool=None):
    """ONE fused decode-step launch (all layers): hidden state x0
    [N, hidden] -> post-layer-stack hidden state [N, hidden], with the
    step's K/V rows appended to the aliased in-call rings at index ``t``.
    The caller owns the epilogue (final norm, lm_head, sampling) and the
    end-of-call ring->pool writeback — shared verbatim with the ragged
    path, which is what the greedy stream-parity tests pin."""
    x, rk, rv = _mega_call(
        params, config, x0=x0, t0=t, block_table=block_table,
        walk_lens=walk_lens, lens=lens, active=None, last0=None,
        budgets=None, eos_ids=None, ring_k=ring_k, ring_v=ring_v,
        k_pool=k_pool, v_pool=v_pool, ks_pool=ks_pool, vs_pool=vs_pool,
        multi_step=False, n_steps=1)
    return x, rk, rv


def mega_decode_loop(params, config, *, x0, n_steps, block_table,
                     walk_lens, lens, active, last0, budgets, eos_ids,
                     ring_k, ring_v, k_pool, v_pool):
    """The speculative-draft fusion target: ``n_steps`` greedy decode
    steps in ONE persistent launch (grid (k, L)) instead of k — the
    greedy epilogue (streamed lm_head + running argmax, embedding-row
    DMA, lens/done/budget updates mirroring ``_paged_decode``'s scan
    body) runs in-kernel at each step's last layer. ``x0`` is
    ``embed[last0]``; ``done0`` must be all-false (the spec wave's
    contract). Returns (emitted [k, N] i32 with -1 padding, last, lens,
    done, budgets, ring_k, ring_v); the caller runs the shared ring ->
    pool writeback."""
    emitted, state, rk, rv = _mega_call(
        params, config, x0=x0, t0=0, block_table=block_table,
        walk_lens=walk_lens, lens=lens, active=active, last0=last0,
        budgets=budgets, eos_ids=eos_ids, ring_k=ring_k, ring_v=ring_v,
        k_pool=k_pool, v_pool=v_pool, multi_step=True, n_steps=n_steps)
    return (emitted.T, state[:, 0], state[:, 1],
            state[:, 2].astype(bool), state[:, 3], rk, rv)
