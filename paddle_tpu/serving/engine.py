"""Continuous-batching paged-cache serving engine for any model that gives
the served-model interface (models/llama_served.py states it; llama-family
dense decoders, the latent-attention sparse-expert family
models/deepseek_v2.py, the short-convolution family models/lfm2_moe.py,
which keeps per-slot state beside the paged cache, and models/mellum.py,
whose window layers' pools are a second kind of cache entry with a block
ledger of their own (serving/window_ledger.py), provide it).

Parity surface: the reference wires its paged decode kernel into serving via
incubate/nn/functional/block_multihead_attention (block tables + per-seq
lengths updated by an external loop); vLLM-style continuous batching is the
behavioral model its serving stacks build on top.

TPU-native design — everything the chip executes has STATIC shapes:

- ONE compiled decode step over ``max_slots`` sequence slots. A slot is
  a row of the batch; requests come and go, the program never retraces
  on slot churn. Idle slots write their K/V to a reserved trash block
  and are masked out of sampling.
- Ragged paged attention (r12, the TPU default): decode attention runs
  the Pallas true-length block-walk kernel
  (kernels/paged_attention.ragged_decode_partial) — per-slot programs
  read exactly ``ceil(length/bs)`` real blocks with an online softmax,
  lengths ride as a RUNTIME operand and the block table ships at full
  width, so the decode compile cache holds ONE variant per
  (batch, sampling-flags) set and per-step KV reads scale with the
  tokens actually resident. Off-TPU (or forced via
  ``decode_kernel="bucketed"``) the r6 fallback applies instead: the
  dense prefix gather spans the smallest power-of-two BLOCK COUNT
  covering ``max(lengths) + decode_steps`` across the active slots
  (plus the in-flight pipeline lag) — bounded at (log2 buckets) x
  (<= 8 sampling-flag tuples) compiled variants. Either path is
  counted per dispatch in ``serving_decode_kernel_total{path}`` and
  mirrored by the ``serving_decode_prefix_bucket`` /
  ``serving_decode_variants`` gauges and the
  ``serving_decode_recompiles_total`` counter.
- Bucketed prefill: prompts pad to the smallest configured bucket, one
  compiled program per bucket (the guard-cache analogue of the reference's
  shape-bucketed serving graphs). Prefill K/V is scattered straight into
  the slot's pool blocks; blocks past the true length are handed back.
- Host-side block allocator: a free list over a
  ``[L, num_blocks, block_size, Hkv, D]`` pool pair. Admission reserves
  ceil(bucket/bs) blocks; decode allocates one block per slot whenever the
  next token crosses a block boundary; EOS/max-len frees the slot. When the
  pool runs dry mid-decode the newest-admitted request is preempted (blocks
  freed, request re-queued for a fresh prefill) — forward progress for the
  rest, vLLM's recompute-preemption policy.
- int8 everywhere (optional, decode is weight/KV-bandwidth-bound):
  int8 weight-only params (models/llama.quantize_params) feed the matmuls
  UNCONVERTED via kernels/quant_matmul.weight_only_matmul — scales apply
  to the output, no dequantized weight copy per step — including under a
  'tp' mesh (the int8 qweights + scales shard with the same Megatron
  specs as their dense counterparts). ``kv_dtype="int8"`` additionally
  quantizes the K/V pools with per-entry scales dequantized inside the
  bucketed attention contractions: half the decode KV traffic, double the
  effective block-pool capacity at the same HBM (fewer preemptions).
- Per-request sampling knobs (temperature/top-k/top-p) ride as traced
  vectors through the compiled step: varying them never recompiles.
- Pools are donated through both prefill and decode (jax donate_argnums),
  so the multi-GB cache is updated in place, never copied per token.
- Prefix caching + chunked prefill (optional, r10): ``prefix_cache=True``
  indexes full prompt blocks in a refcounted radix trie
  (serving/prefix_cache.py) so admissions sharing a system prompt or
  multi-turn prefix pin the cached blocks and prefill only the suffix;
  ``prefill_chunk=K`` splits long suffixes into K-token chunks fed one
  per step between decode waves, so prefill cost scales with NEW tokens
  and never monopolizes a step.
- Async two-tier KV offload (r15, on whenever a host tier exists):
  preemption swap-outs and prefix-cache spills dispatch non-blocking
  d2h (serving/offload.py; blocks ride a transient ``in_flight``
  ledger term until the step-boundary sweep lands them), queue-head
  restores prefetch h2d into staging buffers ahead of admission
  (prefetch_hit vs counted inline stall), and cold cached blocks
  spill proactively under pool pressure so reclaim never pays d2h
  inline. Greedy streams are bit-identical to the forced-sync tier
  (``kv_offload="sync"`` / FLAGS_serve_kv_offload_sync).
- Draft-model speculative decoding (optional, r13): the engine hosts a
  SECOND, smaller llama (``draft_params``/``draft_config``) whose KV
  pools ride in the same pool dict under ``dk``/``dv`` keys, indexed by
  the SAME physical block ids as the target pools — one block backs
  both models' KV for its token range, so the block ledger, the prefix
  cache's spill/restore, preemption swap and crash recovery all cover
  the draft for free. Per greedy decode wave the draft autoregressively
  proposes ``spec_tokens`` tokens per slot (the existing ``_paged_decode``
  program at draft scale), the target scores all proposals in ONE
  batched prefill-shaped verify call (``_spec_verify``: dense history
  gather + causal in-piece attention, greedy argmax at every position),
  and the host commits the longest agreeing prefix — decode cost per
  committed token approaches draft cost + 1/k of a verify, instead of
  one full target pass per token. Rejected-suffix KV (both pools) rolls
  back by the length invariant: positions >= ``lengths`` are never read
  and the next wave overwrites them. ``spec=False`` or no draft leaves
  the one-token path byte-identical.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import observability as _obs
from ..distributed.resilience.faults import SimulatedCrash
from ..models.llama_served import ServeOpts
from ..observability import flight_recorder as _flight
from ..observability import numerics as _nm
from ..observability import perf as _perf
from ..observability import profiling as _profiling
from ..observability import request_trace as _rt
from ..observability import timeseries as _ts
from ..observability import trace_span
from ..observability.catalog import instrument as _instrument
from ..framework.flags import get_flag
from .admission import AdmissionConfig, AdmissionController, ShedError
from .kv_swap import HostKVPool
from .offload import OffloadEngine
from .prefix_cache import PrefixCache
from .window_ledger import WindowLedger

__all__ = ["LLMEngine", "Request"]

# always-on serving telemetry (no-ops until FLAGS_obs_enabled /
# observability.enable(); names documented in observability.catalog)
_M_QUEUE_DEPTH = _instrument("serving_queue_depth")
_M_ACTIVE_SLOTS = _instrument("serving_active_slots")
_M_KV_USED = _instrument("serving_kv_pool_used_blocks")
_M_KV_BLOCKS = _instrument("serving_kv_pool_blocks")
_M_ADMISSIONS = _instrument("serving_admissions_total")
_M_PREEMPTIONS = _instrument("serving_preemptions_total")
_M_FINISHED = _instrument("serving_requests_finished_total")
_M_TOKENS = _instrument("serving_tokens_total")
_M_TTFT = _instrument("serving_ttft_seconds")
_M_TPS = _instrument("serving_tokens_per_second")
_M_STEP_SECONDS = _instrument("serving_step_seconds")
_M_STEP_HOST_SECONDS = _instrument("serving_step_host_seconds")
_M_PREFIX_BUCKET = _instrument("serving_decode_prefix_bucket")
_M_DECODE_RECOMPILES = _instrument("serving_decode_recompiles_total")
_M_KV_READ_BYTES = _instrument("serving_decode_kv_read_bytes")
_M_TPOT = _instrument("serving_tpot_seconds")
_M_DEADLINE = _instrument("serving_deadline_exceeded_total")
_M_SWAP_FALLBACK = _instrument("serving_kv_swap_fallback_total")
_M_DECODE_KERNEL = _instrument("serving_decode_kernel_total")
_M_DECODE_VARIANTS = _instrument("serving_decode_variants")
_M_SPEC_PROPOSED = _instrument("serving_spec_proposed_total")
_M_SPEC_ACCEPTED = _instrument("serving_spec_accepted_total")
_M_SPEC_ACCEPT_RATE = _instrument("serving_spec_acceptance_rate")
_M_SPEC_TOKENS_PER_WAVE = _instrument("serving_spec_tokens_per_wave")
_M_CANCEL_NOOP = _instrument("serving_cancel_noop_total")
_M_DISAGG_HANDOFFS = _instrument("serving_disagg_handoffs_total")
_M_DISAGG_SECONDS = _instrument("serving_disagg_handoff_seconds")
_M_KV_TOKEN_BYTES = _instrument("serving_kv_bytes_per_token")
_M_MOE_ROUTED = _instrument("serving_moe_routed_total")
_M_MOE_ASSIGNED = _instrument("serving_moe_assigned_total")
_M_MOE_LOAD = _instrument("serving_moe_load_max_over_mean")
_M_MOE_TILES = _instrument("serving_moe_row_tiles_total")
_M_FLASH_TILES = _instrument("serving_flash_tiles_total")
_M_STATE_SLOT_BYTES = _instrument("serving_state_bytes_per_slot")
_M_STATE_RESETS = _instrument("serving_state_resets_total")
_M_WINDOW_SLOT_BYTES = _instrument("serving_window_bytes_per_slot")
_M_WINDOW_RECYCLED = _instrument("serving_window_blocks_recycled_total")
_M_WINDOW_BOUNDED = _instrument("serving_window_bounded_tokens_total")
_M_PREFILL_PROGRAMS = _instrument("serving_prefill_programs_total")
_M_DECODE_STEPS = _instrument("serving_decode_steps_total")
_M_STARVED = _instrument("serving_device_starved_seconds_total")
_M_NO_WORK = _instrument("serving_engine_no_work_seconds_total")
_M_DRAINS = _instrument("serving_pipeline_drains_total")
_M_COUNTED_ENDS = _instrument("serving_counted_finishes_total")


@dataclasses.dataclass
class Request:
    req_id: int
    prompt: List[int]
    max_new_tokens: int = 64
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    eos_token_id: Optional[int] = None
    # latency budget in seconds from add_request; past it the request is
    # evicted (queued or mid-decode), its KV blocks freed, partial tokens
    # delivered with finish reason "deadline_exceeded". None = no deadline.
    deadline_s: Optional[float] = None
    # admission-control tenant for the per-tenant token-bucket rate limit
    tenant: str = "default"
    # absolute perf_counter deadline, stamped by add_request
    t_deadline: Optional[float] = None
    # tokens generated before a preemption; a re-admission prefills
    # prompt+generated so already-streamed tokens are never re-emitted
    # (vLLM recompute semantics)
    generated: List[int] = dataclasses.field(default_factory=list)
    # disaggregated serving (r19): key of a relay-pool KV entry spilled
    # by a prefill replica. Admission restores the entry (batched h2d
    # scatter) instead of prefilling; a missing entry degrades to a full
    # prefill of the same context — streams identical either way.
    relay_key: Optional[int] = None


# ---------------------------------------------------------------------------
# device programs
# ---------------------------------------------------------------------------
def _named(name: str, fn):
    """``fn`` under ``name`` for ``jax.jit``: a ``functools.partial`` has
    no ``__name__``, and the program would be ``jit__unknown`` in every
    trace and compile log."""
    fn.__name__ = name
    return fn


def _sample_rows(logits, key, temps, top_ks, top_ps, any_sampled=True,
                 use_top_k=True, use_top_p=True):
    """Vectorized per-row sampling: every knob is a traced [N] vector, so
    one compiled program serves any mix of greedy/sampled requests.
    temps<=0 → greedy; top_k<=0 → disabled; top_p>=1 → disabled.

    The three ``*_`` flags are STATIC: they prune program branches the
    current slot mix provably doesn't need. The full-vocab ``sort`` /
    ``argsort`` behind top-k/top-p cost ~1.5 ms each per step on a v5e —
    as much as an entire 510M decode layer stack — so an all-greedy batch
    (the common serving state) must compile to a bare argmax. The engine
    derives the flags from its active requests and keeps one compiled
    decode variant per flag tuple (≤8)."""
    N, vocab = logits.shape
    greedy = jnp.argmax(logits, axis=-1)
    if not any_sampled:
        return greedy.astype(jnp.int32)
    lg = logits / jnp.maximum(temps, 1e-6)[:, None]
    if use_top_k:
        # top-k: mask below the per-row kth value (disabled rows: k=vocab)
        eff_k = jnp.where(top_ks > 0, top_ks, vocab)
        srt = jnp.sort(lg, axis=-1)                      # ascending
        kth_idx = jnp.clip(vocab - eff_k, 0, vocab - 1).astype(jnp.int32)
        kth = jnp.take_along_axis(srt, kth_idx[:, None], axis=-1)
        lg = jnp.where(lg < kth, -1e30, lg)
    if use_top_p:
        # top-p: drop tokens outside the smallest prefix with mass >= p
        sort_idx = jnp.argsort(-lg, axis=-1)
        sort_p = jnp.take_along_axis(jax.nn.softmax(lg, axis=-1), sort_idx,
                                     axis=-1)
        cum = jnp.cumsum(sort_p, axis=-1)
        eff_p = jnp.where(top_ps < 1.0, top_ps, 1.0)
        drop_sorted = cum - sort_p >= eff_p[:, None]
        drop = jnp.zeros_like(drop_sorted).at[
            jnp.arange(N)[:, None], sort_idx].set(drop_sorted)
        lg = jnp.where(drop, -1e30, lg)
    sampled = jax.random.categorical(key, lg, axis=-1)
    return jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _apply_admissions(c_last, c_len, c_done, c_rem, wave_toks, slot_of_row,
                      lens_new, rems_new, upd_mask):
    """Scatter admitted rows' first tokens into the decode carry — a
    SINGLE compiled program: ``wave_toks`` and ``slot_of_row`` are as
    wide as a prefill program's token output (the engine's are one row),
    everything else is fixed at [max_slots] (a slot_of_row == N is
    dropped by the out-of-bounds scatter mode). The eager .at[].set
    chain this replaces re-specialized per wave size: each new size was
    a compile inside the serving hot path."""
    N = c_last.shape[0]
    scattered = jnp.zeros((N,), c_last.dtype).at[slot_of_row].set(
        wave_toks.astype(c_last.dtype), mode="drop")
    c_last = jnp.where(upd_mask, scattered, c_last)
    c_len = jnp.where(upd_mask, lens_new.astype(c_len.dtype), c_len)
    c_done = jnp.where(upd_mask, False, c_done)
    c_rem = jnp.where(upd_mask, rems_new.astype(c_rem.dtype), c_rem)
    return c_last, c_len, c_done, c_rem


def _paged_prefill(params, tokens, blk_ids, true_len, pools,
                   temps, top_ks, top_ps, key, hist_len=None,
                   ctx_tbl=None, slot=None, win=None, dec=None, *, model,
                   opts: ServeOpts = ServeOpts(),
                   sample_flags=(True, True, True), prefix_nbk: int = 0):
    """Prefill a WAVE of admissions in one compiled program: causal
    forward over the padded prompt batch, every layer's new cache entries
    written into the slots' pool blocks by ONE batched scatter per entry,
    and each request's FIRST generated token sampled in-program.

    The layer is the MODEL's (``model.prefill_layer``, the interface in
    models/llama_served.py); this program owns what every served model
    shares: the wave, the loop over the layers at static indices (a
    model's layers may differ in kind), the write-back, the head on the
    last real position, sampling.

    tokens: [B, S_bucket]; blk_ids: [B, S_bucket // bs] physical block
    ids (0 = trash block for pad rows / the pad tail); true_len: [B];
    temps/top_ks/top_ps: [B] sampling knobs; pools: the donated pool dict
    (the model's cache entries, ``{name: [L, NB, bs, ...]}``, ``L`` the
    layers an entry covers). Returns
    (first_tokens [B] int32, pools, stats) — ``stats`` is the model's
    small vector of counts for this wave (an expert layer's routed and
    assigned pairs), or None.

    The program is written over B rows, and the engine calls it with
    ONE: every admitted row, cache-hit suffix or chunk is its own call
    in its own bucket (``LLMEngine._dispatch_prefill``), so ONE compiled
    variant per (bucket, flags, history width) serves any admission mix
    and no row pays for another's padding — batch-size-shaped recompiles
    cannot exist. (A caller that does pass pad rows points all their
    blocks at the trash block and discards their token.)

    Sampling lives inside the compiled program: an eager ~15-op sampling
    pipeline plus a blocking int() per admission is host work and a sync
    on the step thread, per admission. Pad positions beyond true_len land
    in the trash block, and causality keeps them out of the
    true-last-token's context.

    Suffix/chunked prefill (``prefix_nbk > 0``, r10): the wave prefills
    only a PIECE of each row's context — tokens ``[hist_len[b],
    hist_len[b] + true_len[b])`` — against cache entries already resident
    in the pools (a matched prefix-cache path and/or this slot's earlier
    chunks). ``ctx_tbl`` [B, prefix_nbk] names the history's physical
    blocks (``model.history_blocks`` gives the width: llama's is
    power-of-two bucketed like the decode table; pad rows point at the
    trash block and mask via ``hist_len``). With ``prefix_nbk == 0`` the
    program is the original full-prompt prefill — cold traffic never pays
    for the feature. The compiled family stays bounded: (prompt bucket)
    x (<= 8 flag tuples) x (history widths).

    ``opts.prefix`` (r13 speculative decoding) selects which pool entries
    this program reads/writes: ``""`` = the target model's, ``"d"`` = the
    draft model's. The draft prefill is the SAME program over the draft
    params/model, dispatched right after the target's so both models'
    entries cover every prefilled position (the draft's sampled token is
    discarded — the target samples the stream). ``opts.mesh``: the
    engine's tp mesh, handed to the model's kernels, which shard_map
    themselves (GSPMD partitions everything else, but not a Mosaic
    kernel).

    Per-slot state (``model.state_entries``, e.g. what a short
    convolution remembers; ``{name: [layers that have one, slots + 1,
    ...]}`` in the same donated dict): ``slot`` [B] names each row's slot
    (pad rows the trash row). A row that starts its context (``hist_len``
    0: an admission, or a re-admission after a preemption, which
    recomputes the state with the tokens) begins from ZERO state, a
    continuing piece from what its predecessor left in the row's slot;
    the model returns the state after the piece's last real token under
    the entry's name and it is scattered back to the slot with the
    piece's cache entries. A layer returns either kind of entry, never
    both, and a layer with no per-token entry takes no pool.

    Window entries (``model.window_entries``: pools of the WINDOW kind,
    with a block-id space of their own, ``serving/window_ledger.py``):
    ``win`` carries their operands, ``blk_ids`` [B, S_bucket // bs] (the
    ring's columns this piece writes, as ``blk_ids`` is for the full
    kind) and, with a history, ``ctx_tbl`` [B, ring width] and
    ``ctx_start`` [B]: the blocks that hold the last ``W - 1`` tokens
    before the piece, in order, and the position of the first of them. A
    model with one kind is given no ``win`` and its program is unchanged.

    The decode rows in the same program (``dec``: ``_paged_decode``'s
    operands from ``last_tokens`` to ``eos_ids`` and, for a model with
    window entries, ``win_table``, in its order without ``params`` and
    ``pools``; ``LLMEngine._piggyback`` says when): one decode step of the
    slots rides with the piece. Every layer's token-mixing half runs for
    each kind of row as it does in its own program (``model.prefill_mix``
    over the piece, ``model.decode_mix`` over the slots: the walk, the
    one-token ring, the per-slot state), and its row-wise half
    (``model.ffn``: norm, router, experts or dense FFN, residual) ONCE
    over the piece's rows and the slots' rows laid end to end, so the
    experts' weights stream once for both. Both write-backs follow, then
    one head over the piece's last real position and the slots' rows.
    Returns (first_tokens [B], emitted [1, N], last, lengths, done,
    budgets, key, pools, stats): what both programs return, ``stats`` ONE
    vector for the whole program. With ``active`` all false it is the lone
    piece: the walks visit no block, no slot's row is routed, state or
    carry moves, and the ring lands in the trash block.
    """
    B, S = tokens.shape
    x = model.embed(params, tokens)
    aux = model.prefill_begin(params, pools, tokens, true_len, hist_len,
                              ctx_tbl, prefix_nbk, opts,
                              **({} if win is None else {"win": win}))
    if model.state_entries:
        carried = (jnp.zeros((B,), bool) if hist_len is None
                   else hist_len > 0)
        aux["state"] = {
            n: jnp.where(carried.reshape((1, B) + (1,) * (pools[n].ndim - 2)),
                         pools[n][:, slot], 0)
            for n in model.state_entries}
    if dec is not None:
        return _piece_with_decode_rows(
            params, x, aux, blk_ids, true_len, pools, temps, top_ks, top_ps,
            key, slot, win, dec, model, opts, sample_flags)
    new = []
    for l in range(model.num_layers):
        x, ent = model.prefill_layer(params, l, x, aux, pools, opts)
        new.append(ent)
    pools = _scatter_piece(model, opts, pools, new, blk_ids, slot, win, B, S)

    x = model.final_norm(params, x)
    last_h = x[jnp.arange(B), jnp.maximum(true_len - 1, 0)]
    logits = model.head(params, last_h)
    toks = _sample_rows(logits, key, temps, top_ks, top_ps, *sample_flags)
    stats = (sum(e["_stats"] for e in new) if "_stats" in new[-1] else None)
    return toks, pools, stats


def _scatter_piece(model, opts, pools, new, blk_ids, slot, win, B, S):
    """A piece's new entries into the pools: all layers' in ONE scatter per
    pool (the per-layer Pallas/XLA block appends cost ~0.6 ms of launch
    overhead each — 2L calls/prefill dwarfed the prefill math itself), and
    its per-slot state into the row's slot."""
    flat = blk_ids.reshape(-1)
    names = dict.fromkeys(n for e in new for n in e if not n.startswith("_"))
    stacked = {n: jnp.stack([e[n] for e in new if n in e]) for n in names}
    pools = dict(pools)
    for name in model.state_entries:
        pools[name] = pools[name].at[:, slot].set(
            stacked.pop(name).astype(pools[name].dtype))
    wflat = None if win is None else win["blk_ids"].reshape(-1)
    window = getattr(model, "window_entries", ())
    for name, val in model.pack_entries(stacked, opts).items():
        bs = pools[name].shape[2]
        pools[name] = pools[name].at[
            :, wflat if name in window else flat].set(
            val.reshape((val.shape[0], B * (S // bs), bs) + val.shape[3:]))
    return pools


def _piece_with_decode_rows(params, x, aux, blk_ids, true_len, pools, temps,
                            top_ks, top_ps, key, slot, win, dec, model, opts,
                            sample_flags):
    """``_paged_prefill`` from its layers on, with one decode step of the
    slots in the same program (its docstring says what is shared)."""
    (last, lens0, done, rem, dkey, active, table, dtemps, dtop_ks, dtop_ps,
     eos_ids, *win_table) = dec
    win_table = win_table[0] if win_table else None
    B, S, h = x.shape
    N = last.shape[0]
    daux = model.decode_begin(
        params, pools, table, lens0, active, 1, opts,
        **({} if win_table is None else {"win_table": win_table}))
    dkey, sub = jax.random.split(dkey)
    act = active & ~done
    xd = model.embed(params, last)                              # [N, h]
    step = model.decode_step_begin(daux, lens0, 0, 1)
    ring = model.ring_init(N, 1, opts)
    _state_into_ring(model, ring, pools, N)
    # pad positions of the piece and idle slots load no expert
    valid = jnp.concatenate([aux["valid"], act])
    new, stats = [], None
    for l in range(model.num_layers):
        x, ent = model.prefill_mix(params, l, x, aux, pools, opts)
        xd, ring = model.decode_mix(params, l, xd[:, None], daux, step, ring,
                                    0, pools, act, opts)
        rows, counts = model.ffn(
            params, l, jnp.concatenate([x.reshape(B * S, h), xd]), valid)
        x, xd = rows[:B * S].reshape(B, S, h), rows[B * S:]
        if counts is not None:
            stats = counts if stats is None else stats + counts
        new.append(ent)

    # the slots' rows first: the ring's per-slot state is every slot's as it
    # was read, moved only where ``act``, so the piece's own slot (never a
    # decode row of this program) keeps what the piece writes after it
    lens_end = lens0 + act.astype(lens0.dtype)
    pools, _ = _scatter_ring(model, opts, pools, ring, table, win_table,
                             lens0, lens_end, active, 1)
    pools = _scatter_piece(model, opts, pools, new, blk_ids, slot, win, B, S)

    # one head over the piece's last real position and the slots' rows
    last_h = x[jnp.arange(B), jnp.maximum(true_len - 1, 0)]
    logits = model.head(params, model.final_norm(
        params, jnp.concatenate([last_h, xd])))
    toks = _sample_rows(logits[:B], key, temps, top_ks, top_ps,
                        *sample_flags)
    nxt = _sample_rows(logits[B:], sub, dtemps, dtop_ks, dtop_ps,
                       *sample_flags)
    emitted = jnp.where(act, nxt, -1)[None]
    rem = rem - act.astype(rem.dtype)
    done = done | (act & (eos_ids >= 0) & (nxt == eos_ids)) \
        | (act & (rem <= 0))
    last = jnp.where(act, nxt, last)
    return toks, emitted, last, lens_end, done, rem, dkey, pools, stats


def _paged_decode(params, last_tokens, lengths, done0, budgets, key, active,
                  block_table, pools, temps, top_ks, top_ps,
                  eos_ids, win_table=None, *, model, n_steps: int,
                  opts: ServeOpts = ServeOpts(),
                  sample_flags=(True, True, True)):
    """``n_steps`` decode iterations in ONE compiled program (multi-step
    scheduling): the host loop syncs once per call instead of once per
    token. Slots that hit their eos or budget
    mid-scan flip to done (their ring entries are masked and never written
    back; their emitted entries read -1).

    The layer is the MODEL's (``model.decode_layer``); this program owns
    the scan, the in-call ring of new cache entries, the sampling epilogue
    and the ring's write-back, shared by every served model.

    Hoisted-dense structure (r4; the per-step Pallas paged-append +
    paged-attention variant measured ~0.6 ms of launch overhead per call
    × 24 calls/step — 4-5× the decode math): the slot prefixes are frozen
    for the whole call, so a model may gather them ONCE up front
    (``model.decode_begin``), the scan body runs pure fused XLA
    (attention over prefix + an in-call ring buffer written at
    the uniform step index — no scatter), and the ring is written back to
    the pools in ONE batched scatter per entry at call end.

    Ragged prefix bucketing (r6): ``block_table`` arrives SLICED to the
    engine-chosen bucket [N, MB_bucket], so P = MB_bucket * bs covers only
    ``max(lengths) + n_steps`` (rounded to a power-of-two block count) —
    the gather, the scores, and the PV contraction all scale with the
    ACTUAL ragged horizon instead of max_model_len. Exactness: every
    position >= a slot's length was masked to -1e30 before the softmax,
    so dropping it changes nothing (exp underflows to exactly 0.0).

    int8 KV pools (``opts.kv_int8``, llama): the gathered prefix stays
    int8 through the QK/PV contractions with per-entry scales applied to
    the f32 scores resp. folded into the probabilities
    (kernels/quant_matmul) — half the gather/attention KV bytes. The
    in-call ring stays model dtype and is quantized once at writeback.

    Ragged Pallas path (``opts.ragged``, r12 — the default on TPU): no
    dense hoist at all. ``block_table`` arrives at FULL width [N, mb] (one
    static shape forever) and ``lengths`` is a runtime operand: each
    step, each layer calls the model's walk kernel
    (kernels/paged_attention: ``ragged_decode_partial`` over K/V pools,
    ``latent_decode_partial`` over a latent pool), whose per-slot program
    walks the slot's block table at its TRUE length (blocks past
    ``ceil(len/bs)`` are never visited — the walk's trip count ends
    there: no DMA, no FLOPs) with an online softmax. The kernel's partial
    state (acc, m, l) merges with the in-call ring's scores via the
    flash-decoding combine — mathematically the same softmax over
    [prefix ; ring], computed blockwise. Consequences: the compile cache
    loses its prefix-bucket axis entirely (ONE variant per sampling-flag
    set), per-step cache reads scale with the tokens actually resident,
    and inactive / mid-chunk slots walk zero blocks (their lengths are
    zeroed going in). Under a tp ``mesh`` llama's kernel call is
    shard_mapped over the KV heads (r19).

    The (last, lengths, done, budgets, key) quintet is a device-resident
    carry: the engine feeds each call the previous call's outputs
    untouched while the slot composition is unchanged, so steady-state
    decode performs no h2d transfers at all. ``done`` PERSISTS across
    calls — that is what makes it safe for the engine to dispatch call
    k+1 before reading call k's tokens (speculative chaining): a slot
    that finished mid-call-k stays done in call k+1 and emits -1 padding
    instead of garbage. Call k+1's prefix gather reads call k's pool
    writeback through the donated-pool data dependency.

    eos_ids: [N] (-1 = no eos); budgets: [N] tokens each slot may still
    emit. Returns (emitted [n_steps, N] int32 with -1 padding, last,
    lengths, done, budgets, key, pools, stats) — ``stats`` as in
    ``_paged_prefill``.

    ``opts.prefix`` (r13): ``"d"`` runs this program as the speculative
    DRAFT proposal loop — draft params/model, greedy flags, the draft's
    pool entries — reusing the identical ragged/bucketed machinery at
    draft scale. Target pool entries pass through the donated dict
    untouched.

    ``win_table`` [N, ring width] (a model with ``window_entries``): the
    window kind's table, a ring — logical block b in column ``b % width``
    (``serving/window_ledger.py``). The model's window layers walk it from
    the window's edge; the ring's write-back names its columns for the
    window entries as ``block_table`` does for the others.
    """
    N, MB = block_table.shape
    S = n_steps
    lens0 = lengths                       # frozen prefix lengths
    aux = model.decode_begin(
        params, pools, block_table, lens0, active, n_steps, opts,
        **({} if win_table is None else {"win_table": win_table}))
    head_w = model.decode_head(params)

    def body(carry, t):
        last, lens, done, rem, ring, k = carry
        k, sub = jax.random.split(k)
        act = active & ~done
        x = model.embed(params, last)[:, None]              # [N, 1, h]
        step = model.decode_step_begin(aux, lens, t, S)
        for l in range(model.num_layers):
            x, ring = model.decode_layer(params, l, x, aux, step, ring, t,
                                         pools, act, opts)

        xf = model.final_norm(params, x)
        logits = model.decode_logits(params, head_w, xf[:, 0])
        nxt = _sample_rows(logits, sub, temps, top_ks, top_ps,
                           *sample_flags)
        emitted = jnp.where(act, nxt, -1)
        lens = lens + act.astype(lens.dtype)
        rem = rem - act.astype(rem.dtype)
        done = done | (act & (eos_ids >= 0) & (nxt == eos_ids)) \
            | (act & (rem <= 0))
        last = jnp.where(act, nxt, last)
        return (last, lens, done, rem, ring, k), emitted

    ring = model.ring_init(N, S, opts)
    # per-slot state rides in the carry beside the ring: the model advances
    # it only where ``act`` (an idle, mid-chunk or finished slot's must not
    # move), and it is written back once, below
    _state_into_ring(model, ring, pools, N)
    init = (last_tokens, lengths, done0, budgets, ring, key)
    (last_tokens, lens_end, done0, budgets, ring, key), \
        emitted = jax.lax.scan(body, init, jnp.arange(S))

    pools, stats = _scatter_ring(model, opts, pools, ring, block_table,
                                 win_table, lens0, lens_end, active, S)
    return (emitted, last_tokens, lens_end, done0, budgets, key, pools,
            stats)


def _state_into_ring(model, ring, pools, N):
    """A decode call's per-slot state beside the ring: the slots' rows of
    each entry, copied in here and written back whole by ``_scatter_ring``
    (kilobytes a slot). An entry that the model advances IN PLACE
    (``model.state_in_place``: megabytes a slot, which no call can copy in
    and out) is the pools' own donated buffer, trash row and all: the
    model's kernel aliases it in and out and touches the active slots'
    rows only, and what it returns IS the pools' entry."""
    in_place = getattr(model, "state_in_place", ())
    for name in model.state_entries:
        ring[name] = pools[name] if name in in_place else pools[name][:, :N]


def _scatter_ring(model, opts, pools, ring, block_table, win_table, lens0,
                  lens_end, active, S):
    """A decode call's ring into the pools, its valid entries in ONE
    scatter per pool at (block, offset), and the per-slot state back to
    its rows: ``(pools, the ring's "_stats" or None)``."""
    N, MB = block_table.shape
    stats = ring.pop("_stats", None)
    state = {name: ring.pop(name) for name in model.state_entries}
    packed = model.pack_entries(ring, opts)
    bs = pools[next(iter(packed))].shape[2]
    P = MB * bs
    cnt = lens_end - lens0                                # [N]
    j = jnp.arange(S)[None, :]
    valid = (j < cnt[:, None]) & active[:, None]          # [N, S]
    pos = jnp.minimum(lens0[:, None] + j, P - 1)
    log_blk = pos // bs
    phys = jnp.take_along_axis(block_table, log_blk, axis=1)
    phys = jnp.where(valid, phys, 0)                      # trash block 0
    off = pos % bs
    pools = dict(pools)
    window = getattr(model, "window_entries", ())
    if win_table is not None:
        phys_w = jnp.where(valid, jnp.take_along_axis(
            win_table, log_blk % win_table.shape[1], axis=1), 0)
    for name, val in packed.items():
        pools[name] = pools[name].at[
            :, phys_w if name in window else phys, off].set(val)
    in_place = getattr(model, "state_in_place", ())
    for name, val in state.items():
        pools[name] = (val if name in in_place
                       else pools[name].at[:, :N].set(val))
    return pools, stats


# ---------------------------------------------------------------------------
# host engine
# ---------------------------------------------------------------------------
def decode_path(decode_kernel: str, backend: str, model,
                kv_int8: bool = False) -> str:
    """Which decode path a model's pools are read by: ``"ragged"`` (the
    model's true-length Pallas walk) or ``"bucketed"`` (the dense gather
    over a power-of-two prefix). The ONE place the rule is written; the
    engine asks it for the target and, with a draft, for the draft model,
    whose pools have their own head dim and are never int8.

    ``"auto"`` walks where the walk is compiled and is the faster of the
    two: on a TPU ``backend``, at a shape the chip's compiler takes
    (``model.ragged_refusal(kv_int8)`` is None: bf16/f32 pool rows of a
    multiple of 128 lanes — the dense family at head dim 128, a latent
    row, head dim 64 with a token's KV heads side by side in one row; the
    dense family's ``[Hkv, D]`` rows at another head dim, and int8 pools,
    are refused). Elsewhere it gathers:
    off a TPU the walk would run in the Pallas interpreter. A path asked
    for by name is taken, except that ``"ragged"`` at a shape the TPU's
    compiler refuses raises with the compiler's message: no request falls
    to another path unasked."""
    if decode_kernel not in ("auto", "ragged", "bucketed"):
        raise ValueError(
            "decode_kernel must be 'auto', 'ragged' or 'bucketed', got "
            f"{decode_kernel!r}")
    on_tpu = backend == "tpu"
    refusal = on_tpu and model.ragged_refusal(kv_int8)
    if decode_kernel == "auto":
        return "ragged" if on_tpu and not refusal else "bucketed"
    if decode_kernel == "ragged" and refusal:
        raise NotImplementedError(
            f"decode_kernel='ragged' for {type(model.config).__name__}, "
            f"kv_int8={kv_int8} does not compile for TPU: {refusal}")
    return decode_kernel


class LLMEngine:
    """Continuous-batching serving loop.

    >>> eng = LLMEngine(params, config, max_slots=4)
    >>> eng.add_request([1, 2, 3], max_new_tokens=32)
    >>> outputs = eng.run()          # {req_id: [generated tokens...]}

    ``step()`` advances one decode step (admitting queued requests first)
    and returns the (req_id, token) pairs emitted — the streaming hook.
    """

    def __init__(self, params, config, max_slots: int = 4,
                 block_size: int = 16, max_model_len: int = 512,
                 num_blocks: Optional[int] = None,
                 prompt_buckets: Optional[List[int]] = None, seed: int = 0,
                 mesh=None, decode_steps: int = 1, kv_dtype=None,
                 admission=None, kv_swap_bytes: int = 0, injector=None,
                 prefix_cache: bool = False, prefill_chunk: int = 0,
                 prefix_cache_host_bytes: int = 0,
                 decode_kernel: str = "auto",
                 draft_params=None, draft_config=None,
                 spec_tokens: int = 4, spec: bool = True,
                 kv_offload: str = "auto", role: str = "both",
                 relay: Optional[HostKVPool] = None):
        """``params`` may be dense (bf16/f32) or int8 weight-only
        (llama.quantize_params) — quantized leaves feed the decode/prefill
        matmuls unconverted (kernels/quant_matmul.weight_only_matmul).

        ``mesh``: an optional jax Mesh with a 'tp' axis — weights take
        the model's Megatron shardings (llama.make_serving_shardings;
        int8 qweights + scales shard with the same specs as their dense
        counterparts), the KV pools shard their kv-head dim over 'tp',
        and GSPMD inserts the serving collectives (the reference's
        multi-GPU serving via mp_degree). The ragged decode kernel
        shard_maps its block walk over the sharded KV heads (r19), and
        spec decode composes by running the DRAFT replicated (params
        and dk/dv pools carry P()) while the verify rides the sharded
        prefill-shaped program — greedy streams stay bit-identical to
        the unsharded engine's across every path.

        ``decode_steps``: decode iterations fused into one compiled call
        (multi-step scheduling). 1 = a host sync per token (exact
        admission granularity); larger values amortize the host's
        per-call work and its readback — admission and slot reclamation
        then happen every K tokens.

        ``kv_dtype``: ``None`` keeps the pools in the model dtype;
        ``"int8"`` quantizes them with per-entry scales (dequant fused
        into the bucketed attention contractions) — half the decode KV
        traffic and double the effective block capacity at the same HBM.

        ``admission``: an :class:`AdmissionConfig` (or a prebuilt
        :class:`AdmissionController`) enabling load shedding —
        ``add_request`` raises :class:`ShedError` (typed: queue_full /
        rate_limited / pool_pressure) instead of queueing unboundedly
        under sustained overload. ``None`` admits everything.

        ``kv_swap_bytes``: capacity of the pinned host-RAM KV swap tier
        (:mod:`paddle_tpu.serving.kv_swap`). Non-zero turns preemption
        from recompute into swap: the victim's pool blocks move to host
        memory and re-admission restores them bit-exactly with one h2d
        copy instead of a full re-prefill; recompute remains the
        fallback when the host pool is full. 0 keeps pure recompute.

        ``injector``: a resilience ``FaultInjector`` whose serving kinds
        (``readback_fail`` / ``slow_step`` / ``pool_squeeze``, keyed by
        engine step index) fire inside the step loop — the seeded chaos
        surface behind ``tools/chaos_run.py --serving`` and
        :class:`~paddle_tpu.serving.resilient.ResilientEngine`.

        ``prefix_cache``: a refcounted radix index over the block pool
        (:mod:`paddle_tpu.serving.prefix_cache`) — ``add_request``
        matches the longest cached prefix at block granularity, pins
        those blocks into the slot's table, and prefills ONLY the
        suffix. Cached blocks are LRU-evicted at refcount 0 under pool
        pressure, spilling to a pinned host tier of
        ``prefix_cache_host_bytes`` (0 = drop instead of spill) and
        restoring on a later match.

        ``prefill_chunk``: split suffix prefills longer than this many
        tokens into fixed-size chunks (rounded up to a block-size
        multiple), one chunk per engine step, interleaved with the
        decode waves of the other slots — a long prefill stops
        monopolizing a step, so TTFT stays bounded under mixed traffic.
        0 = one-shot suffix prefill (the pre-r10 behavior).

        ``decode_kernel``: which decode attention path serves the slots
        (r12). ``"ragged"`` — the Pallas true-length block-walk kernel
        (kernels/paged_attention.ragged_decode_partial): lengths become
        a runtime operand, the block table ships at full width, and the
        decode compile cache collapses to ONE variant per (batch,
        sampling-flags) set. ``"bucketed"`` — the r6 host-side
        power-of-two prefix buckets over the hoisted dense gather.
        ``"auto"`` (default) picks ragged on a TPU backend — sharded or
        not — for the shapes Mosaic compiles (bf16/f32 pools whose rows
        are a multiple of 128 lanes: head dim 128 for the dense family,
        head dim 64 where a row holds all of a token's KV heads as
        models/lfm2_moe.py keeps them: ``kernels.paged_attention.
        ragged_tpu_refusal``) and bucketed elsewhere (other shapes; and
        off-TPU, where the kernel would run in the Pallas interpreter —
        correct but slow); the choice is counted per dispatch in
        ``serving_decode_kernel_total{path}``, never silent.
        ``"ragged"`` at a shape its walk is refused for raises here
        with the compiler's message; :func:`decode_path` is the rule,
        asked for the target and for a draft model separately. The
        supported mesh matrix (r19): ragged and bucketed both compose
        with a 'tp' mesh (ragged shard_maps the block walk over the KV
        heads; bucketed shards through its plain gathers/dots), and
        spec decode runs its draft replicated under the mesh.
        Both paths share admission, writeback, preemption, the prefix
        cache, chunked prefill, swap and the numerics probes; greedy
        token streams are parity-tested identical.

        ``draft_params`` / ``draft_config``: a second, smaller llama —
        the speculative DRAFT (r13). Greedy decode waves then run
        draft-then-verify: the draft proposes ``spec_tokens`` tokens per
        slot (one multi-step draft call), the target verifies all of
        them in one prefill-shaped batched call, and the longest
        agreeing prefix commits — up to ``spec_tokens`` tokens per
        target forward, token streams EXACTLY the non-speculative
        greedy streams. The draft must share the target's vocabulary;
        its KV pools ride in the same pool dict (``dk``/``dv``) over
        the same physical blocks, so the ledger, prefix cache, swap
        tier and crash recovery need no draft-aware changes. Waves with
        any sampled (temperature>0) slot, or slots whose draft KV fell
        behind, fall back to the normal decode path — never wrong,
        at worst unaccelerated. ``spec=False`` disables the machinery
        entirely (no draft pools, byte-identical engine).

        Pipelining caveat: the engine dispatches call k+1 before reading
        call k's tokens only when nothing in call k can surprise the
        host (``_spec_safe``) — which requires ``eos_token_id`` unset,
        since an eos can finish a slot at any step (a budget's end can
        be counted ahead and drains nothing). Workloads where every
        request carries an eos run with a synchronous readback between
        decode calls instead; ``decode_steps`` remains the amortization
        lever there.
        Speculative waves are the exception either way: acceptance is a
        host decision, so a spec wave DRAINS the pipeline and syncs
        once per wave — the draft/verify pair replaces multi-step
        chaining as the round-trip amortizer (and, unlike the chained
        path, composes with per-request eos).

        ``kv_offload`` (r15): how the host tiers move their bytes.
        ``"async"`` — swap-outs and prefix-cache spills dispatch
        non-blocking d2h (blocks stay accounted until the transfer
        lands at a step boundary), queued restores prefetch h2d into
        staging buffers ahead of admission, and refcount-0 cached
        blocks spill proactively under pool pressure
        (:mod:`paddle_tpu.serving.offload`). ``"sync"`` — the pre-r15
        inline transfers (the parity-test reference). ``"auto"``
        (default) follows ``FLAGS_serve_kv_offload_sync``. Greedy token
        streams are bit-identical either way (test-enforced, bf16 and
        int8); only the stall profile differs. Ignored when no host
        tier is configured.

        ``role`` / ``relay`` (r19, disaggregated serving): ``role``
        declares which phase of a request this engine serves —
        ``"both"`` (default: the colocated engine), ``"decode"``
        (identical engine behavior; a placement hint for the
        ReplicaRouter, which keeps fresh prefills off it when a
        prefill-capable replica is healthy), or ``"prefill"``: the
        engine runs admission + (chunked) prefill ONLY — as soon as a
        slot's first token is host-visible it spills the slot's pool
        blocks (payload + scales bit-exact, the swap-out d2h path) into
        the shared host ``relay`` pool (``HostKVPool(kind="relay")``)
        keyed by request id, frees the slot, and finishes the request
        with reason ``"handoff"`` — partial result: the first token.
        A decode/both engine admitting a request whose ``relay_key``
        finds a relay entry restores it via the batched h2d scatter
        instead of prefilling (the swap-in path); a missing or
        incomplete entry degrades to a full prefill of the same context
        — greedy streams are bit-identical to a colocated engine's
        either way (test-enforced, bf16 and int8). ``role="prefill"``
        requires a ``relay``."""
        c = config
        assert max_model_len % block_size == 0
        if not hasattr(config, "served_model"):
            raise TypeError(
                f"{type(config).__name__} names no served model: the "
                "engine runs a model through the interface that "
                "models/llama_served.py states (config.served_model())")
        self.model = model = config.served_model()
        # what the model cannot do yet is refused here, with the reason:
        # no option falls back to another path unasked
        asked = {"spec": spec and draft_params is not None,
                 "prefix_cache": bool(prefix_cache),
                 "kv_swap": bool(kv_swap_bytes),
                 "mesh": mesh is not None,
                 "kv_int8": kv_dtype is not None,
                 "disagg": role != "both" or relay is not None,
                 "decode_steps": int(decode_steps) > 1}
        for feature, on in asked.items():
            if on and feature in model.unsupported:
                raise NotImplementedError(
                    f"{type(model).__name__} does not support {feature}: "
                    + model.unsupported[feature])
        self.params = params
        self.config = config
        self.N = max_slots
        self.bs = block_size
        self.mb = max_model_len // block_size      # logical blocks per slot
        self.max_model_len = max_model_len
        # +1: physical block 0 is the trash block for idle slots
        self.nb = (num_blocks if num_blocks is not None
                   else max_slots * self.mb) + 1
        self.buckets = sorted(prompt_buckets or
                              [b for b in (64, 128, 256, 512)
                               if b <= max_model_len] or [max_model_len])
        if self.buckets[-1] < max_model_len:
            # re-admission after preemption prefills prompt+generated, which
            # can reach max_model_len — it must always have a bucket
            self.buckets.append(max_model_len)
        for b in self.buckets:
            if b % block_size:
                raise ValueError(
                    f"prompt bucket {b} is not a multiple of "
                    f"block_size {block_size}")
        if kv_dtype not in (None, "int8", jnp.int8):
            raise ValueError(
                f"kv_dtype must be None or 'int8', got {kv_dtype!r}")
        self.kv_int8 = kv_dtype is not None
        # the model's cache entries, one pool each: per-head K and V rows
        # for llama, one latent row for a latent-attention model
        # a model whose layers differ in what they keep of a context names
        # its entries of the WINDOW kind (a layer that sees ``window``
        # tokens back keeps a ring of ceil(window / bs) + 1 blocks a slot,
        # whatever the context): those get an id space of their own in
        # which every slot owns its ring for good (``window_ledger.py``:
        # nothing to allocate, nothing that runs dry, a table that never
        # changes). A model with one kind has one ledger, as before
        self.win: Optional[WindowLedger] = None
        self._wtable_dev = None
        if getattr(model, "window_entries", ()):
            self.win = WindowLedger(self.N, model.window, block_size)
            self._wtable_dev = jnp.asarray(self.win.table)
            self.pools = model.make_pools(self.nb, block_size, self.kv_int8,
                                          nb_window=self.win.nb)
        else:
            self.pools = model.make_pools(self.nb, block_size, self.kv_int8)
        self._target_pools = tuple(self.pools)
        # the model's per-slot entries ride in the same donated dict: one
        # row a slot and a trash row, indexed by slot and never by block
        # (the block ledger, block bytes and the cache-traffic estimates
        # count the per-token entries only)
        self._state_bytes_per_slot = 0
        if model.state_entries:
            state = model.make_state(self.N)
            if tuple(state) != tuple(model.state_entries):
                raise ValueError("make_state must give state_entries")
            self.pools.update(state)
            self._state_bytes_per_slot = sum(
                a.nbytes // a.shape[1] for a in state.values())
        # -- speculative decoding (r13): the optional draft model --------
        self._spec_on = spec and draft_params is not None
        self.spec_k = int(spec_tokens)
        self.draft_params = draft_params if self._spec_on else None
        self.draft_config = draft_config if self._spec_on else None
        if self._spec_on:
            if draft_config is None:
                raise ValueError(
                    "draft_params requires a draft_config")
            if draft_config.vocab_size != c.vocab_size:
                raise ValueError(
                    f"draft vocab {draft_config.vocab_size} != target "
                    f"vocab {c.vocab_size} — the two models must share "
                    "a tokenizer")
            if self.spec_k < 1:
                raise ValueError(
                    f"spec_tokens must be >= 1, got {spec_tokens}")
            self.draft_model = draft_config.served_model()
            # draft KV pools share the target's physical block grid
            # (same nb/bs, same block ids): one block backs BOTH
            # models' KV for its token range, so block accounting,
            # prefix-cache spill/restore, preemption swap and crash
            # recovery cover the draft with zero new bookkeeping. Draft
            # pools stay in the draft dtype (the draft is small — int8
            # draft WEIGHTS are the bandwidth lever, not its KV).
            self.pools.update(self.draft_model.make_pools(
                self.nb, block_size, prefix="d"))
        self.mesh = mesh
        if mesh is not None:
            # tp serving (r19): target params shard Megatron-style, the
            # KV pools shard over their kv-head axis, and the ragged
            # block-walk runs under a shard_map over 'tp' (each shard
            # walks the same tables against its head slice — see
            # kernels/paged_attention.ragged_decode_partial). The spec
            # DRAFT stays replicated: its params and dk/dv pools carry
            # P() shardings (draft kv heads need not divide tp), while
            # _spec_verify reuses the sharded prefill program via GSPMD.
            self.params, self.pools, self.draft_params = model.shard(
                params, self.pools, mesh, self.draft_params)
            params = self.params
        self.free_blocks = deque(range(1, self.nb))
        self.table = np.zeros((self.N, self.mb), np.int32)
        self.n_alloc = np.zeros(self.N, np.int64)  # backed logical blocks
        self.lengths = np.zeros(self.N, np.int64)
        self.slot_req: List[Optional[Request]] = [None] * self.N
        self.slot_out: List[List[int]] = [[] for _ in range(self.N)]
        self.admit_order: List[int] = []           # slots, oldest first
        self.queue: deque = deque()
        self.results: Dict[int, List[int]] = {}
        self.cancel_noops = 0   # cancels/finishes that raced a terminal
        self._next_id = 0
        self._key = jax.random.PRNGKey(seed)
        self._prefill = {}
        self.decode_steps = max(1, int(decode_steps))
        self.decode_kernel = decode_kernel
        # what decode_path refuses (an unknown name; a walk asked for by
        # name that the TPU's compiler refuses, for either model) is an
        # error here, not at the first dispatch
        self._decode_path()
        if self._spec_on:
            self._decode_path(draft=True)
        # decode compile cache. Ragged path (r12): keyed ("ragged",
        # flags) — ONE variant per sampling-flag tuple (≤8 total; an
        # all-greedy slot mix must not pay top-k/top-p's full-vocab
        # sorts), since lengths are a runtime operand and the table
        # ships at full width. Bucketed fallback: keyed (prefix-bucket,
        # flags) — power-of-two block counts (≤ log2(mb)+2 values) × ≤8
        # flag tuples, bounded however the workload mixes lengths.
        self._decode_cache: Dict = {}
        # cumulative host estimate of decode-call KV pool traffic (see
        # _dispatch_decode) — bench evidence, kept whether or not the
        # metrics registry is enabled
        self.kv_read_bytes_total = 0
        # swap-enabled preemptions that fell back to recompute (host
        # evidence for the offload bench row: the async tier's
        # acceptance is ZERO of these under a fitting host pool)
        self.swap_fallbacks = 0
        # disagg handoff host evidence (r19, bench rows): spills this
        # prefill-role engine completed, their d2h+relay bytes/seconds
        self.handoffs = 0
        self.handoff_bytes = 0
        self.handoff_seconds = 0.0
        # device-resident decode carry (last/lengths/done/budgets/key) +
        # static per-slot vectors; the carry chains from call to call and
        # is only rebuilt from host state when the pipeline is drained
        self._carry = None
        self._slot_vecs = None
        self._slots_dirty = True
        self._table_dirty = True
        self._table_dev = {}         # prefix-bucket (blocks) → device table
        self._win_recycled = 0       # of win.recycled, what the counter has
        self._win_bounded = 0        # tokens emitted past the window, unflushed
        # the dispatched-but-unread decode call (pipeline depth 1): its
        # tokens are fetched while the NEXT call occupies the chip
        self._inflight = None
        # admissions whose in-program-sampled first token has not yet been
        # read back; attached to the next dispatch record
        self._pending_adm: List = []
        # (stats array, span attrs) of dispatched programs whose model
        # counts (an expert layer's routed/assigned pairs) are not read
        # yet; attached to the next dispatch record like _pending_adm
        self._pending_stats: List = []
        # -- the starved-time ledger (``_device_get``) ---------------------
        # programs the step thread has dispatched, and of those the newest
        # whose first token waits in ``_pending_adm``; a record remembers
        # the count at its making (``seq``)
        self._seq = 0
        self._adm_seq = 0
        # since when the device is known empty and unaccounted for (None:
        # something this engine dispatched may still run), the phase the
        # step thread is in, and whether the engine held no request then
        self._starved_t: Optional[float] = None
        self._phase = "between_steps"
        self._idle = False
        # seconds accrued since the last flush (``_step_telemetry``)
        self._starved: Dict[str, float] = {}
        self._no_work_s = 0.0
        self._step_drain: Optional[str] = None   # this step's first drain
        self._step_carried = 0    # counted ends this step read behind a call
        # observability: add_request wall time per req awaiting its first
        # host-visible token (TTFT); entries die with the request
        self._obs_t_add: Dict[int, float] = {}
        # first-token wall time per req still decoding, for TPOT at
        # finish; survives preemption (the decode clock keeps running)
        self._obs_t_first: Dict[int, float] = {}
        # seconds this step spent inside serving.readback_wait (the
        # blocking device_get calls): step wall time less this is the
        # host's own share, serving_step_host_seconds
        self._wait_s = 0.0
        # -- survivability layer (deadlines / shedding / swap / chaos) ----
        self.admission = (AdmissionController(admission)
                          if isinstance(admission, AdmissionConfig)
                          else admission)
        self.swap_pool = (HostKVPool(kv_swap_bytes) if kv_swap_bytes
                          else None)
        # -- disaggregated prefill/decode (r19) ---------------------------
        if role not in ("prefill", "decode", "both"):
            raise ValueError(
                f"role must be 'prefill', 'decode' or 'both', got "
                f"{role!r}")
        if relay is not None and getattr(relay, "kind", None) != "relay":
            raise ValueError(
                "relay must be a HostKVPool(kind='relay') shared "
                "between the prefill and decode replicas")
        if role == "prefill" and relay is None:
            raise ValueError(
                "role='prefill' requires a relay pool — the handed-off "
                "KV has to live somewhere the decode replica can reach")
        self.role = role
        self.relay = relay
        # -- async two-tier offload (r15): one transfer engine whenever
        # ANY host tier exists. "auto" defers the sync decision to
        # FLAGS_serve_kv_offload_sync
        if kv_offload not in ("auto", "async", "sync"):
            raise ValueError(
                f"kv_offload must be 'auto', 'async' or 'sync', got "
                f"{kv_offload!r}")
        self.offload = (OffloadEngine(
            sync=None if kv_offload == "auto" else kv_offload == "sync")
            if (kv_swap_bytes or (prefix_cache and prefix_cache_host_bytes))
            else None)
        # proactive-spill pressure threshold: the flag default, raised
        # to 2x the admission shed threshold when one is configured
        # (spilling must engage before shedding — one free_frac signal)
        self._spill_free_frac = float(
            get_flag("serve_kv_offload_spill_free_frac"))
        if isinstance(self.admission, AdmissionController):
            self._spill_free_frac = self.admission.spill_free_frac(
                self._spill_free_frac)
        self.injector = injector
        # terminal disposition per request id: every id that entered
        # add_request ends in exactly one of finished / shed /
        # deadline_exceeded / client_disconnected / drained (the
        # chaos-suite contract)
        self.finish_reasons: Dict[int, str] = {}
        self._step_idx = 0
        # blocks held hostage by an injected pool_squeeze, with their
        # release step — counted by block_accounting so the free+backed+
        # squeezed invariant holds THROUGH the fault
        self._squeezed: List = []
        # swap-ins whose carry lanes await their host-known state
        # ((slot, req_id); the recompute path uses _pending_adm instead)
        self._pending_swapin: List = []
        # slots (re)admitted via swap since the last dispatch: their
        # rem_start must come from host state, never the previous
        # record's chained countdown (the slot id may be recycled)
        self._fresh_swapins: set = set()
        self._swapin_cache: Dict = {}
        # requests currently carrying a deadline — the per-step expiry
        # sweep is skipped entirely at 0, so deadline-free traffic pays
        # nothing for the feature (no O(queue) scan in the hot loop)
        self._deadline_live = 0
        # rid -> reason marked by cancel_request (the HTTP front door's
        # disconnect/stall/drain hook); applied at the next step boundary
        # through the deadline-eviction machinery, so a dropped client's
        # slot and KV blocks free within one engine step. The lock makes
        # the marker handoff safe from ANY thread (a lock-free dict swap
        # could lose a marker written between the swap's load and store
        # — a disconnect that never cancels pins its KV blocks)
        self._cancels: Dict[int, str] = {}
        self._cancel_lock = threading.Lock()
        # every (rid, tok) pair committed host-side THIS step, in commit
        # order — the crash-salvage buffer: a step that raises after
        # committing tokens must still deliver them exactly once
        # (ResilientEngine returns this on recovery)
        self._step_emitted: List = []
        # -- prefix cache + chunked prefill (r10) -------------------------
        if prefill_chunk:
            # chunks start and (except the final one) end on block
            # boundaries, so cached prefixes and chunk history stay
            # block-aligned — round up rather than reject
            prefill_chunk = -(-int(prefill_chunk) // block_size) \
                * block_size
            if prefill_chunk > self.buckets[-1]:
                raise ValueError(
                    f"prefill_chunk {prefill_chunk} exceeds the largest "
                    f"prompt bucket {self.buckets[-1]}")
        self.prefill_chunk = prefill_chunk
        # Whether a step's LAST prefill piece carries the decode rows in
        # its own program (``_paged_prefill``'s ``dec``), so that a step
        # with a piece streams the experts (and every row-wise weight)
        # once and not once a program. Derived from what this engine is,
        # never set: pieces run between decode steps (``prefill_chunk``),
        # a decode call is one step, the ragged walk (one table shape for
        # good), no draft model, a role that decodes, and a model that
        # offers its layers' two halves (``prefill_mix`` / ``decode_mix`` /
        # ``ffn``, docs/served_models.md). Such an engine has no other
        # prefill program: a piece that carries nothing runs the same one
        # with ``active`` all false.
        self._piggyback = bool(
            prefill_chunk and self.decode_steps == 1 and not self._spec_on
            and role != "prefill" and self._decode_path() == "ragged"
            and all(hasattr(model, half) for half in
                    ("prefill_mix", "decode_mix", "ffn")))
        # the step's last piece, built and waiting for the decode dispatch
        # (``_launch_row``'s operand); None between steps
        self._held = None
        # a final piece that rode with the decode rows: its first token is
        # read back with that record, and its slot joins the decode rows at
        # the NEXT dispatch, which puts the token into the carry
        self._joining: List = []
        self._idle_dec = None        # a lone piece's decode operands
        # this step's decode steps by the program that ran them, for
        # _step_telemetry
        self._step_decodes = {"piece": 0, "decode": 0}
        self.prefix_cache = (
            PrefixCache(block_size,
                        HostKVPool(prefix_cache_host_bytes, kind="prefix")
                        if prefix_cache_host_bytes else None)
            if prefix_cache else None)
        # trie nodes each slot has pinned, in block-table order: the
        # first len(_pinned[slot]) table entries are cache-owned (shared,
        # never freed by the slot — unpinned instead)
        self._pinned: List[List] = [[] for _ in range(self.N)]
        # slots mid-chunked-prefill: slot -> {"ctx", "pos", "rid"};
        # excluded from decode dispatch until the final chunk lands
        self._chunks: Dict[int, Dict] = {}
        # -- speculative decoding state (r13) -----------------------------
        # per-slot draft-KV coverage: the draft participates in a spec
        # wave only while its KV covers exactly [0, lengths) — a slot
        # advanced by the NORMAL decode path (sampled mix in the wave)
        # goes stale (-1) until a re-prefill resets it. Staleness is a
        # throughput concern only: proposals from bad draft KV still
        # verify against the target, they just stop being accepted.
        self._draft_len = np.zeros(self.N, np.int64)
        self._spec_draft_cache: Dict = {}    # "ragged"|"bucketed" → draft fn
        self._spec_verify_cache: Dict = {}   # nbk → verify fn
        # host-side spec evidence (kept whether or not the metrics
        # registry is enabled — bench rows read these)
        self.spec_proposed = 0      # draft tokens offered to verify
        self.spec_accepted = 0      # of those, accepted by the target
        self.spec_committed = 0     # tokens committed by spec waves
        self.spec_waves = 0         # draft+verify wave count
        self.spec_draft_steps = 0   # draft decode steps run (waves * k)
        self.spec_verify_calls = 0  # batched target verify calls

    # -- public api ---------------------------------------------------------
    @property
    def k_pool(self):
        return self.pools["k"]

    @property
    def v_pool(self):
        return self.pools["v"]

    def add_request(self, prompt: List[int], **kw) -> int:
        # validate BEFORE minting the id: a rejected request must not
        # consume a rid, or the "every minted id ends in exactly one
        # terminal reason" contract (finish_reasons) breaks for every
        # oversize prompt a client sends — remotely reachable through
        # the HTTP front door's 400 path
        req = Request(req_id=self._next_id, prompt=list(prompt), **kw)
        if len(req.prompt) + req.max_new_tokens > self.max_model_len:
            raise ValueError(
                f"request {req.req_id}: prompt({len(req.prompt)}) + "
                f"max_new_tokens({req.max_new_tokens}) exceeds "
                f"max_model_len({self.max_model_len})")
        if len(req.prompt) > self.buckets[-1]:
            raise ValueError(
                f"request {req.req_id}: prompt length {len(req.prompt)} "
                f"exceeds the largest prompt bucket {self.buckets[-1]}")
        rid = self._next_id
        self._next_id += 1
        if req.deadline_s is not None:
            req.t_deadline = time.perf_counter() + float(req.deadline_s)
        if self.admission is not None:
            # cache-aware pressure: refcount-0 cached blocks are
            # reclaimable (spill/drop), so they count as headroom — a
            # full-looking pool of evictable prefixes must not shed
            reason = self.admission.check(
                req, queue_depth=len(self.queue),
                free_frac=self._avail_blocks() / max(1, self.nb - 1))
            if reason is not None:
                # reject-newest load shedding: fail THIS request in
                # microseconds (typed, maps to HTTP 429/503) so the
                # admitted ones keep their latency
                self.finish_reasons[rid] = "shed"
                _flight.record("request_shed", req_id=rid, reason=reason)
                if _obs.enabled():
                    tracer = _rt.get_request_tracer()
                    tracer.submit(rid, prompt_tokens=len(req.prompt),
                                  max_new_tokens=req.max_new_tokens,
                                  tenant=req.tenant)
                    tracer.finish(rid, tokens=0, reason="shed",
                                  shed_reason=reason)
                raise ShedError(reason, rid)
        self.queue.append(req)
        if self._starved_t is not None:
            self._mark(self._phase)     # an empty engine has work again
        if req.t_deadline is not None:
            self._deadline_live += 1
        if _obs.enabled():
            self._obs_t_add[rid] = time.perf_counter()
            _M_QUEUE_DEPTH.set(len(self.queue))
            # the request_id minted here IS the distributed-trace id: it
            # follows the request through slots, preemptions and
            # re-admissions (observability.request_trace); the tenant
            # rides the meta into the summary (obs_dump --requests)
            _rt.get_request_tracer().submit(
                rid, prompt_tokens=len(req.prompt),
                max_new_tokens=req.max_new_tokens, tenant=req.tenant)
        return rid

    def has_work(self) -> bool:
        return bool(self.queue) or any(r is not None for r in self.slot_req)

    def run(self) -> Dict[int, List[int]]:
        while self.has_work():
            self.step()
        if self._inflight is not None:      # defensive: step() drains first
            self._process_inflight("run_end")
        self.drain_offload()                # land stragglers: in_flight→0
        return self.results

    # -- internals ----------------------------------------------------------
    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(f"prompt length {n} exceeds largest bucket "
                         f"{self.buckets[-1]}")

    def _prefill_fn(self, bucket: int, flags, prefix_nbk: int = 0,
                    draft: bool = False):
        if draft:
            # draft prefill: greedy flags always (its sampled token is
            # discarded) so the draft never multiplies the flag axis
            flags = (False, False, False)
        # target keys are (bucket, flags, history width): every program
        # takes ONE row, so no batch form is part of the key; the draft
        # adds a parallel family, one tag deeper
        key = ((bucket, flags, prefix_nbk) if not draft
               else (bucket, flags, prefix_nbk, "draft"))
        fn = self._prefill.get(key)
        if fn is None:
            # the numerics gate is baked at variant-compile time (the
            # probes are trace-time ops): variants compiled while
            # FLAGS_obs_numerics was off keep their compiled form —
            # flip the flag before the engine serves to instrument
            fn = jax.jit(_named("paged_prefill", functools.partial(
                             _paged_prefill,
                             model=(self.draft_model if draft
                                    else self.model),
                             sample_flags=flags,
                             opts=ServeOpts(
                                 kv_int8=self.kv_int8 and not draft,
                                 numerics=(self.kv_int8 and not draft
                                           and _nm.active()),
                                 ragged=self._piggyback,
                                 prefix="d" if draft else "",
                                 mesh=self.mesh),
                             prefix_nbk=prefix_nbk)),
                         donate_argnums=(4,))
            self._prefill[key] = fn
        return fn

    # -- block allocation over the free list + the prefix cache ------------
    def _avail_blocks(self) -> int:
        """Blocks an allocation could obtain right now: the free list
        plus every refcount-0 cached block (reclaimable by spill/drop)."""
        n = len(self.free_blocks)
        if self.prefix_cache is not None:
            n += self.prefix_cache.evictable_blocks
        return n

    def _take_up_to(self, k: int) -> List[int]:
        """Pop up to ``k`` free blocks, reclaiming from the prefix cache
        (LRU spill-then-drop) when the free list runs short — ONE
        reclaim sweep and one batched d2h however many blocks are
        needed, never a sweep per block."""
        if len(self.free_blocks) < k and self.prefix_cache is not None:
            self.free_blocks.extend(self.prefix_cache.reclaim(
                k - len(self.free_blocks), self._fetch_blocks))
        out: List[int] = []
        while self.free_blocks and len(out) < k:
            out.append(self.free_blocks.popleft())
        return out

    def _fetch_blocks(self, blks: List[int]) -> Dict:
        """d2h a batch of blocks from every pool entry in one gather per
        entry (payload AND scales under int8 pools — the spill/restore
        round-trip is bit-exact). Returns arrays stacked on the block
        axis, the layout :meth:`PrefixCache.reclaim` slices per node."""
        idx = np.asarray(blks, np.int32)
        return {name: np.asarray(jax.device_get(pool[:, idx]))
                for name, pool in self.pools.items()}

    def _restore_blocks(self, blks: List[int], ents: List) -> None:
        """h2d a matched path's spilled blocks in ONE batched scatter
        (the kv_swap restore at block count len(blks), pools donated) —
        never a transfer per block on the admission path. Entries the
        offload engine staged ahead of time (``SwapEntry.staged``, r15)
        contribute device-resident buffers (prefetch hits); the rest
        start their h2d here and the observed wait counts as a stall."""
        names = sorted(ents[0].data)
        staged_i = [i for i, e in enumerate(ents) if e.staged is not None]
        fresh_i = [i for i, e in enumerate(ents) if e.staged is None]
        # reorder entries staged-first WITH their blocks (the scatter
        # pairs blks[i] with slice i, so any consistent permutation is
        # exact) — every fresh payload then batches into ONE host-side
        # concat + one h2d per pool entry, the r10 contract, whatever
        # mix of staged/unstaged the path carries
        blks = [blks[i] for i in staged_i + fresh_i]
        t0 = time.perf_counter()
        fresh_up = {}
        if fresh_i:
            fresh_up = {n: jnp.asarray(np.concatenate(
                [np.asarray(ents[i].data[n]) for i in fresh_i], axis=1)
                if len(fresh_i) > 1 else np.asarray(
                    ents[fresh_i[0]].data[n])) for n in names}
        if self.offload is not None and fresh_i:
            if not self.offload.sync:
                # async miss: observe the true inline wait. Sync mode
                # skips the barrier — the pre-r15 behavior let the
                # transfer overlap into the scatter dispatch, and the
                # forced-sync leg is the bench baseline for exactly
                # that behavior (dt then measures the host-side cost)
                jax.block_until_ready(list(fresh_up.values()))
            self.offload.note_stall(time.perf_counter() - t0,
                                    n=len(fresh_i))
        if self.offload is not None and staged_i:
            self.offload.note_hit(len(staged_i))
        stacked = {}
        for n in names:
            parts = [ents[i].staged[n] for i in staged_i]
            if fresh_i:
                parts.append(fresh_up[n])
            stacked[n] = (jnp.concatenate(parts, axis=1)
                          if len(parts) > 1 else parts[0])
        for i in staged_i:
            ents[i].staged = None
        self.pools = self._swapin_fn(len(blks))(
            self.pools, jnp.asarray(np.asarray(blks, np.int32)),
            *[stacked[n] for n in names])

    def _free_slot(self, slot: int, requeue: bool = False,
                   reason: str = "finished", swap: bool = True):
        req = self.slot_req[slot]
        out = self.slot_out[slot]
        swapped, held = False, []
        if requeue and req is not None and swap \
                and self.swap_pool is not None:
            # swap-instead-of-recompute: move the victim's blocks to the
            # host tier BEFORE they are freed (fallback: plain recompute;
            # async mode parks `held` with the in-flight transfer)
            swapped, held = self._swap_out(slot, req, out)
            if not swapped:
                self.swap_fallbacks += 1
        # blocks [0, keep) are cache-owned: shared, unpinned below, never
        # freed here. A finishing request first offers its decode-grown
        # FULL blocks to the trie (multi-turn prefix reuse: the next turn
        # re-sends prompt+answer and matches them) — adopted blocks
        # transfer ownership to the cache instead of the free list.
        keep = len(self._pinned[slot])
        if not requeue and req is not None and reason == "finished" \
                and self.prefix_cache is not None:
            # KV is valid for the first self.lengths positions only (the
            # final emitted token's KV was never written)
            full = int(self.lengths[slot]) // self.bs
            if full > keep:
                ctx_all = req.prompt + req.generated + out
                adopted = self.prefix_cache.extend(
                    ctx_all, keep,
                    [int(self.table[slot, j]) for j in range(keep, full)],
                    pin=False)
                keep += len(adopted)
        held_set = set(held)
        for j in range(keep, int(self.n_alloc[slot])):
            blk = int(self.table[slot, j])
            if blk not in held_set:     # custody: frees when the spill lands
                self.free_blocks.append(blk)
        if self._pinned[slot]:
            self.prefix_cache.unpin(self._pinned[slot])
            self._pinned[slot] = []
        self._chunks.pop(slot, None)
        if self.win is not None:
            self.win.release(slot)
        self.table[slot, :] = 0
        self.n_alloc[slot] = 0
        self.lengths[slot] = 0
        self._draft_len[slot] = 0
        self.slot_req[slot] = None
        if slot in self.admit_order:
            self.admit_order.remove(slot)
        self.slot_out[slot] = []
        self._table_dirty = True
        self._slots_dirty = True
        # an admission whose first token was never read back dies with the
        # slot (recompute semantics: re-admission prefills and re-samples)
        self._pending_adm = [e for e in self._pending_adm if e[0] != slot]
        self._joining = [e for e in self._joining if e[0] != slot]
        if self._held is not None and self._held[0][0] == slot:
            self._held = None         # a piece built and not yet dispatched
        self._pending_swapin = [e for e in self._pending_swapin
                                if e[0] != slot]
        self._fresh_swapins.discard(slot)
        if requeue and req is not None:
            # preemption: carry generated tokens so re-admission continues
            # from prompt+generated — streamed tokens stay valid and are
            # never re-emitted (swap-in restores their KV; recompute
            # re-prefills it)
            req.generated.extend(out)
            self.queue.appendleft(req)
            _M_PREEMPTIONS.inc()
            _flight.record("preemption", req_id=req.req_id,
                           generated=len(req.generated), swapped=swapped)
            if _obs.enabled():
                _rt.get_request_tracer().record(
                    req.req_id, "preempt", slot=slot,
                    generated=len(req.generated), swapped=swapped)
        elif req is not None:
            self.results[req.req_id] = req.generated + out
            self.finish_reasons[req.req_id] = reason
            if req.t_deadline is not None:
                self._deadline_live = max(0, self._deadline_live - 1)
            if self.swap_pool is not None:
                self.swap_pool.discard(req.req_id)
                if self.offload is not None:
                    # an in-flight spill for a terminal request is moot:
                    # drop it, reclaim its custody blocks now
                    self.free_blocks.extend(
                        self.offload.cancel(req.req_id))
            if reason == "deadline_exceeded":
                _M_DEADLINE.inc()
                _flight.record("deadline_exceeded", req_id=req.req_id,
                               tokens=len(self.results[req.req_id]))
            elif reason != "finished":
                # front-door cancellation (client_disconnected / drained):
                # terminal, partial tokens delivered, but NOT a completed
                # request — the finished counter must not absorb it
                _flight.record(reason, req_id=req.req_id,
                               tokens=len(self.results[req.req_id]))
            else:
                _M_FINISHED.inc()
            now = time.perf_counter()
            t_first = self._obs_t_first.pop(req.req_id, None)
            # a request that finishes in the SAME step its first token
            # became host-visible retires before step()'s TTFT loop runs —
            # its first token is host-visible right now, so observe here.
            # No TPOT for it: first-visibility and finish coincide, so
            # there is no decode cadence to measure (an exact-0
            # observation would drag the SLO gauge optimistically)
            t_add = self._obs_t_add.pop(req.req_id, None)
            tracer = _rt.get_request_tracer() if _obs.enabled() else None
            if t_add is not None and (req.generated or out):
                if tracer is not None:
                    tracer.record(req.req_id, "first_token")
                _rt.observe_with_exemplar(_M_TTFT, now - t_add,
                                          req.req_id)
            elif t_first is not None:
                # TPOT = decode latency after first-token visibility, per
                # subsequent token (the depth-1 pipeline batches
                # readbacks; the histogram tracks steady-state cadence)
                n_out = len(req.generated) + len(out)
                if n_out > 1:
                    _rt.observe_with_exemplar(
                        _M_TPOT, (now - t_first) / (n_out - 1),
                        req.req_id)
            if tracer is not None:
                tracer.finish(req.req_id,
                              tokens=len(self.results[req.req_id]),
                              reason=reason)

    # -- survivability: swap, deadlines, chaos ------------------------------
    def _swap_out(self, slot: int, req: Request,
                  out: List[int]) -> Tuple[bool, List[int]]:
        """Copy the slot's live KV blocks to the host tier. Keeps
        ``len(ctx) - 1`` positions where ``ctx = prompt + generated +
        out``: the context tail is the re-admission's next decode input,
        whose K/V the first restored decode step rewrites — so a slot
        whose sampled-but-unread first token died with it (KV covers ALL
        of ctx) and a mid-decode victim (KV covers ctx[:-1]) restore
        through one invariant.

        Returns ``(swapped, held)``. Async mode (r15) dispatches a
        NON-BLOCKING d2h and parks the victim's private blocks in the
        offload engine's custody (``held`` — the ledger's transient
        ``in_flight`` term; cache-pinned head blocks stay ``cached``,
        the transfer reads them safely by stream order): the step
        thread never waits on the spill, and the blocks return to the
        free list at the step boundary after it lands. Sync mode blocks
        inline and holds nothing. ``swapped=False`` on fallback (host
        pool full / nothing to keep) — the caller then recomputes."""
        n_keep = len(req.prompt) + len(req.generated) + len(out) - 1
        if n_keep <= 0 or self.lengths[slot] < n_keep:
            # every swap-enabled preemption lands in swap_out OR fallback
            # — an uncounted recompute would hide a swap-tier regression
            _M_SWAP_FALLBACK.inc(reason="nothing_to_keep")
            return False, []
        nb_keep = -(-n_keep // self.bs)
        blocks = np.asarray(self.table[slot, :nb_keep], np.int32)
        # both modes route through the offload engine (a swap pool
        # implies one exists): spill_async owns the sync/async decision
        # — async parks `held` in custody, sync completes inline and
        # holds nothing. Payload AND scales move verbatim either way,
        # so the restore is bit-exact (no requantization drift).
        keep = len(self._pinned[slot])
        held = ([] if self.offload.sync else
                [int(b) for b in self.table[slot, keep:nb_keep]])
        ok = self.offload.spill_async(
            req.req_id, self.pools, blocks, n_keep, self.swap_pool,
            hold_blocks=held)
        return ok, (held if ok else [])

    def _swapin_fn(self, nb: int):
        """One compiled restore per block count: scatter every host pool
        entry back into freshly allocated blocks, pools donated (the
        multi-GB pools are patched in place, never copied)."""
        fn = self._swapin_cache.get(nb)
        if fn is None:
            names = sorted(self.pools)

            def restore(pools, blk, *data):
                pools = dict(pools)
                for name, d in zip(names, data):
                    pools[name] = pools[name].at[:, blk].set(d)
                return pools

            fn = self._swapin_cache[nb] = jax.jit(restore,
                                                  donate_argnums=(0,))
        return fn

    def _swap_in(self, slot: int, req: Request, ent) -> None:
        """Re-admit a preempted request from its host-tier KV: allocate
        blocks, restore the payload, and rebuild host bookkeeping — a
        short h2d instead of a full re-prefill."""
        blocks = self._take_up_to(max(1, ent.n_blocks))
        assert len(blocks) == max(1, ent.n_blocks), \
            "swap-in allocated past _avail_blocks"
        self._pinned[slot] = []      # restored KV is slot-private
        self.table[slot, :len(blocks)] = blocks
        self.n_alloc[slot] = len(blocks)
        self.lengths[slot] = ent.n_tokens
        if self._spec_on:
            # the swap moved BOTH models' pool entries verbatim, so the
            # draft's coverage restores with the target's (a slot whose
            # draft was stale at swap-out restores stale draft KV —
            # acceptance-rate noise, never a correctness issue: every
            # proposal is target-verified)
            self._draft_len[slot] = ent.n_tokens
        self.slot_req[slot] = req
        self.admit_order.append(slot)
        self._table_dirty = True
        self._slots_dirty = True
        offload_mode = None
        if ent.n_blocks:
            names = sorted(ent.data)
            blk = jnp.asarray(np.asarray(blocks[:ent.n_blocks], np.int32))
            staged = ent.staged
            if staged is not None:
                # prefetch hit (r15): the offload engine staged this
                # entry's payload h2d ahead of admission — the scatter
                # consumes already-resident buffers, zero inline wait
                datas = [staged[n] for n in names]
                ent.staged = None
                offload_mode = "hit"
                if self.offload is not None:
                    self.offload.note_hit()
            else:
                t0 = time.perf_counter()
                datas = [jnp.asarray(ent.data[n]) for n in names]
                if self.offload is not None:
                    # the inline h2d is the stall the prefetch tier
                    # exists to hide: observe exactly what it cost.
                    # Sync mode skips the barrier — pre-r15 let the
                    # transfer overlap into the scatter dispatch, and
                    # the forced-sync leg must stay that baseline
                    if not self.offload.sync:
                        jax.block_until_ready(datas)
                    self.offload.note_stall(time.perf_counter() - t0)
                    offload_mode = "stall"
            self.pools = self._swapin_fn(ent.n_blocks)(
                self.pools, blk, *datas)
        self._pending_swapin.append((slot, req.req_id))
        self._fresh_swapins.add(slot)
        _M_ADMISSIONS.inc()
        _flight.record("kv_swap_in", req_id=req.req_id,
                       tokens=ent.n_tokens, blocks=ent.n_blocks,
                       offload=offload_mode)
        if _obs.enabled():
            kw = ({"offload": offload_mode} if offload_mode is not None
                  else {})
            _rt.get_request_tracer().admitted(
                req.req_id, slot=slot, context_tokens=ent.n_tokens,
                swapped_in=True, **kw)

    def _handoff(self, slot: int) -> None:
        """Disaggregated handoff (r19): spill the slot's prefilled KV
        blocks into the shared relay pool and finish the stream with
        reason ``"handoff"`` — the prefill replica's terminal. Keeps
        ``lengths[slot]`` positions (every prefilled token; the sampled
        first token's KV is written by the decode replica's first
        restored step — the :meth:`_swap_out` invariant with the first
        token as ``out``), so a decode replica re-admitting ``prompt +
        delivered`` finds a relay entry of exactly ``len(ctx) - 1``
        tokens: the same restore contract as a swap-in, payload +
        scales bit-exact. A capacity refusal still hands the stream off
        — the decode replica then re-prefills the identical context
        (the pool counts outcome="relay_full"; streams match either
        way, only the transfer saving is lost)."""
        req = self.slot_req[slot]
        t0 = time.perf_counter()
        n_keep = int(self.lengths[slot])
        nb_keep = -(-n_keep // self.bs)
        data = self._fetch_blocks(
            [int(self.table[slot, j]) for j in range(nb_keep)])
        ok = self.relay.put(req.req_id, data, n_keep)
        dt = time.perf_counter() - t0
        nbytes = int(sum(a.nbytes for a in data.values()))
        self.handoffs += 1
        self.handoff_bytes += nbytes
        self.handoff_seconds += dt
        if ok:
            _M_DISAGG_HANDOFFS.inc(outcome="ok")
        _M_DISAGG_SECONDS.observe(dt)
        _flight.record("kv_handoff", req_id=req.req_id, tokens=n_keep,
                       blocks=nb_keep, bytes=nbytes, relayed=ok)
        self._free_slot(slot, reason="handoff")

    def _prefill_handoffs(self):
        """The ``role="prefill"`` tail of a step (standing in for the
        decode dispatch): flush pending first tokens (host sync — a
        handoff must not outrun its stream's delivered prefix), then
        spill every slot whose prefill completed. Mid-chunk slots keep
        chunking; a request that finished ON its first token (budget 1
        or eos) already freed its slot in the flush and never relays."""
        emitted = []
        if self._pending_adm:
            adm, self._pending_adm = self._pending_adm, []
            emitted += self._flush_adm(adm, self._adm_seq)
            self._mark("serving.step")
        for slot in self._decode_slots():
            if self.slot_req[slot] is not None:
                self._handoff(slot)
        return emitted

    def _finish_expired(self, req: Request, out: List[int],
                        queued: bool,
                        reason: str = "deadline_exceeded") -> None:
        """Terminal bookkeeping for a QUEUED request evicted before any
        slot (deadline expiry or a front-door cancellation): partial
        tokens delivered, its trace closes with ``reason``. Idempotent:
        a rid that already reached a terminal reason is a counted
        no-op — never a double-free of its swap/offload state."""
        rid = req.req_id
        if rid in self.finish_reasons:
            self.cancel_noops += 1
            _M_CANCEL_NOOP.inc()
            return
        self.results[rid] = out
        self.finish_reasons[rid] = reason
        if req.t_deadline is not None:
            self._deadline_live = max(0, self._deadline_live - 1)
        if self.swap_pool is not None:
            self.swap_pool.discard(rid)
            if self.offload is not None:
                self.free_blocks.extend(self.offload.cancel(rid))
        if reason == "deadline_exceeded":
            _M_DEADLINE.inc()
        _flight.record(reason, req_id=rid, queued=queued,
                       tokens=len(out))
        self._obs_t_add.pop(rid, None)
        self._obs_t_first.pop(rid, None)
        if _obs.enabled():
            _rt.get_request_tracer().finish(
                rid, tokens=len(out), reason=reason)

    def cancel_request(self, rid: int,
                       reason: str = "client_disconnected") -> None:
        """Mark a live request for cancellation — the HTTP front door's
        hook for a dropped connection, a stalled reader, or a drain
        cutoff. Applied at the NEXT step boundary (the engine's state
        machine is single-owner per step; the marker dict write is
        atomic, so any thread may call this): queued victims finish
        immediately with their partial tokens, in-slot victims ride the
        deadline-eviction path — slot freed, KV blocks returned, the
        unread in-flight wave's lanes skipped at readback via the
        (slot, rid) snapshot check. Already-terminal rids are a
        COUNTED no-op (``cancel_noops`` / ``serving_cancel_noop_total``)
        — the router's failover path races natural finishes by design,
        and the race must never KeyError or double-free."""
        if rid in self.finish_reasons:
            self.cancel_noops += 1
            _M_CANCEL_NOOP.inc()
            return
        with self._cancel_lock:
            self._cancels[rid] = str(reason)

    def _apply_cancels(self) -> None:
        """Evict every request marked by :meth:`cancel_request` —
        queued (cheap) and in-slot (KV blocks freed within this step).
        Free when no cancellation is pending (the unlocked emptiness
        probe is safe: a marker racing past it is applied next step)."""
        if not self._cancels:
            return
        with self._cancel_lock:
            cancels, self._cancels = self._cancels, {}
        live = {req.req_id for req in self.queue} \
            | {r.req_id for r in self.slot_req if r is not None}
        kept_markers = {rid: rsn for rid, rsn in cancels.items()
                        if rid in live}
        dropped = len(cancels) - len(kept_markers)
        if dropped:
            # Markers that raced a natural finish between the write and
            # this step boundary: counted no-ops, same contract as the
            # early return in cancel_request.
            self.cancel_noops += dropped
            _M_CANCEL_NOOP.inc(dropped)
        cancels = kept_markers
        if not cancels:
            return
        if any(req.req_id in cancels for req in self.queue):
            kept = deque()
            for req in self.queue:
                if req.req_id in cancels:
                    self._finish_expired(req, list(req.generated),
                                         queued=True,
                                         reason=cancels[req.req_id])
                else:
                    kept.append(req)
            self.queue = kept
        for slot in self._active_slots():
            req = self.slot_req[slot]
            if req.req_id in cancels:
                self._free_slot(slot, reason=cancels[req.req_id])

    def _expire_deadlines(self) -> None:
        """Evict every request past its deadline — queued (cheap) and
        in-slot (KV blocks freed; the in-flight record's lanes for the
        slot are skipped at readback via the (slot, rid) snapshot
        check). Free when no live request carries a deadline."""
        if not self._deadline_live:
            return
        now = time.perf_counter()
        if any(r.t_deadline is not None and now >= r.t_deadline
               for r in self.queue):
            kept = deque()
            for req in self.queue:
                if req.t_deadline is not None and now >= req.t_deadline:
                    self._finish_expired(req, list(req.generated),
                                         queued=True)
                else:
                    kept.append(req)
            self.queue = kept
        for slot in self._active_slots():
            req = self.slot_req[slot]
            if req.t_deadline is not None and now >= req.t_deadline:
                self._free_slot(slot, reason="deadline_exceeded")

    def _apply_faults(self) -> None:
        """Release expired pool squeezes, then fire this step's injected
        serving faults (slow_step / pool_squeeze here; readback_fail at
        the readback site in :meth:`_process`)."""
        if self._squeezed:
            keep = []
            for release_step, blocks in self._squeezed:
                if self._step_idx >= release_step:
                    self.free_blocks.extend(blocks)
                else:
                    keep.append((release_step, blocks))
            self._squeezed = keep
        inj = self.injector
        if inj is None:
            return
        if inj.fires("slow_step", self._step_idx):
            _flight.record("injected_slow_step", step=self._step_idx)
            time.sleep(0.02)
        if inj.fires("pool_squeeze", self._step_idx):
            n = min(max(1, (self.nb - 1) // 2), len(self.free_blocks))
            taken = [self.free_blocks.popleft() for _ in range(n)]
            if taken:
                self._squeezed.append((self._step_idx + 2, taken))
            _flight.record("injected_pool_squeeze", step=self._step_idx,
                           blocks=len(taken))

    def _offload_tick(self) -> None:
        """The r15 step-boundary offload sweep, in three moves:

        1. **Land** — commit every finished async spill into its host
           pool and return the custody blocks to the free list (this is
           where a swap-out's ``in_flight`` blocks become ``free``).
        2. **Proactive spill** — when the allocatable-block fraction
           drops below the pressure threshold (admission's ``free_frac``
           signal), start background d2h for the coldest refcount-0
           cached blocks, so a later reclaim frees them without paying
           the transfer inline (``_take_up_to`` never runs dry into a
           blocking d2h storm).
        3. **Prefetch** — scan the first ``prefetch_depth`` queued
           requests: a swapped one's host entry, or the host-resident
           trie nodes its prompt would match, start staging h2d NOW so
           the admission-time restore is a ``prefetch_hit``.

        The seeded ``offload_crash`` chaos fault fires here — with
        transfers potentially in flight — to prove the poisoned-wave
        recovery extends to the transfer engine."""
        off = self.offload
        if off is None:
            return
        freed = off.poll()
        if freed:
            self.free_blocks.extend(freed)
        pc = self.prefix_cache
        if not off.sync:
            if pc is not None and pc.host is not None:
                frac = self._avail_blocks() / max(1, self.nb - 1)
                # one arithmetic headroom probe before the O(trie)
                # candidate sweep: a saturated host tier must not be
                # re-asked every step (doomed reserves would spam the
                # drop_host_full cause counter and re-sort the trie)
                blk_bytes = sum(
                    a.shape[0] * int(np.prod(a.shape[2:]))
                    * a.dtype.itemsize for a in self.pools.values())
                room = (pc.host.capacity_bytes - pc.host.bytes_used
                        - pc.host.reserved_bytes)
                # cap the batch by the room that actually exists, so a
                # partially-full tier never dispatches doomed reserves
                # (each would spuriously count a drop_host_full cause
                # with no drop following)
                n_spill = min(off.spill_batch(),
                              room // max(1, blk_bytes))
                if frac < self._spill_free_frac and n_spill > 0:
                    for nd in pc.spill_candidates(n_spill):
                        if not off.spill_async(
                                ("pfx", nd.uid), self.pools, [nd.block],
                                self.bs, pc.host, hold_blocks=[],
                                on_land=functools.partial(
                                    pc.finish_spill, nd),
                                proactive=True):
                            pc.abort_spill(nd)
            depth = off.prefetch_depth()
            if depth:
                for req in itertools.islice(self.queue, depth):
                    if self.swap_pool is not None:
                        ent = self.swap_pool.get(req.req_id)
                        if ent is not None:
                            off.stage(self.swap_pool, req.req_id, ent)
                            continue
                    if pc is not None and pc.host is not None:
                        ctx = req.prompt + req.generated
                        for key, ent in pc.host_path_entries(
                                ctx, (len(ctx) - 1) // self.bs):
                            off.stage(pc.host, key, ent)
        if self.injector is not None and \
                self.injector.fires("offload_crash", self._step_idx):
            _flight.record("injected_offload_crash",
                           step=self._step_idx,
                           in_flight=off.held_blocks,
                           inflight_bytes=off.inflight_bytes)
            raise SimulatedCrash(
                f"injected offload crash at serving step "
                f"{self._step_idx}")

    def drain_offload(self) -> None:
        """Land every in-flight offload transfer NOW (blocking) — the
        run()-exit / quiescence hook, so a drained engine's ledger
        shows ``in_flight == 0`` and the host tiers hold exactly their
        committed entries."""
        if self.offload is not None:
            self.free_blocks.extend(self.offload.poll(block=True))

    def recover_crashed_step(self) -> None:
        """Recovery surface for a crashed ``step()`` (ResilientEngine):
        drop the poisoned in-flight wave — its tokens were never
        host-visible, so the stream stays exactly-once — and requeue
        every in-flight request from its traced host state for a
        recompute re-admission (the pools' contents are suspect, so the
        swap tier is bypassed). The device carry is rebuilt from host
        state at the next dispatch."""
        self._inflight = None
        self._starved_t = None       # what was dispatched may still run
        self._pending_adm = []
        self._joining = []
        self._held = None
        self._pending_swapin = []
        self._fresh_swapins = set()
        self._carry = None
        self._slots_dirty = True
        for slot in self._active_slots():
            self._free_slot(slot, requeue=True, swap=False)
        self._chunks = {}
        if self.offload is not None:
            # the poisoned-wave rule extends to transfers (r15): every
            # in-flight spill is abandoned (host reservations released,
            # nothing half-landed ever commits) and its custody blocks
            # return to the free list; staged prefetch buffers drop too
            # — the queued requests re-stage or recompute
            self.free_blocks.extend(self.offload.abandon())
        if self.prefix_cache is not None:
            # cached KV is as suspect as the rest of the pools: drop the
            # whole trie (host tier included) and recycle its blocks
            self.free_blocks.extend(self.prefix_cache.clear())

    def block_accounting(self) -> Dict[str, int]:
        """Device block-pool ledger: ``free + backed + cached +
        squeezed + in_flight == total`` at every step boundary, whatever
        mix of eviction / shed / preempt-swap / cache-spill /
        crash-requeue ran — the leak-regression invariant. ``backed``
        counts blocks a slot owns PRIVATELY; a cache-owned block counts
        once under ``cached`` however many slots pin it. ``in_flight``
        (r15) counts blocks custody-parked behind an async swap-out d2h
        still moving — a TRANSIENT term that is zero whenever no
        transfer is in flight, collapsing the ledger back to its 4-term
        form (a proactively spilling cache block stays under ``cached``:
        its node keeps it until reclaim). ``host_spilled_blocks``
        (prefix-cache blocks resident only in the host tier) and
        ``swapped_host_blocks`` ride along — those blocks were freed on
        device and are NOT in the sum.

        Speculative decoding (r13) adds NO terms: the draft's ``dk``/
        ``dv`` pools are indexed by the same physical block ids as the
        target's, so every block holding draft KV already IS one of
        free/backed/cached/squeezed — the invariant is
        model-count-independent (the chaos suite asserts it per step
        with spec on)."""
        pc = self.prefix_cache
        return {
            "total": self.nb - 1,
            "free": len(self.free_blocks),
            "backed": int(sum(int(self.n_alloc[i]) - len(self._pinned[i])
                              for i in range(self.N))),
            "cached": pc.device_blocks if pc is not None else 0,
            "squeezed": sum(len(b) for _, b in self._squeezed),
            "in_flight": (self.offload.held_blocks
                          if self.offload is not None else 0),
            "host_spilled_blocks": (pc.host_blocks if pc is not None
                                    else 0),
            "swapped_host_blocks": (self.swap_pool.swapped_blocks
                                    if self.swap_pool is not None else 0),
            # the window kind's own ledger (free + backed == total), where
            # the model has one
            **({"window": self.win.accounting()}
               if self.win is not None else {}),
        }

    def _admit(self):
        """Admit every queued request a free slot and free blocks can
        take, then dispatch the wave row by row: one one-row prefill
        program a row, each in the row's own bucket
        (:meth:`_dispatch_prefill`), so the compiled-variant set is one
        per bucket — a serving burst can never hit a batch-size-shaped
        recompile, and no row is padded to another's length. NO host
        sync: each first generated token is sampled inside the row's
        program and rides to the host one decode call later
        (``_pending_adm`` → the next dispatch record).

        With the prefix cache on, each admission first matches the
        longest cached prefix at block granularity (capped at
        ``(len(ctx)-1)//bs`` so at least one token always prefills and
        yields the sampling hidden state), pins those blocks into the
        slot's table, and prefills ONLY the suffix. Suffixes longer than
        ``prefill_chunk`` enter chunked mode: the wave carries their
        first chunk and :meth:`_advance_chunks` feeds one chunk per step
        until the final chunk samples the first token."""
        wave = []           # rows: (slot, req, ctx, hist, piece, final)
        while self.queue and len(wave) < self.N:
            slot = next((i for i in range(self.N)
                         if self.slot_req[i] is None), None)
            if slot is None:
                break
            req = self.queue[0]
            ent = (self.swap_pool.get(req.req_id)
                   if self.swap_pool is not None else None)
            if ent is None and self.offload is not None \
                    and self.swap_pool is not None \
                    and self.offload.pending(req.req_id):
                # the request's swap-out is still in flight but its
                # re-admission is due NOW: land it (blocking — counted
                # as a stall) so the swap-in path sees a committed entry
                freed = self.offload.force_land(req.req_id)
                if freed:
                    self.free_blocks.extend(freed)
                ent = self.swap_pool.get(req.req_id)
            if ent is not None:
                # swap-in re-admission: restore the preempted KV blocks
                # from the host tier — no prefill, no sampled first token
                # (the tail of prompt+generated is the next decode input)
                if self._avail_blocks() < max(1, ent.n_blocks):
                    if not any(r is not None for r in self.slot_req) \
                            and not self._squeezed \
                            and not (self.offload is not None
                                     and self.offload.held_blocks):
                        raise RuntimeError(
                            f"request {req.req_id}: swap-in needs "
                            f"{ent.n_blocks} blocks but the pool only has "
                            f"{self.nb - 1} usable")
                    break                    # blocks busy: wait for frees
                self.queue.popleft()
                self._swap_in(slot, req, self.swap_pool.pop(req.req_id))
                continue
            if self.relay is not None and req.relay_key is not None:
                # disagg restore (r19): a prefill replica's relay entry
                # stands in for the whole prefill — the same batched h2d
                # scatter as a swap-in, bit-exact payload + scales. An
                # entry that vanished with its replica, or whose pool
                # names don't match this engine's (asymmetric draft
                # configs), degrades to a full prefill of the identical
                # context — streams match either way.
                rent = self.relay.get(req.relay_key)
                if rent is not None and set(rent.data) == set(self.pools) \
                        and rent.n_tokens == len(req.prompt) \
                        + len(req.generated) - 1:
                    if self._avail_blocks() < max(1, rent.n_blocks):
                        if not any(r is not None for r in self.slot_req) \
                                and not self._squeezed \
                                and not (self.offload is not None
                                         and self.offload.held_blocks):
                            raise RuntimeError(
                                f"request {req.req_id}: relay restore "
                                f"needs {rent.n_blocks} blocks but the "
                                f"pool only has {self.nb - 1} usable")
                        break            # blocks busy: wait for frees
                    self.queue.popleft()
                    self._swap_in(slot, req,
                                  self.relay.pop(req.relay_key))
                    _M_DISAGG_HANDOFFS.inc(outcome="restored")
                    continue
                self.relay.discard(req.relay_key)
                req.relay_key = None
                _M_DISAGG_HANDOFFS.inc(outcome="missing")
            ctx = req.prompt + req.generated   # re-admission continues
            true_len = len(ctx)
            nodes, cached_blocks = [], []
            if self.prefix_cache is not None:
                # longest cached prefix, pinned; host-resident blocks on
                # the path restore through the free list (one h2d each)
                nodes, cached_blocks = self.prefix_cache.match_and_pin(
                    ctx, (true_len - 1) // self.bs,
                    self._take_up_to, self._restore_blocks)
            m = len(nodes)
            hist = m * self.bs
            # only the blocks the true prompt occupies; the bucket's pad
            # tail scatters into the trash block (never read: causality)
            need = max(1, -(-true_len // self.bs)) - m
            if self._avail_blocks() < need:
                if nodes:
                    self.prefix_cache.unpin(nodes)
                if not any(r is not None for r in self.slot_req) \
                        and not self._squeezed \
                        and not (self.offload is not None
                                 and self.offload.held_blocks):
                    # (an injected pool_squeeze releases its hostage
                    # blocks in a step or two — starvation then is
                    # pressure, not an impossible request)
                    raise RuntimeError(
                        f"request {req.req_id}: prefill needs {need} blocks "
                        f"but the pool only has {self.nb - 1} usable — the "
                        "block pool is too small for this request")
                break                        # blocks busy: wait for frees
            self.queue.popleft()
            blocks = cached_blocks + self._take_up_to(need)
            self.table[slot, :len(blocks)] = blocks
            self.n_alloc[slot] = len(blocks)
            self.lengths[slot] = hist        # grows as pieces land
            self.slot_req[slot] = req
            self.admit_order.append(slot)
            self._pinned[slot] = nodes
            self._table_dirty = True
            self._slots_dirty = True
            if self.prefix_cache is not None:
                self.prefix_cache.note_lookup(hist)
            suffix = true_len - hist
            piece = (min(suffix, self.prefill_chunk)
                     if self.prefill_chunk else suffix)
            if _obs.enabled():
                # "admitted" first time, "resumed" after a preemption —
                # the tracer keys on whether this id was admitted before
                _rt.get_request_tracer().admitted(
                    req.req_id, slot=slot, context_tokens=true_len,
                    cached_tokens=hist)
            wave.append((slot, req, ctx, hist, piece,
                         piece == suffix))
        if wave:
            _M_ADMISSIONS.inc(len(wave))
            self._dispatch_prefill(wave)
        return len(wave)

    def _admit_phase(self, chunks: bool = False) -> None:
        """One ``serving.admit`` span around an admission attempt (and
        the chunk advance that leads a step's first); a call with
        nothing queued and no chunk due is not worth a span."""
        if not self.queue and not (chunks and self._chunks):
            return
        self._mark("serving.admit")
        with trace_span("serving.admit", queue=len(self.queue)) as sp:
            if chunks:
                self._advance_chunks()
            sp.attrs["wave"] = self._admit()
        self._mark("serving.step")

    def _advance_chunks(self):
        """Feed every mid-prefill slot its next chunk — ONE chunk per
        slot per step, so long prefills interleave with the other slots'
        decode waves instead of monopolizing the step (bounded TTFT
        under mixed traffic). The final chunk samples the request's
        first token and hands the slot to the decode path."""
        if not self._chunks:
            return
        rows = []
        for slot in sorted(self._chunks):
            st = self._chunks[slot]
            req = self.slot_req[slot]
            if req is None or req.req_id != st["rid"]:
                self._chunks.pop(slot)     # freed since (defensive)
                continue
            ctx, pos = st["ctx"], st["pos"]
            piece = min(self.prefill_chunk, len(ctx) - pos)
            rows.append((slot, req, ctx, pos, piece,
                         pos + piece == len(ctx)))
        if rows:
            self._dispatch_prefill(rows)

    def _dispatch_prefill(self, rows):
        """Dispatch a wave of context PIECES — full prompts, cache-hit
        suffixes and chunk continuations mix freely — as one compiled
        ONE-ROW program a row, each in its own bucket and against its
        own history width, one behind the other in this step. Dispatch
        is asynchronous: row i+1's operands are built while row i's
        program runs, and nothing drains between rows.

        Rows whose piece completes the context (``final``) keep their
        in-program-sampled first token (``_pending_adm``, one ``[1]``
        array a row, all fetched by the next record's one readback);
        chunk rows discard it and stay in ``_chunks``. The variant key
        (bucket, flags, history width) keeps the compiled family
        bounded — chunking and the cache extend the (bucket, flags)
        cache with one log-bounded axis, not a new family — and holds
        no batch form: a row never pays for a wider wave's padding
        (prompts of 300 and 900 tokens cost 729 ms as 16 rows x 1024
        and 71 ms as their own two programs, PERF.md §6 PR 31)."""
        wave = len(rows)
        if self._piggyback:
            # the step's last piece waits for the decode dispatch and goes
            # out with the decode rows; one held before it (an earlier
            # admission phase of this step) is no longer the last
            self._flush_held()
            rows, last = rows[:-1], rows[-1]
        for row in rows:
            self._launch_row(self._build_row(row, wave))
        if self._piggyback:
            self._held = self._build_row(last, wave)

    def _flush_held(self) -> None:
        """The held piece alone: nothing rides with it (no decode rows in
        this step, or a later piece took its place)."""
        if self._held is not None:
            held, self._held = self._held, None
            self._launch_row(held)

    def _build_row(self, row, wave: int):
        """One row's program, built: ``(row, bucket, flags, pnbk, args,
        kw, span attrs)``. ``wave``: rows dispatched with it in this
        step."""
        slot, req, ctx, hist, piece, final = row
        self._mark("serving.prefill_build")
        with trace_span("serving.prefill_build", wave=wave) as sp:
            bucket, flags, pnbk, args = self._prefill_operands(row)
            sp.attrs.update(bucket=bucket, batch=1)
        self._mark("serving.admit")
        # tokens: the row's real tokens in THIS program; start: what of
        # the row is already cached (a chunk is not a whole prompt)
        attrs = dict(bucket=bucket, batch=1, wave=wave,
                     prefix_bucket=pnbk * self.bs, request_ids=[req.req_id],
                     tokens=[piece], start=[hist])
        kw = {}
        if self.model.state_entries:
            # the row's slot for its per-slot state: carried from the
            # piece before, or begun from zero (the program decides by
            # hist_len; counted here by why the row starts over)
            kw["slot"] = jnp.asarray([slot], jnp.int32)
            attrs["state_in"] = hist > 0
            # the row's own state: read where carried, written once
            attrs["state_bytes"] = self._state_bytes_per_slot
            if getattr(self.model, "scan_layers", 0):
                # real tokens x the layers whose state a scan advances
                attrs["scan_tokens"] = piece * self.model.scan_layers
            if not hist:
                _M_STATE_RESETS.inc(
                    reason="preempt" if req.generated else "admit")
        if self.win is not None:
            kw["win"] = self._window_operands(row, bucket, pnbk)
            # the history tokens a window layer gathers for this piece
            attrs["hist_window"] = min(hist, self.win.W - 1)
        if _obs.enabled() and hasattr(self.model, "piece_flash_tiles"):
            # what the piece's blockwise attention will do with its tiles,
            # from the bucket and the history length alone
            for kernel, counts in self.model.piece_flash_tiles(
                    bucket, hist, pnbk, self.bs).items():
                for kind, n in zip(("interior", "edge", "skipped"), counts):
                    _M_FLASH_TILES.inc(n, kernel=kernel, kind=kind)
        return row, bucket, flags, pnbk, args, kw, attrs

    def _launch_row(self, built, dec=None, dec_flags=None, **dec_attrs):
        """A built row's call, the draft's call behind it, the host's
        bookkeeping. In an engine whose pieces carry the decode rows
        (``_piggyback``) the ONE program takes ``dec``, the decode call's
        operands, and returns what it returns: the carry and the emitted
        tokens come back to ``_dispatch_decode``; a lone piece takes the
        idle operands (``active`` all false) and its carry is dropped. The
        program's span is ``serving.prefill`` either way, with the decode
        rows it carried as ``decode_slots`` and their walk's
        ``walk_blocks`` / ``kv_bytes`` (``dec_attrs``), and there is no
        ``serving.decode`` span for the step: one pass over the weights,
        one set of the model's counts."""
        row, bucket, flags, pnbk, args, kw, attrs = built
        req = row[1]
        args[4] = self.pools          # as donated by whatever ran since
        if self._piggyback:
            if dec is None:
                dec = self._idle_rows()
                dec_attrs = dict(decode_slots=0, walk_blocks=0, kv_bytes=0)
            else:
                # one flag tuple a program: a branch either kind needs
                flags = tuple(a or b for a, b in zip(flags, dec_flags))
            kw = dict(kw, dec=dec)
            if "state_bytes" in attrs:
                # and the state of the decode rows that move with it
                attrs = dict(attrs, state_bytes=attrs["state_bytes"] * (
                    1 + dec_attrs["decode_slots"]))
            carried = "rows" if dec_attrs["decode_slots"] else "none"
            _M_PREFILL_PROGRAMS.inc(carried=carried)
            self._step_decodes["piece"] += carried == "rows"
        self._mark("serving.prefill")
        with trace_span("serving.prefill", **attrs, **dec_attrs) as sp:
            tok_dev, *rest, self.pools, stats = self._prefill_fn(
                bucket, flags, pnbk)(*args, **kw)
            seq = self._dispatched()
        if stats is not None:
            # read back with the next decode record's tokens
            self._pending_stats.append((stats, sp.attrs))
        if self._spec_on:
            # the SAME row through the draft model, right behind the
            # target's call (pools chain through donation): both models'
            # KV now cover every prefilled position, so the slot enters
            # spec waves in sync. The draft's sampled token is
            # discarded — the target owns the stream.
            self._key, dsub = jax.random.split(self._key)
            dargs = [self.draft_params] + args[1:8] + [dsub] + args[9:]
            dargs[4] = self.pools
            with trace_span("serving.prefill", bucket=bucket, batch=1,
                            wave=attrs["wave"], model="draft",
                            request_ids=[req.req_id]):
                _junk, self.pools, _st = self._prefill_fn(
                    bucket, flags, pnbk, draft=True)(*dargs)
                self._dispatched()
        self._prefill_dispatched(row, bucket, tok_dev, seq)
        return rest

    def _idle_rows(self):
        """The decode operands of a piece that carries no decode rows:
        ``active`` all false, so no slot walks, is routed or moves; made
        once."""
        if self._idle_dec is None:
            # from host arrays: a copy each, no program compiled for them
            zi = np.zeros(self.N, np.int32)
            off = np.zeros(self.N, bool)
            self._idle_dec = tuple(jnp.asarray(a) for a in (
                zi, zi, off, zi, np.zeros(2, np.uint32), off,
                np.zeros((self.N, self.mb), np.int32),
                np.zeros(self.N, np.float32), zi,
                np.ones(self.N, np.float32), np.full(self.N, -1, np.int32))
            ) + (() if self.win is None else (self._wtable_dev,))
        return self._idle_dec

    def _prefill_operands(self, row):
        """A row's program variant and its operands, from the bucket
        choice to the last host-to-device copy: ``(bucket, flags, pnbk,
        args)``. Every operand is one row wide."""
        slot, req, ctx, hist, piece, final = row
        bucket = self._bucket_for(piece)
        b0 = hist // self.bs
        # where pieces carry the decode rows, a row that starts its context
        # takes the history's operands too, at a length of 0: ONE piece
        # program a bucket to trace, load and keep, which now holds the
        # decode rows' half as well (a kernel skips the tiles past a
        # history's length, so the table's width is what it costs)
        pnbk = self.model.history_blocks(max(b0, int(self._piggyback)),
                                         self.mb)
        # only the blocks the piece occupies; the bucket's pad tail
        # scatters into the trash block (never read: causality)
        nblk = -(-(hist + piece) // self.bs) - b0
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :piece] = ctx[hist:hist + piece]
        blk_ids = np.zeros((1, bucket // self.bs), np.int32)
        blk_ids[0, :nblk] = self.table[slot, b0:b0 + nblk]
        # a non-final row samples a discarded argmax
        sampled = final and req.temperature > 0
        flags = (sampled, sampled and req.top_k > 0,
                 sampled and req.top_p < 1.0)
        self._key, sub = jax.random.split(self._key)
        args = [self.params, jnp.asarray(toks), jnp.asarray(blk_ids),
                jnp.asarray([piece], jnp.int32), self.pools,
                jnp.asarray([req.temperature if final else 0.0],
                            jnp.float32),
                jnp.asarray([req.top_k if final else 0], jnp.int32),
                jnp.asarray([req.top_p if final else 1.0], jnp.float32),
                sub]
        if pnbk:
            ctx_tbl = np.zeros((1, pnbk), np.int32)
            ctx_tbl[0, :b0] = self.table[slot, :b0]
            args += [jnp.asarray([hist], jnp.int32), jnp.asarray(ctx_tbl)]
        return bucket, flags, pnbk, args

    def _window_operands(self, row, bucket: int, pnbk: int) -> Dict:
        """The window kind's operands of a row's program: the ring's
        columns its piece writes and, with a history, the blocks that
        hold the window's reach before the piece."""
        slot, _req, _ctx, hist, piece, _final = row
        b0 = hist // self.bs
        nblk = -(-(hist + piece) // self.bs) - b0
        win = {"blk_ids": jnp.asarray(self.win.write_ids(
            slot, b0, nblk, bucket // self.bs)[None])}
        if pnbk:
            tbl, start = self.win.history(slot, hist)
            win["ctx_tbl"] = jnp.asarray(tbl[None])
            win["ctx_start"] = jnp.asarray([start], jnp.int32)
        return win

    def _prefill_dispatched(self, row, bucket, tok_dev, seq: int):
        """Host bookkeeping of a dispatched row: lengths, the pending
        first token (``seq``: its program's number), chunk state,
        timelines, prefix-cache adoption."""
        slot, req, ctx, hist, piece, final = row
        self.lengths[slot] = hist + piece
        if self.win is not None:
            self.win.note_written(slot, -(-(hist + piece) // self.bs))
        if self._spec_on:
            self._draft_len[slot] = hist + piece
        if final:
            if self._chunks.pop(slot, None) is not None:
                self._slots_dirty = True   # rejoins the decode mask
            # the row's [1] first-token array: the readback fetches all
            # of a record's arrays in one call
            self._pending_adm.append((slot, req.req_id, tok_dev))
            self._adm_seq = seq
        else:
            if slot not in self._chunks:
                self._slots_dirty = True   # leaves the decode mask
            self._chunks[slot] = {"ctx": ctx, "pos": hist + piece,
                                  "rid": req.req_id}
        if _obs.enabled():
            _rt.get_request_tracer().record(
                req.req_id, "prefill", bucket=bucket, batch=1,
                chunk_start=hist, chunk=piece)
        if self.prefix_cache is not None \
                and len(self._pinned[slot]) == hist // self.bs:
            # adopt this piece's FULL blocks into the trie (pinned:
            # the slot itself holds them); adoption stays contiguous
            # with the pinned head — a gap (another request cached
            # the same block first) ends adoption for this slot
            b0 = hist // self.bs
            full = (hist + piece) // self.bs
            if full > b0:
                self._pinned[slot].extend(self.prefix_cache.extend(
                    ctx, b0,
                    [int(self.table[slot, j]) for j in range(b0, full)],
                    pin=True))

    def _emit(self, slot: int, tok: int) -> bool:
        """Record a generated token; free the slot when the request is done.
        Returns True if the request finished."""
        req = self.slot_req[slot]
        self.slot_out[slot].append(tok)
        n_gen = len(req.generated) + len(self.slot_out[slot])
        if self.win is not None and len(req.prompt) + n_gen > self.win.W:
            # the token's context had passed the window: its window layers
            # read a ring that is being written again, not a whole context
            self._win_bounded += 1
        done = (req.eos_token_id is not None and tok == req.eos_token_id) \
            or n_gen >= req.max_new_tokens
        if done:
            self._free_slot(slot)
        return done

    def _ensure_backed(self, slot: int, lag: int = 0,
                       steps: Optional[int] = None) -> bool:
        """Back every block this slot's next ``decode_steps`` writes can
        touch (clamped to its remaining token budget — a near-finished slot
        must not reserve blocks it can never write). ``lag``: tokens the
        unread in-flight call may already have appended beyond the host's
        view of the length (pipelined dispatch); the horizon covers them
        too, since under-backing silently diverts K/V to the trash block.
        ``steps`` overrides the per-wave write horizon (a speculative
        wave commits up to ``spec_k`` tokens, not ``decode_steps``).
        Returns False if the pool is exhausted (caller preempts)."""
        req = self.slot_req[slot]
        remaining = req.max_new_tokens - len(req.generated) \
            - len(self.slot_out[slot])
        base = self.decode_steps if steps is None else steps
        steps = max(1, min(base + lag, remaining + lag))
        horizon = int(self.lengths[slot]) + steps - 1
        last_blk = min(horizon, self.max_model_len - 1) // self.bs
        if self.win is not None:
            # the window kind is backed by the slot's own ring: past its
            # width the block is written again in place
            self.win.note_written(slot, last_blk + 1)
        need = last_blk + 1 - int(self.n_alloc[slot])
        if need <= 0:
            return True
        got = self._take_up_to(need)     # one reclaim sweep for the lot
        for blk in got:
            self.table[slot, int(self.n_alloc[slot])] = blk
            self.n_alloc[slot] += 1
            self._table_dirty = True
        return len(got) == need

    def _active_slots(self):
        return [i for i in range(self.N) if self.slot_req[i] is not None]

    def _decode_slots(self):
        """Slots the decode call covers: active and not mid-chunked-
        prefill (a chunking slot joins once its final chunk lands; the
        slot of a piece held for this step's decode dispatch joins at the
        next), less the lanes that the in-flight record counted to their
        end (its ``ends``): no block is backed for them, no record behind
        it names them and no span counts them."""
        held = None if self._held is None else self._held[0][0]
        # a lane whose last token the unread call emits takes no part in
        # the call behind it (``_spec_safe``)
        ends = () if self._inflight is None else self._inflight["ends"]
        return [i for i in range(self.N) if self.slot_req[i] is not None
                and i not in self._chunks and i != held and i not in ends]

    def _spec_safe(self) -> bool:
        """True iff the step may dispatch its program BEFORE reading the
        in-flight record: nothing in that record can surprise the host.

        A budget's end is no surprise. With no ``eos_token_id`` on its
        request, the ``rem_start`` that says a lane's budget ends inside
        the in-flight call also says at which step, and the device's
        carry holds the lane ``done`` from there on (``_paged_decode``:
        it emits -1 and writes nothing in the call behind). The record
        names such lanes at its making (``ends``: ``_dispatch_decode``),
        ``_decode_slots`` leaves them out of everything the next dispatch
        does, and the record comes back through that dispatch's ``prev``,
        which emits the last token and frees the slot; the NEXT step's
        admission refills it while the device runs. What that costs is
        the freed slot's lane for one call (``decode_steps`` lane-steps),
        where a drain leaves EVERY live lane without a program for about
        a step's time (PERF.md section 6, PR 38: ~12 ms of a 23.5 ms step
        in ``rag-offline``).

        The engine still syncs first where the end is not the host's to
        count, or where nothing would run behind the record: a lane with
        an ``eos_token_id`` (any step may be its last); a lane that is
        gone or holds another request (cancelled, expired, preempted); a
        speculating engine (its waves read host state); and where the
        ended slots would sit out more lane-steps than the lanes that go
        on have in one step, ``len(ends) * decode_steps > live`` (``live``:
        the decode rows of the next dispatch, and a held piece). Every
        lane ending with no piece held is the case ``live == 0``: no
        all-done call is ever dispatched."""
        rec = self._inflight
        ends = rec["ends"]
        for slot, rid in rec["snapshot"]:
            req = self.slot_req[slot]
            if req is None or req.req_id != rid:
                return False
            if req.eos_token_id is not None:
                return False
            if slot not in ends \
                    and rec["rem_start"][slot] <= self.decode_steps:
                return False      # a speculating engine counts no end
        for slot, rid, _ in self._joining:
            # a final piece rode in the in-flight program: its first
            # token, unread, may be an eos
            req = self.slot_req[slot]
            if req is None or req.req_id != rid \
                    or req.eos_token_id is not None:
                return False
        if ends:
            live = len(self._decode_slots()) + (self._held is not None)
            return len(ends) * self.decode_steps <= live
        return True

    def _back_or_preempt(self, steps: Optional[int] = None):
        """Back upcoming writes for every active slot; preempt the newest
        admissions while the pool is short (vLLM recompute policy). With
        an unread call in flight the host length lags by up to
        decode_steps — if generous backing fails, the pipeline is drained
        so preemption decisions see exact state. ``steps`` overrides the
        write horizon (speculative waves back ``spec_k`` positions and
        run with the pipeline already drained)."""
        emitted = []
        # chunking slots never appear here (_decode_slots excludes them;
        # their whole context was preallocated at admission — nothing to
        # back until they decode)
        for slot in list(self._decode_slots()):
            if self.slot_req[slot] is None:
                continue                      # already preempted as a victim
            while True:
                in_snap = self._inflight is not None and any(
                    s == slot for s, _ in self._inflight["snapshot"])
                if self._ensure_backed(slot,
                                       self.decode_steps if in_snap else 0,
                                       steps=steps):
                    break
                if self._inflight is not None:
                    # exact lengths before evicting anyone
                    emitted += self._process_inflight("backing")
                    if self.slot_req[slot] is None:
                        break
                    continue
                if self.offload is not None \
                        and self.offload.held_blocks:
                    # blocks are custody-parked behind an in-flight
                    # spill: landing them (blocking) beats preempting
                    # ANOTHER victim — a cascade the async tier must
                    # never cause (held > 0 guarantees progress)
                    self.drain_offload()
                    continue
                victim = self.admit_order[-1]
                if victim == slot and len(self.admit_order) == 1 \
                        and not self._squeezed:
                    # alone and starved: nothing else will ever free a
                    # block — preempting ourselves would livelock. (Under
                    # an injected pool_squeeze the hostage blocks return
                    # in a step or two: self-preempt and wait instead.)
                    raise RuntimeError(
                        f"request {self.slot_req[slot].req_id}: the block "
                        f"pool ({self.nb - 1} usable blocks) is too small "
                        "to decode this request any further")
                self._free_slot(victim, requeue=True)
                if victim == slot:
                    break
        return emitted

    def _refresh_carry(self, active_slots):
        """Bring the device carry and per-slot vectors up to date.

        The carry CHAINS on device from call to call; host state is only
        injected where it is exact: a full rebuild when no call is unread
        (carry is None), or a per-slot scatter for freshly admitted slots
        (whose first token exists only on device). Freed slots are simply
        masked out via the active vector — their stale carry lanes are
        never read."""
        if self._carry is None:
            assert self._inflight is None, \
                "carry rebuild requires a drained pipeline"
            last = np.zeros(self.N, np.int32)
            budgets = np.zeros(self.N, np.int32)
            pend = {s for s, _, _ in self._pending_adm + self._joining}
            for i in active_slots:
                req = self.slot_req[i]
                # swap-in slots continue from the context tail (their KV
                # was restored, not re-prefilled); pend slots get a
                # placeholder overwritten by _apply_admissions
                last[i] = self.slot_out[i][-1] if self.slot_out[i] else \
                    (req.generated[-1] if req.generated
                     else req.prompt[-1])
                budgets[i] = req.max_new_tokens - len(req.generated) \
                    - len(self.slot_out[i]) - (1 if i in pend else 0)
            self._key, sub = jax.random.split(self._key)
            self._carry = (jnp.asarray(last),
                           jnp.asarray(self.lengths, jnp.int32),
                           jnp.zeros(self.N, bool),
                           jnp.asarray(budgets), sub)
        if self._pending_adm or self._joining:
            # one _apply_admissions call per admitted row (usually one):
            # the row's [1] token array, everything else pinned to
            # [max_slots], so nothing here can ever compile inside the
            # serving loop. ``_joining``: a final piece that rode with the
            # last dispatch's decode rows, its record already made
            c_last, c_len, c_done, c_rem, c_key = self._carry
            for s, _rid, arr in self._pending_adm + self._joining:
                upd = np.zeros(self.N, bool)
                lens_new = np.zeros(self.N, np.int32)
                rems_new = np.zeros(self.N, np.int32)
                upd[s] = True
                lens_new[s] = int(self.lengths[s])
                req = self.slot_req[s]
                rems_new[s] = req.max_new_tokens - len(req.generated) - 1
                c_last, c_len, c_done, c_rem = _apply_admissions(
                    c_last, c_len, c_done, c_rem, arr,
                    jnp.asarray([s], jnp.int32), jnp.asarray(lens_new),
                    jnp.asarray(rems_new), jnp.asarray(upd))
            self._carry = (c_last, c_len, c_done, c_rem, c_key)
        if self._pending_swapin:
            # swap-in lanes: the same [max_slots]-pinned scatter as a
            # prefill wave, but the "wave token" is host-known (the tail
            # of prompt+generated — no prefill sampled a first token).
            # Also exact after a carry-None rebuild (idempotent values).
            c_last, c_len, c_done, c_rem, c_key = self._carry
            slot_of_row = np.full(self.N, self.N, np.int32)  # N → dropped
            upd = np.zeros(self.N, bool)
            toks = np.zeros(self.N, np.int32)
            lens_new = np.zeros(self.N, np.int32)
            rems_new = np.zeros(self.N, np.int32)
            for row, (s, rid) in enumerate(self._pending_swapin):
                req = self.slot_req[s]
                if req is None or req.req_id != rid:
                    continue          # freed again before any dispatch
                slot_of_row[row] = s
                upd[s] = True
                toks[row] = (req.generated[-1] if req.generated
                             else req.prompt[-1])
                lens_new[s] = int(self.lengths[s])
                rems_new[s] = req.max_new_tokens - len(req.generated)
            self._pending_swapin = []
            if upd.any():
                c_last, c_len, c_done, c_rem = _apply_admissions(
                    c_last, c_len, c_done, c_rem, jnp.asarray(toks),
                    jnp.asarray(slot_of_row), jnp.asarray(lens_new),
                    jnp.asarray(rems_new), jnp.asarray(upd))
                self._carry = (c_last, c_len, c_done, c_rem, c_key)
        if self._slots_dirty or self._slot_vecs is None:
            temps = np.zeros(self.N, np.float32)
            top_ks = np.zeros(self.N, np.int32)
            top_ps = np.ones(self.N, np.float32)
            eos_ids = np.full(self.N, -1, np.int32)
            active = np.zeros(self.N, bool)
            for i in active_slots:
                req = self.slot_req[i]
                temps[i] = req.temperature
                top_ks[i] = req.top_k
                top_ps[i] = req.top_p
                if req.eos_token_id is not None:
                    eos_ids[i] = req.eos_token_id
                active[i] = True
            self._slot_vecs = (jnp.asarray(active), jnp.asarray(temps),
                               jnp.asarray(top_ks), jnp.asarray(top_ps),
                               jnp.asarray(eos_ids))
            self._slots_dirty = False

    def _prefix_blocks(self, active_slots) -> int:
        """Pick the decode call's prefix horizon: the smallest
        power-of-two BLOCK COUNT covering ``max(lengths) + decode_steps``
        over the active slots — from the engine's exact host lengths,
        plus the pipeline lag (an unread in-flight call may already have
        appended up to ``decode_steps`` tokens beyond the host's view for
        the slots in its snapshot). Power-of-two rounding keeps the
        compiled-variant set logarithmic in ``mb`` while amortizing
        growth recompiles."""
        prev = self._inflight
        snap = ({s for s, _ in prev["snapshot"]} if prev is not None
                else ())
        hmax = need = 0
        for i in active_slots:
            h = int(self.lengths[i]) + (self.decode_steps if i in snap
                                        else 0)
            hmax = max(hmax, h)
            need = max(need, int(self.n_alloc[i]))
        horizon = min(hmax + self.decode_steps, self.max_model_len)
        need = max(1, need, -(-horizon // self.bs))
        nbk = 1 << (need - 1).bit_length()
        return min(nbk, self.mb)        # mb >= need, so the clamp is safe

    def _decode_path(self, draft: bool = False) -> str:
        """``"ragged"`` or ``"bucketed"``: the path the next decode
        dispatch takes over the target's pools, or a speculation wave's
        draft program over the draft's (:func:`decode_path`). The
        per-dispatch label in serving_decode_kernel_total{path} refines
        bucketed to ``dense`` at the full-width bucket."""
        model, kv_int8 = ((self.draft_model, False) if draft
                          else (self.model, self.kv_int8))
        return decode_path(self.decode_kernel, jax.default_backend(),
                           model, kv_int8)

    def _pool_block_bytes(self, draft: bool = False,
                          window: bool = False) -> int:
        """Bytes one physical block occupies across one MODEL's pool
        entries and layers, whatever the entries are (K and V rows, int8
        payload + scales, latent rows). The decode cache-traffic estimates
        count the target's entries only — a draft's entries share the
        block ids but are read by the draft's own (cheaper) walks.
        ``window``: the entries of the window kind instead (a block of the
        second ledger), which the others' count leaves out."""
        wnames = getattr(self.model, "window_entries", ())
        return sum(a.shape[0] * int(np.prod(a.shape[2:])) * a.dtype.itemsize
                   for n, a in self.pools.items()
                   if n not in self.model.state_entries
                   and (n in self._target_pools) != draft
                   and (n in wnames) == window)

    def _dispatch_decode(self, active_slots, prep=None):
        """Enqueue one multi-step decode call and record it as in-flight.
        rem_start tracks each slot's EXACT remaining budget at the start
        of the call (host bookkeeping lags; this chains from the previous
        record when pipelined). ``prep``: the caller's open
        ``serving.decode_prepare`` span, ended here right at the call."""
        prev = self._inflight
        pend = {s for s, _, _ in self._pending_adm + self._joining}
        self._joining = []            # in the carry since _refresh_carry
        rem_start = {}
        for i in active_slots:
            req = self.slot_req[i]
            if i in pend:
                rem_start[i] = req.max_new_tokens - len(req.generated) - 1
            elif i in self._fresh_swapins:
                # swap-in since the last dispatch: the slot id may be
                # recycled from the previous record — its budget comes
                # from host state, never the stale chained countdown
                rem_start[i] = req.max_new_tokens - len(req.generated)
            elif prev is not None and i in prev["rem_start"]:
                rem_start[i] = prev["rem_start"][i] - self.decode_steps
            else:
                rem_start[i] = req.max_new_tokens - len(req.generated) \
                    - len(self.slot_out[i])
        # the lanes whose LAST token this call emits, by the host's own
        # count: a budget that ends inside the call and no eos that could
        # end it sooner (``_spec_safe``; a speculating engine counts none)
        ends = set() if self._spec_on else {
            i for i in active_slots if rem_start[i] <= self.decode_steps
            and self.slot_req[i].eos_token_id is None}
        if prev is not None and prev["ends"] and _obs.enabled():
            # ends carried: ``prev`` is read back behind this dispatch
            self._step_carried = len(prev["ends"])
            _M_COUNTED_ENDS.inc(self._step_carried, drained="no")
        path = self._decode_path()
        ragged = path == "ragged"
        # ragged: the table ships at FULL width — one static shape
        # forever, lengths ride as a runtime operand (no bucket axis in
        # the compile key). Bucketed: host-side power-of-two slice.
        nbk = self.mb if ragged else self._prefix_blocks(active_slots)
        if self._table_dirty:
            self._table_dev = {}
            self._table_dirty = False
        tbl = self._table_dev.get(nbk)
        if tbl is None:
            # host-side slice: one tiny h2d per (table change, bucket)
            tbl = self._table_dev[nbk] = jnp.asarray(self.table[:, :nbk])
        c_last, c_len, c_done, c_rem, c_key = self._carry
        v_act, v_t, v_k, v_p, v_eos = self._slot_vecs
        reqs = [self.slot_req[i] for i in active_slots]
        sampled = any(r.temperature > 0 for r in reqs)
        flags = (sampled,
                 sampled and any(r.top_k > 0 for r in reqs
                                 if r.temperature > 0),
                 sampled and any(r.top_p < 1.0 for r in reqs
                                 if r.temperature > 0))
        vk = (path, flags) if ragged else (nbk, flags)
        decode = self._decode_cache.get(vk)
        if decode is None and self._held is None:
            # numerics gate baked per variant, like _prefill_fn (the key
            # stays ("ragged"|bucket, flags): a mid-run flag flip
            # instruments new variants only — docs/observability.md)
            decode = self._decode_cache[vk] = jax.jit(
                _named("paged_decode", functools.partial(
                    _paged_decode, model=self.model,
                    n_steps=self.decode_steps, sample_flags=flags,
                    opts=ServeOpts(
                        kv_int8=self.kv_int8,
                        numerics=self.kv_int8 and _nm.active(),
                        ragged=ragged, mesh=self.mesh))),
                donate_argnums=(8,))
            _M_DECODE_RECOMPILES.inc()
        # path + traffic accounting (host ints — kept whether or not the
        # registry is on, so bench rows can report evidence without
        # perturbing the measured workload with full telemetry)
        if not ragged:
            path = "dense" if nbk >= self.mb else "bucketed"
        _M_DECODE_KERNEL.inc(path=path)
        _M_DECODE_VARIANTS.set(len(self._decode_cache))
        pb = self._pool_block_bytes()
        if ragged:
            # every scan step re-walks each slot's true-length blocks.
            # The kernel walks the DEVICE carry lengths, which lag the
            # host's view by up to decode_steps for slots chained
            # behind an unread call — add the lag (the _prefix_blocks
            # convention) so the estimate matches the true walk
            snap = ({s for s, _ in prev["snapshot"]}
                    if prev is not None else ())
            lens = {i: int(self.lengths[i])
                    + (self.decode_steps if i in snap else 0)
                    for i in active_slots}
            walk = sum(-(-ln // self.bs) for ln in lens.values())
            kv_call_bytes = walk * pb * self.decode_steps
            step_bytes = walk * pb
            if self.win is not None:
                # the window layers' walks start at the window's edge
                wwalk = sum(self.win.walk_blocks(ln) for ln in lens.values())
            horizon = max(lens.values(), default=0)
            bucket_tokens = -(-horizon // self.bs) * self.bs
        else:
            # one dense gather (pool read + dense write) + one dense
            # read per scan step, all at the bucket ceiling
            walk = self.N * nbk
            step_bytes = pb * walk
            kv_call_bytes = step_bytes * (2 + self.decode_steps)
            bucket_tokens = nbk * self.bs
            if self.win is not None:
                wwalk = self.N * self.win.width      # the whole ring, dense
        win_attrs, win_args = {}, ()
        if self.win is not None:
            # kv_bytes is what BOTH kinds' walks read; window_bytes the
            # window layers' part of it
            wbytes = wwalk * self._pool_block_bytes(window=True)
            step_bytes += wbytes
            kv_call_bytes += wbytes * (self.decode_steps if ragged
                                       else 2 + self.decode_steps)
            win_attrs = dict(window_bytes=wbytes, window_walk_blocks=wwalk)
            win_args = (self._wtable_dev,)
        self.kv_read_bytes_total += kv_call_bytes
        if _obs.enabled():
            _M_PREFIX_BUCKET.set(bucket_tokens)
            _M_KV_READ_BYTES.set(step_bytes)
        if prep is not None:
            prep.attrs["slots"] = len(active_slots)
            prep.end()
        latent = self.model.cache_kind == "latent"
        held, self._held = self._held, None
        if held is not None:
            # ONE program for the step's last piece and the decode rows
            # (``_launch_row``): its span is the piece's, its counts one set
            toks, c_last, c_len, c_done, c_rem, c_key = self._launch_row(
                held, (c_last, c_len, c_done, c_rem, c_key, v_act, tbl,
                       v_t, v_k, v_p, v_eos, *win_args), flags,
                decode_slots=len(active_slots), walk_blocks=walk,
                kv_bytes=step_bytes, **win_attrs)
            if held[0][5]:
                # a final piece: read back with this record, in the carry
                # and the decode mask from the next dispatch on
                self._joining = self._pending_adm[-1:]
                self._slots_dirty = True
                req = held[0][1]
                if req.eos_token_id is None \
                        and req.max_new_tokens - len(req.generated) <= 1:
                    ends.add(held[0][0])   # its first token is its last
            stats = None              # on the piece's span already
        else:
            self._step_decodes["decode"] += 1
            self._mark("serving.decode")
            with trace_span("serving.decode", slots=len(active_slots),
                            steps=self.decode_steps,
                            walk_blocks=walk, kv_bytes=step_bytes,
                            latent_bytes=step_bytes if latent else 0,
                            # per-slot state a step reads and writes, of
                            # the slots that move
                            state_bytes=(self._state_bytes_per_slot
                                         * len(active_slots)),
                            # the true dispatched horizon (ragged: max real
                            # length; bucketed: the ceiling) — matches the
                            # serving_decode_prefix_bucket gauge, never the
                            # full-width table shape
                            prefix_bucket=bucket_tokens, **win_attrs,
                            request_ids=[r.req_id for r in reqs]) as sp:
                (toks, c_last, c_len, c_done, c_rem, c_key,
                 self.pools, stats) = decode(
                    self.params, c_last, c_len, c_done, c_rem, c_key, v_act,
                    tbl, self.pools, v_t, v_k, v_p, v_eos, *win_args)
                self._dispatched()
        self._carry = (c_last, c_len, c_done, c_rem, c_key)
        if stats is not None:
            self._pending_stats.append((stats, sp.attrs))
        self._inflight = {
            "toks": toks,
            # the model's counts of this call and of the prefill waves
            # since the last one: they ride this record's readback
            "stats": self._pending_stats,
            "snapshot": [(i, self.slot_req[i].req_id)
                         for i in active_slots],
            "adm": self._pending_adm,
            "rem_start": rem_start,
            "ends": ends,
            # the newest program dispatched: once this record is back and
            # the count has not moved, the device is empty (_device_get)
            "seq": self._seq,
        }
        self._pending_adm = []
        self._pending_stats = []
        self._fresh_swapins = set()
        return prev

    # -- speculative decoding (r13): draft-then-verify waves ---------------
    def _spec_eligible(self, active) -> bool:
        """True when the next decode wave can run draft-then-verify:
        a draft is configured, every decode slot is GREEDY (the
        accept-longest-prefix rule is exact for argmax sampling only),
        and every slot's draft KV covers its full context (a slot
        advanced by the normal path while a sampled request shared its
        wave is stale until re-prefilled). Ineligible waves take the
        normal decode path — never wrong, at worst unaccelerated."""
        if not self._spec_on or not active:
            return False
        for i in active:
            req = self.slot_req[i]
            if req.temperature > 0:
                return False
            if self._draft_len[i] != self.lengths[i]:
                return False
        return True

    def _spec_bucket(self, active) -> int:
        """Power-of-two block count covering every wave slot's history
        PLUS the verify piece's k+1 writes — the verify table slice
        (and the draft's, off the ragged path). Same convention as
        :meth:`_prefix_blocks`, horizon ``spec_k + 1``."""
        hmax = need = 0
        for i in active:
            hmax = max(hmax, int(self.lengths[i]))
            need = max(need, int(self.n_alloc[i]))
        horizon = min(hmax + self.spec_k + 1, self.max_model_len)
        need = max(1, need, -(-horizon // self.bs))
        nbk = 1 << (need - 1).bit_length()
        return min(nbk, self.mb)

    def _spec_draft_fn(self, path: str):
        """The draft proposal program: ``_paged_decode`` at draft scale
        — draft config, ``spec_k`` fused steps, greedy flags, the
        ``dk``/``dv`` pool entries. One cached jit per decode path (the
        bucketed table width re-specializes inside jax's own cache)."""
        fn = self._spec_draft_cache.get(path)
        if fn is None:
            fn = self._spec_draft_cache[path] = jax.jit(
                _named("spec_draft", functools.partial(
                    _paged_decode, model=self.draft_model,
                    n_steps=self.spec_k,
                    sample_flags=(False, False, False),
                    opts=ServeOpts(ragged=(path == "ragged"),
                                   prefix="d"))),
                donate_argnums=(8,))
        return fn

    def _spec_verify_fn(self, nbk: int):
        """The batched verify program, one variant per history bucket —
        the log-bounded axis the chunked-prefill family already pays
        for, with no flag axis (verify is always greedy)."""
        fn = self._spec_verify_cache.get(nbk)
        if fn is None:
            fn = self._spec_verify_cache[nbk] = jax.jit(
                _named("spec_verify", functools.partial(
                    self.model.spec_verify,
                    n_spec=self.spec_k, kv_int8=self.kv_int8,
                    numerics=self.kv_int8 and _nm.active(),
                    max_model_len=self.max_model_len)),
                donate_argnums=(6,))
        return fn

    def _spec_wave(self, active):
        """One draft-then-verify decode wave: the draft proposes
        ``spec_k`` tokens per slot in one multi-step call, the target
        scores every proposal in one prefill-shaped batched call (the
        draft grid feeds it device-to-device — no host hop between the
        two), and the host commits the longest agreeing prefix per slot
        — atomically into lengths, the block tables' backing, the
        prefix-cache adoption path (via ``_free_slot``/finish) and the
        emit stream. Capping commits at ``spec_k`` (the "bonus" token
        of classic speculative sampling is dropped) keeps the draft's
        KV in exact lockstep with the target's, so the rejected-suffix
        rollback is pure length bookkeeping: positions >=
        ``lengths`` in EITHER pool are unreadable and the next wave
        overwrites them.

        Runs with the pipeline drained — acceptance is a host decision,
        so the wave syncs once (its amortization is the k-for-1 verify,
        not call chaining), which is also why spec waves, unlike the
        chained path, compose with per-request eos."""
        from ..distributed.watchdog import guarded

        emitted = []
        if self._pending_adm:
            adm, self._pending_adm = self._pending_adm, []
            with guarded("serving-spec-readback"), \
                    trace_span("serving.readback"):
                emitted += self._flush_adm(adm, self._adm_seq)
            self._mark("serving.step")
        # swap-in carry lanes are host-known state; the spec wave reads
        # host state directly and invalidates the chained device carry
        self._pending_swapin = []
        self._fresh_swapins = set()
        self._carry = None
        self._slots_dirty = True
        emitted += self._back_or_preempt(steps=self.spec_k)
        active = self._decode_slots()
        if not active:
            return emitted
        k = self.spec_k
        N = self.N
        # the draft program reads the DRAFT's pools, at its own head dim:
        # its path is asked for the draft model; the verify program is
        # prefill-shaped and takes neither
        path = self._decode_path(draft=True)
        ragged = path == "ragged"
        nbk = self._spec_bucket(active)
        if self._table_dirty:
            self._table_dev = {}
            self._table_dirty = False

        def tdev(width):
            t = self._table_dev.get(width)
            if t is None:
                t = self._table_dev[width] = jnp.asarray(
                    self.table[:, :width])
            return t

        tbl_v = tdev(nbk)
        tbl_d = tdev(self.mb) if ragged else tbl_v
        last = np.zeros(N, np.int32)
        budgets = np.zeros(N, np.int32)
        act = np.zeros(N, bool)
        for i in active:
            req = self.slot_req[i]
            out = self.slot_out[i]
            last[i] = out[-1] if out else (
                req.generated[-1] if req.generated else req.prompt[-1])
            # the draft stops proposing at the slot's remaining budget:
            # tokens past it could never commit, and their writes would
            # clamp into real blocks near max_model_len
            budgets[i] = req.max_new_tokens - len(req.generated) \
                - len(out)
            act[i] = True
        walk = sum(-(-int(self.lengths[i]) // self.bs) for i in active)
        last_j = jnp.asarray(last)
        lens_j = jnp.asarray(self.lengths, jnp.int32)
        act_j = jnp.asarray(act)
        rids = [self.slot_req[i].req_id for i in active]
        draft_fn = self._spec_draft_fn(path)
        self._mark("serving.spec_draft")
        with trace_span("serving.spec_draft", slots=len(active), k=k,
                        request_ids=rids):
            (demitted, _dl, _dn, _dd, _db, _dk, self.pools,
             _st) = draft_fn(
                self.draft_params, last_j, lens_j, jnp.zeros(N, bool),
                jnp.asarray(budgets), jax.random.PRNGKey(0), act_j,
                tbl_d, self.pools, jnp.zeros(N, jnp.float32),
                jnp.zeros(N, jnp.int32), jnp.ones(N, jnp.float32),
                jnp.full(N, -1, jnp.int32))
            self._dispatched()
        verify_fn = self._spec_verify_fn(nbk)
        with trace_span("serving.spec_verify", slots=len(active), k=k,
                        prefix_bucket=nbk * self.bs, request_ids=rids):
            vtoks, self.pools = verify_fn(
                self.params, tbl_v, last_j, demitted, lens_j, act_j,
                self.pools)
            seq = self._dispatched()
        if self.injector is not None and \
                self.injector.fires("spec_verify_fail", self._step_idx):
            # chaos surface: a crash between the verify dispatch and
            # its readback. NOTHING of this wave is host-visible yet,
            # so recovery (drop + requeue from host state) rolls back
            # to the last committed token with zero stream divergence
            _flight.record("injected_spec_verify_fail",
                           step=self._step_idx)
            raise SimulatedCrash(
                f"injected speculative-verify failure at serving step "
                f"{self._step_idx}")
        with guarded("serving-spec-readback"), \
                trace_span("serving.readback"):
            d_host, v_host = self._device_get(
                (demitted, vtoks), seq)             # [k, N], [N, k+1]
        wave_prop = wave_acc = wave_commit = 0
        for i in active:
            req = self.slot_req[i]
            rid = req.req_id
            rem = req.max_new_tokens - len(req.generated) \
                - len(self.slot_out[i])
            prop = min(k, rem)              # what the draft really ran
            d, g = d_host[:, i], v_host[i]
            a = 0
            while a < prop and d[a] == g[a]:
                a += 1
            # commit the agreeing prefix + the target's one new token,
            # capped at k (the draft-KV lockstep invariant) and at the
            # budget; a == 0 still commits g[0] — a zero-acceptance
            # draft degenerates to one token per wave, never fewer
            c = min(a + 1, k, rem)
            wave_prop += prop
            wave_acc += a
            for j in range(c):
                tok = int(g[j])
                self.lengths[i] += 1        # verify wrote its K/V
                self._draft_len[i] += 1     # the draft wrote its too
                wave_commit += 1
                emitted.append((rid, tok))
                self._step_emitted.append((rid, tok))
                if self._emit(i, tok):
                    break                   # eos/budget mid-wave
        self._mark("serving.step")      # the commits were the readback's
        self.spec_waves += 1
        self.spec_verify_calls += 1
        self.spec_draft_steps += k
        self.spec_proposed += wave_prop
        self.spec_accepted += wave_acc
        self.spec_committed += wave_commit
        _M_SPEC_PROPOSED.inc(wave_prop)
        if wave_acc:
            _M_SPEC_ACCEPTED.inc(wave_acc)
        # KV-traffic estimate (host ints, registry-independent): the
        # draft's walks/gathers at draft-pool bytes + the verify's one
        # dense history gather at target-pool bytes
        pb_t, pb_d = self._pool_block_bytes(), \
            self._pool_block_bytes(draft=True)
        if ragged:
            self.kv_read_bytes_total += walk * pb_d * k
        else:
            self.kv_read_bytes_total += pb_d * N * nbk * (2 + k)
        self.kv_read_bytes_total += pb_t * N * nbk
        if _obs.enabled():
            _M_SPEC_ACCEPT_RATE.set(
                self.spec_accepted / max(1, self.spec_proposed))
            _M_SPEC_TOKENS_PER_WAVE.set(
                self.spec_committed / max(1, self.spec_verify_calls))
        return emitted

    def _process(self, rec):
        """Read back one decode record (first tokens of its admissions,
        then its emitted grid) and update host bookkeeping. Slots whose
        request changed since dispatch (finished or preempted) are
        skipped — their lanes are -1 padding or discarded speculation.

        The device_get readbacks below are the engine's blocking host
        syncs — the spot a hung collective or wedged device stalls a
        serving process. They run under the process watchdog when one is
        installed (distributed.watchdog.install): a long-lived server
        gets hang detection + emergency-hook checkpointing for free."""
        from ..distributed.watchdog import guarded

        if self.injector is not None and \
                self.injector.fires("readback_fail", self._step_idx):
            # the injectable stand-in for a wedged device at the
            # engine's one blocking sync; ResilientEngine's recovery
            # contract (drop the wave, requeue from traced state) is
            # proven against exactly this raise
            _flight.record("injected_readback_fail", step=self._step_idx)
            raise SimulatedCrash(
                f"injected readback failure at serving step "
                f"{self._step_idx}")
        outer = self._phase
        with guarded("serving-decode-readback"), \
                trace_span("serving.readback"):
            emitted = self._process_guarded(rec)
        self._mark(outer)
        return emitted

    def _device_get(self, tree, seq: Optional[int] = None):
        """The engine's blocking host sync: ``tree``'s arrays on the
        host. The wait is a ``serving.readback_wait`` span of its own
        inside ``serving.readback`` and adds to ``_wait_s``, which
        ``serving_step_host_seconds`` takes off the step's wall time.

        It is also where the starved-time ledger starts. The step thread
        numbers every compiled program it dispatches (``_dispatched``) and
        programs chain through the donated pools, so they end in order:
        when the arrays of program ``seq`` are here and ``seq`` is still
        the newest dispatched, the device has nothing of this engine's
        left to run, and stays so until the next dispatching call returns.
        A readback that returns while a later program is in flight (the
        pipelined case; a drain behind a lone piece dispatched earlier in
        the step) starts nothing. From then on the time goes to the phase
        the step thread is in (``_mark``), under the spans' names, and
        while the engine holds no request to
        ``serving_engine_no_work_seconds_total`` instead; a step's total
        closes its ``serving.step`` span as ``starved_ms`` / ``starved``
        and ``_step_telemetry`` flushes it to
        ``serving_device_starved_seconds_total{phase}``.

        A LOWER bound on the trace's idle time by construction. It cannot
        see: the device-to-host copy inside the wait after the last
        operation ended, the launch latency after a dispatching call
        returned, the seams between operations, a device that ran dry
        behind a program dispatched earlier in the step. Not numbered,
        because they run for microseconds or in no benchmarked shape: the
        carry's ``_apply_admissions`` and the swap tier's gathers and
        scatters (while those run the ledger reads a little high)."""
        with trace_span("serving.readback_wait") as sp:
            host = jax.device_get(tree)
        self._wait_s += sp.seconds
        if seq == self._seq and self._starved_t is None and _obs.enabled():
            self._starved_t = time.perf_counter()
            self._idle = False       # it held this record's requests
            self._phase = "serving.readback"
        return host

    def _dispatched(self) -> int:
        """A dispatching call just returned: the program's number, and
        the end of a starved stretch if one was open."""
        self._seq += 1
        if self._starved_t is not None:
            self._mark(self._phase)
            self._starved_t = None
        return self._seq

    def _mark(self, phase: str) -> None:
        """The step thread enters ``phase``. While the device is known
        empty, the time since the last mark goes to the phase it leaves
        (or to no work, if the engine held no request through it): one
        clock read and one add. Otherwise one attribute read."""
        t = self._starved_t
        if t is not None:
            now = time.perf_counter()
            if self._idle:
                self._no_work_s += now - t
            else:
                self._starved[self._phase] = \
                    self._starved.get(self._phase, 0.0) + (now - t)
            self._starved_t = now
            # has_work(), cheaper: admit_order holds the slots in use
            self._idle = not (self.queue or self.admit_order)
        self._phase = phase

    def _flush_adm(self, adm, seq: Optional[int] = None):
        """Read back a list of pending-admission first tokens
        ((slot, rid, [1] token array) tuples) and commit them host-side
        — ONE readback for all of them, not one per admission. ``seq``:
        the newest program among them, where no record carries them."""
        emitted = []
        host = self._device_get([arr for _, _, arr in adm], seq)
        for (slot, rid, _), h in zip(adm, host):
            req = self.slot_req[slot]
            if req is None or req.req_id != rid:
                continue              # preempted before its call ran
            tok = int(h[0])
            emitted.append((rid, tok))
            # commit point: host-visible from here on — mirrored into
            # the step's salvage buffer so a crash later in this SAME
            # step still delivers it (ResilientEngine)
            self._step_emitted.append((rid, tok))
            self._emit(slot, tok)
        return emitted

    def _process_guarded(self, rec):
        emitted = []
        if rec["adm"]:
            # first tokens of programs BEFORE the record's newest: no seq
            emitted += self._flush_adm(rec["adm"])
        stats = rec.get("stats") or []
        if stats:
            # one blocking sync for the tokens and the counts together
            toks_host, stats_host = self._device_get(
                (rec["toks"], [a for a, _ in stats]), rec["seq"])
            self._note_stats(stats_host, [at for _, at in stats])
        else:
            toks_host = self._device_get(rec["toks"], rec["seq"])   # [K, N]
        for slot, rid in rec["snapshot"]:
            req = self.slot_req[slot]
            if req is None or req.req_id != rid:
                continue
            for k in range(toks_host.shape[0]):
                tok = int(toks_host[k, slot])
                if tok < 0:
                    break          # slot went done mid-scan
                self.lengths[slot] += 1     # its K/V was appended
                if self._spec_on:
                    # this slot advanced through the NORMAL decode path
                    # (a sampled slot was in the wave): its draft KV is
                    # now behind and can't catch up without a
                    # re-prefill — mark it out of the spec pool
                    self._draft_len[slot] = -1
                emitted.append((rid, tok))
                self._step_emitted.append((rid, tok))
                if self._emit(slot, tok):
                    break          # freed: later entries are -1 anyway
        return emitted

    def _note_stats(self, stats_host, span_attrs) -> None:
        """A model's counts, read back with a record's tokens (one step
        after their program ran): the expert layers' ``[routed, assigned,
        experts_hit, fullest x held, row tiles]`` (``kernels/moe_dispatch.
        held_expert_ffn``, summed over the layers) go to the counters and,
        as ``expert_rows`` / ``experts_hit`` / ``expert_tiles``, onto the
        span of the program that produced them."""
        for st, attrs in zip(stats_host, span_attrs):
            routed, assigned, hit, fullest, tiles = (float(v) for v in st)
            attrs.update(expert_rows=int(assigned), experts_hit=int(hit),
                         expert_tiles=int(tiles))
            _M_MOE_ROUTED.inc(routed)
            _M_MOE_ASSIGNED.inc(assigned)
            _M_MOE_TILES.inc(tiles)
            if assigned:
                _M_MOE_LOAD.set(fullest / assigned)

    def _process_inflight(self, reason: str = "run_end"):
        """Drain the depth-1 pipeline: read the in-flight record back
        with nothing dispatched behind it, counted by why
        (``serving_pipeline_drains_total{reason}``; the step's first is
        its span's ``drain``)."""
        rec, self._inflight = self._inflight, None
        if _obs.enabled():
            _M_DRAINS.inc(reason=reason)
            if self._step_drain is None:
                self._step_drain = reason
            if rec["ends"]:
                _M_COUNTED_ENDS.inc(len(rec["ends"]), drained="yes")
        return self._process(rec)

    def step(self):
        """Admit queued requests, keep the chip fed, and return the
        (req_id, token) pairs that became host-visible this call.

        Pipelined: decode call k+1 is dispatched BEFORE call k's tokens
        are read whenever no in-flight slot can finish at a step the host
        cannot count ahead (``_spec_safe``), so the readback latency
        overlaps the next call's compute. The
        token stream therefore lags the chip by up to one call
        (decode_steps tokens per slot).

        Observability (FLAGS_obs_enabled): each call lands a
        ``serving.step`` span (prefill/decode/readback nested inside;
        it closes with ``starved_ms`` / ``starved`` / ``drain`` where the
        device was known empty in it or the pipeline drained:
        ``_device_get``), a step-duration + tokens/sec observation, TTFT (with a
        request_id exemplar) for requests whose first token became
        visible, a per-request decode tick on the timeline, and the
        queue/slot/KV-pool gauges. Disabled, this wrapper costs one
        boolean check (plus the idle profiling-tick global read)."""
        # on-demand device-capture window boundary (near-zero when no
        # capture is armed; deliberately OUTSIDE the enabled() gate — a
        # capture is an explicit operator action, not ambient telemetry)
        _profiling.step_tick()
        if not _obs.enabled():
            return self._step_inner()
        self._wait_s = 0.0
        self._step_drain = None
        self._step_carried = 0
        t0 = time.perf_counter()
        with trace_span("serving.step") as sp:
            self._mark("serving.step")
            emitted = self._step_inner()
            self._mark("between_steps")
            if self._starved:
                # with what accrued between steps since the last flush
                ms = {p: 1e3 * s for p, s in self._starved.items()}
                sp.attrs.update(starved_ms=sum(ms.values()), starved=ms)
            if self._step_drain is not None:
                sp.attrs["drain"] = self._step_drain
            if self._step_carried:
                sp.attrs["counted_ends"] = self._step_carried
        now = time.perf_counter()
        with trace_span("serving.telemetry"):
            self._step_telemetry(emitted, now, now - t0)
        return emitted

    def _step_telemetry(self, emitted, now: float, dt: float) -> None:
        """What a step owes the registry and the request timelines once
        its work is done (the ``serving.telemetry`` span)."""
        _M_STEP_SECONDS.observe(dt)
        # the step's programs: its decode rows rode a piece or ran alone
        for program, n in self._step_decodes.items():
            if n:
                _M_DECODE_STEPS.inc(n, program=program)
                self._step_decodes[program] = 0
        # the starved-time ledger since the last step's flush
        for phase, s in self._starved.items():
            _M_STARVED.inc(s, phase=phase)
        self._starved.clear()
        if self._no_work_s:
            _M_NO_WORK.inc(self._no_work_s)
            self._no_work_s = 0.0
        # the host's own share: the step less its waits on the device
        _M_STEP_HOST_SECONDS.observe(max(0.0, dt - self._wait_s))
        if emitted:
            _M_TOKENS.inc(len(emitted))
            if dt > 0:
                _M_TPS.observe(len(emitted) / dt)
            tracer = _rt.get_request_tracer()
            step_toks: Dict[int, int] = {}
            for rid, _tok in emitted:
                step_toks[rid] = step_toks.get(rid, 0) + 1
                t_add = self._obs_t_add.pop(rid, None)
                if t_add is not None:
                    tracer.record(rid, "first_token")
                    _rt.observe_with_exemplar(_M_TTFT, now - t_add, rid)
                    self._obs_t_first[rid] = now
            for rid, n in step_toks.items():
                # one decode tick per request per step (finished
                # requests already left the live table — no-op there)
                tracer.record(rid, "decode", tokens=n)
        _perf.update_serving_slo_gauges(_M_TTFT, _M_TPOT)
        _perf.update_hbm_gauges()
        _M_QUEUE_DEPTH.set(len(self.queue))
        _M_ACTIVE_SLOTS.set(sum(r is not None for r in self.slot_req))
        _M_KV_BLOCKS.set(self.nb - 1)
        _M_KV_TOKEN_BYTES.set(self._pool_block_bytes() / self.bs)
        _M_STATE_SLOT_BYTES.set(self._state_bytes_per_slot)
        _M_KV_USED.set(self.nb - 1 - len(self.free_blocks))
        if self.win is not None:
            _M_WINDOW_SLOT_BYTES.set(self._pool_block_bytes(window=True)
                                     * self.win.width)
            _M_WINDOW_RECYCLED.inc(self.win.recycled - self._win_recycled)
            self._win_recycled = self.win.recycled
            _M_WINDOW_BOUNDED.inc(self._win_bounded)
            self._win_bounded = 0
        if self.prefix_cache is not None:
            self.prefix_cache.update_gauges()
        # time-series sampler (r20): throttled by FLAGS_obs_ts_interval_s,
        # contention-free — a concurrent replica already sampling means
        # this step skips instead of waiting
        _ts.step_tick()

    def _step_inner(self):
        emitted = []
        self._step_emitted = []
        self._step_idx += 1
        # chaos + deadlines + front-door cancellations run before
        # admission: an injected squeeze shapes this step's block
        # budget, and an expired or disconnected request must not
        # occupy the slot a live one could take
        self._mark("serving.housekeeping")
        with trace_span("serving.housekeeping"):
            self._apply_faults()
            self._expire_deadlines()
            self._apply_cancels()
            # offload sweep AFTER cancellations (a dead request must not
            # be staged) and BEFORE admission (blocks a landed spill just
            # freed are allocatable THIS step; staged payloads meet their
            # restore)
            self._offload_tick()
        self._mark("serving.step")
        self._flush_held()        # left by a step that raised, if at all
        # one chunk per mid-prefill slot BEFORE admission/decode: the
        # chunk program and this step's decode wave share the step, so a
        # long prefill never monopolizes it (bounded TTFT for the slots
        # already decoding)
        self._admit_phase(chunks=True)
        if self.role == "prefill":
            # disagg (r19): no decode ever dispatches here — slots whose
            # prefill (chunked included) just completed hand their KV to
            # the relay and their stream to a decode replica
            return emitted + self._prefill_handoffs()
        if self._spec_on:
            active = self._decode_slots()
            if active and self._spec_eligible(active):
                # a spec wave syncs on its own acceptance decision:
                # drain the depth-1 pipeline first (host state must be
                # exact), re-admit into any slots that freed, then run
                # draft → verify → commit
                if self._inflight is not None:
                    emitted += self._process_inflight("spec_wave")
                    self._admit_phase()
                    active = self._decode_slots()
                if active and self._spec_eligible(active):
                    return emitted + self._spec_wave(active)
        if self._inflight is not None:
            if not self._spec_safe():
                emitted += self._process_inflight("may_finish")
                self._admit_phase()    # freed slots: refill before dispatching
            elif self._inflight["ends"]:
                # counted ends, carried: the lanes leave the decode mask
                # (uploaded behind the running call) and a piece whose
                # first token was its last never joins
                ends = self._inflight["ends"]
                self._joining = [e for e in self._joining
                                 if e[0] not in ends]
                self._slots_dirty = True
        active = self._decode_slots()
        if not active:
            self._flush_held()        # a piece and no decode rows to carry
            if self._inflight is not None:
                emitted += self._process_inflight("no_active")
            return emitted
        # everything the host does for this decode call before it is
        # enqueued; _dispatch_decode ends the span right at the call
        self._mark("serving.decode_prepare")
        with trace_span("serving.decode_prepare") as prep:
            emitted += self._back_or_preempt()
            active = self._decode_slots()
            if not active:
                self._flush_held()
                return emitted
            self._refresh_carry(active)
            prev = self._dispatch_decode(active, prep)
        if prev is not None:
            emitted += self._process(prev)
        return emitted
