"""Async two-tier KV offload: background spill + prefetch behind decode.

PR 8 / PR 10 built the *synchronous* host-DRAM tier under HBM: every
preemption swap-out is a blocking ``device_get`` on the engine step
thread, and every swap-in / prefix-cache restore is a blocking h2d at
admission time — under sustained pool pressure the engine pays the
transfer latency inline with decode. nncase (PAPERS.md) deploys LLMs
across heterogeneous storage tiers; the TPU analog is pinned host RAM
under HBM, and the overlap idiom that hides collectives behind compute
(kernels/moe_dispatch.py's double-buffered halves) applies to the
memory hierarchy just as well. This module is the transfer engine that
makes the host pool a true SECOND TIER of the paged block pool:

- **Async spill (d2h).** A swap-out or a proactive cold-block spill
  dispatches a non-blocking device→host copy (a ``device_put`` into
  the array's own sharding with ``memory_kind="pinned_host"``). The
  spilled blocks stay
  device-resident and ACCOUNTED until the transfer lands: swap-out
  victims park their private blocks in this engine's custody (the
  ledger's transient ``in_flight`` term), proactively spilled cache
  nodes simply keep their block under ``cached``. The step-boundary
  :meth:`poll` sweep commits landed payloads into the
  :class:`~paddle_tpu.serving.kv_swap.HostKVPool` and returns custody
  blocks to the free list — the engine never blocks on a spill.
- **Prefetch-ahead restore (h2d).** When a swapped request nears the
  head of the admission queue, or a queued prompt's prefix walk would
  land on host-resident trie nodes, :meth:`stage` starts the h2d copy
  one or more steps EARLY into staging buffers attached to the host
  entry (``SwapEntry.staged``). A restore that finds its payload staged
  is a ``prefetch_hit`` (zero inline wait); one that must transfer
  inline is a counted ``stall`` with observed stall seconds —
  ``serving_kv_offload_{prefetch_hits,stalls,stall_seconds}_total``.
- **Exactness.** Transfers move every pool entry verbatim (int8
  payload AND per-entry scales), reservations guarantee a dispatched
  spill always fits its pool, and d2h slices are enqueued before any
  subsequent pool write in stream order — async streams are
  bit-identical to the sync path (test-enforced, bf16 and int8).
- **Crash semantics.** ResilientEngine's poisoned-wave rule extends to
  transfers: :meth:`abandon` drops every in-flight spill (host pool
  reservations released, custody blocks returned for the free list,
  staged buffers discarded) — a crashed step can never commit a
  half-landed payload.

``FLAGS_serve_kv_offload_sync`` forces the old inline behavior (the
forced-sync leg of the parity tests and the bench row); the engine's
``kv_offload="auto"|"async"|"sync"`` constructor knob overrides per
instance. See docs/serving.md §KV offload tier.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.flags import define_flag, get_flag
from ..observability.catalog import instrument as _instrument

__all__ = ["OffloadEngine"]

define_flag("serve_kv_offload_sync", False,
            "force synchronous KV offload transfers (the pre-r15 inline "
            "d2h/h2d behavior): spills block the step thread, no "
            "prefetch staging, no proactive spill — the parity-test / "
            "bench reference leg")
define_flag("serve_kv_offload_prefetch_depth", 2,
            "how many queued requests from the admission-queue head the "
            "per-step prefetch sweep inspects for host-resident KV "
            "(swap entries / spilled prefix nodes) to stage h2d early; "
            "0 disables prefetch (every restore stalls inline)")
define_flag("serve_kv_offload_staging_bytes", 256 << 20,
            "device-byte budget for prefetch staging buffers (h2d "
            "copies started ahead of admission); staging requests past "
            "the budget wait for earlier stages to be consumed")
define_flag("serve_kv_offload_spill_free_frac", 0.25,
            "proactive-spill pressure threshold: when the allocatable "
            "block fraction falls below this, refcount-0 LRU cached "
            "blocks start background d2h spills so later reclaims free "
            "them without an inline transfer (doubled shed_free_frac "
            "wins when an AdmissionConfig sets one — the spiller must "
            "engage before the shedder)")
define_flag("serve_kv_offload_spill_batch", 4,
            "max proactive cold-block spills dispatched per engine step "
            "(bounds per-step d2h bandwidth spent on background "
            "spilling)")

_M_PREFETCH_HITS = _instrument("serving_kv_offload_prefetch_hits_total")
_M_STALLS = _instrument("serving_kv_offload_stalls_total")
_M_STALL_SECONDS = _instrument("serving_kv_offload_stall_seconds_total")
_M_INFLIGHT = _instrument("serving_kv_offload_inflight_bytes")
_M_PROACTIVE = _instrument("serving_kv_offload_proactive_spills_total")


def _start_d2h(arr):
    """Begin moving one device array to the host without blocking: a
    ``device_put`` into the same sharding in ``pinned_host`` memory (a
    tp-sharded pool slice stays sharded the same way). Returns the new
    host-memory array, whose readiness marks the landing."""
    return jax.device_put(arr, arr.sharding.with_memory_kind("pinned_host"))


def _is_ready(arr) -> bool:
    """Non-blocking landing probe; absent (exotic array types) the
    transfer is treated as landed and ``np.asarray`` pays the wait."""
    try:
        return bool(arr.is_ready())
    except Exception:
        return True


def _nbytes(arr) -> int:
    return int(np.prod(arr.shape)) * arr.dtype.itemsize


class _Spill:
    """One in-flight d2h batch: the device slices being copied, the
    blocks parked in custody until landing, and the host-pool
    reservation that guarantees the commit fits."""

    __slots__ = ("key", "arrays", "blocks", "n_tokens", "nbytes", "pool",
                 "on_land", "proactive")

    def __init__(self, key, arrays, blocks, n_tokens, nbytes, pool,
                 on_land, proactive):
        self.key = key
        self.arrays = arrays            # name -> device array (landing)
        self.blocks = list(blocks)      # custody (ledger in_flight term)
        self.n_tokens = int(n_tokens)
        self.nbytes = int(nbytes)
        self.pool = pool                # HostKVPool holding the reservation
        self.on_land = on_land          # fn(ok) or None
        self.proactive = proactive


class OffloadEngine:
    """Host-side bookkeeping for the async transfer tier. One instance
    per :class:`~paddle_tpu.serving.engine.LLMEngine`; every method runs
    on the engine's step thread (no locking needed — the engine's state
    machine is single-owner per step)."""

    def __init__(self, sync: Optional[bool] = None):
        # the sync decision is per-instance and frozen at construction:
        # flipping the flag mid-serve must not strand in-flight state
        self.sync = (bool(get_flag("serve_kv_offload_sync"))
                     if sync is None else bool(sync))
        self._spills: Dict = {}         # key -> _Spill
        self._staged: Dict = {}         # key -> (host_pool, entry)
        # host evidence counters (kept whether or not the metrics
        # registry is enabled — bench rows read these)
        self.prefetch_hits = 0
        self.stalls = 0
        self.stall_seconds = 0.0
        self.proactive_spills = 0

    # -- knobs (read per call so tests can set_flags mid-run) -------------
    def prefetch_depth(self) -> int:
        return max(0, int(get_flag("serve_kv_offload_prefetch_depth")))

    def spill_batch(self) -> int:
        return max(0, int(get_flag("serve_kv_offload_spill_batch")))

    def staging_budget(self) -> int:
        return max(0, int(get_flag("serve_kv_offload_staging_bytes")))

    # -- accounting --------------------------------------------------------
    @property
    def held_blocks(self) -> int:
        """Device blocks custody-parked behind in-flight d2h spills —
        the block ledger's transient ``in_flight`` term (zero whenever
        no transfer is in flight, collapsing the ledger back to its
        4-term form)."""
        return sum(len(t.blocks) for t in self._spills.values())

    @property
    def inflight_bytes(self) -> int:
        return sum(t.nbytes for t in self._spills.values())

    @property
    def staged_bytes(self) -> int:
        return sum(ent.nbytes for _p, ent in self._staged.values()
                   if ent.staged is not None)

    def _gauge(self) -> None:
        _M_INFLIGHT.set(self.inflight_bytes)

    # -- spill (d2h) -------------------------------------------------------
    def spill_async(self, key, pools: Dict, block_ids, n_tokens: int,
                    host_pool, hold_blocks: List[int],
                    on_land: Optional[Callable] = None,
                    proactive: bool = False) -> bool:
        """Dispatch one non-blocking d2h spill of ``block_ids`` from
        every pool entry (payload AND scales move verbatim — the restore
        is bit-exact). Reserves ``host_pool`` capacity up front so a
        dispatched transfer can always commit; ``False`` (nothing
        started, the pool's refusal counters fired) when it cannot fit.

        ``hold_blocks`` are parked in this engine's custody until the
        transfer lands (the ledger's ``in_flight`` term) — pass ``[]``
        for spills whose source keeps its block (proactive cache
        spills). In sync mode the transfer completes inline (blocking
        d2h + commit) and nothing is ever held."""
        idx = jnp.asarray(np.asarray(block_ids, np.int32))
        arrays = {name: pool[:, idx] for name, pool in pools.items()}
        nbytes = sum(_nbytes(a) for a in arrays.values())
        if not host_pool.reserve(key, nbytes):
            return False
        if proactive:
            self.proactive_spills += 1
            _M_PROACTIVE.inc()
        if self.sync:
            data = {n: np.asarray(jax.device_get(a))
                    for n, a in arrays.items()}
            host_pool.commit(key, data, n_tokens)
            if on_land is not None:
                on_land(True)
            return True
        # keep the array the transfer actually lands in: on the
        # pinned_host path device_put returns a NEW (host-memory) array
        # — np.asarray on it at landing is a cheap view, not a second
        # d2h of the original device slice
        arrays = {n: _start_d2h(a) for n, a in arrays.items()}
        self._spills[key] = _Spill(key, arrays, hold_blocks, n_tokens,
                                   nbytes, host_pool, on_land, proactive)
        self._gauge()
        return True

    def pending(self, key) -> bool:
        return key in self._spills

    def _land(self, t: _Spill) -> List[int]:
        data = {n: np.asarray(a) for n, a in t.arrays.items()}
        t.pool.commit(t.key, data, t.n_tokens)
        if t.on_land is not None:
            t.on_land(True)
        return t.blocks

    def poll(self, block: bool = False) -> List[int]:
        """The step-boundary completion sweep: commit every landed spill
        into its host pool and return the custody blocks the caller must
        append to the free list. ``block=True`` waits for everything
        (the run()-exit / test-quiescence drain). Also prunes staging
        records whose host entry was consumed or discarded."""
        freed: List[int] = []
        for key in list(self._spills):
            t = self._spills[key]
            if block or all(_is_ready(a) for a in t.arrays.values()):
                del self._spills[key]
                freed.extend(self._land(t))
        for key in list(self._staged):
            pool, ent = self._staged[key]
            if ent.staged is None or pool.get(key) is not ent:
                ent.staged = None          # release the device buffers
                del self._staged[key]
        self._gauge()
        return freed

    def force_land(self, key) -> Optional[List[int]]:
        """Land one in-flight spill NOW (blocking) — admission reached a
        request whose swap-out has not landed yet; the payload commits
        into the transfer's own recorded pool. The observed wait counts
        toward stall seconds (the caller's restore counts the one stall
        event). Returns the custody blocks to free, or ``None`` when no
        such transfer exists."""
        t = self._spills.pop(key, None)
        if t is None:
            return None
        t0 = time.perf_counter()
        blocks = self._land(t)
        # seconds only: the caller's swap-in counts the ONE stall event
        # for this admission (its inline h2d) — counting here too would
        # bill a force-landed restore as two stalls
        self.note_stall(time.perf_counter() - t0, n=0)
        self._gauge()
        return blocks

    def cancel(self, key) -> List[int]:
        """Drop one in-flight spill (its request went terminal): the
        host-pool reservation is released and the custody blocks return
        to the caller for the free list."""
        t = self._spills.pop(key, None)
        if t is None:
            return []
        t.pool.unreserve(key)
        if t.on_land is not None:
            t.on_land(False)
        self._gauge()
        return t.blocks

    def abandon(self) -> List[int]:
        """Crash recovery: drop EVERY in-flight spill and staging buffer
        (the poisoned-wave rule extended to transfers — a crashed step
        must not commit a half-landed payload). Returns all custody
        blocks for the free list."""
        freed: List[int] = []
        for t in self._spills.values():
            t.pool.unreserve(t.key)
            if t.on_land is not None:
                t.on_land(False)
            freed.extend(t.blocks)
        self._spills = {}
        for _pool, ent in self._staged.values():
            ent.staged = None
        self._staged = {}
        self._gauge()
        return freed

    # -- prefetch staging (h2d) --------------------------------------------
    def stage(self, host_pool, key, ent) -> bool:
        """Start the h2d copy of one host entry's payload into staging
        buffers attached to the entry (``SwapEntry.staged``) so the
        restore that eventually consumes it finds the data already
        device-resident (a ``prefetch_hit``). No-ops in sync mode, when
        already staged, or past the staging budget."""
        if self.sync or ent.staged is not None:
            return False
        if self.staged_bytes + ent.nbytes > self.staging_budget():
            return False
        # jnp.asarray enqueues the h2d without waiting on it; the
        # consuming scatter orders behind it by data dependency
        ent.staged = {n: jnp.asarray(np.asarray(a))
                      for n, a in ent.data.items()}
        self._staged[key] = (host_pool, ent)
        return True

    # -- restore outcome counters ------------------------------------------
    def note_hit(self, n: int = 1) -> None:
        self.prefetch_hits += n
        _M_PREFETCH_HITS.inc(n)

    def note_stall(self, seconds: float, n: int = 1) -> None:
        self.stalls += n
        self.stall_seconds += float(seconds)
        _M_STALLS.inc(n)
        _M_STALL_SECONDS.inc(float(seconds))
