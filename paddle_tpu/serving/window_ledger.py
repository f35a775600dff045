"""The blocks of a WINDOW kind of cache entry: a ring a slot.

A layer whose attention sees a token and the ``W - 1`` before it needs a
slot's last ``W`` tokens and nothing else, whatever the context. Its pool
entries are of the window kind (a model names them: ``window_entries``,
``window``), and the engine keeps this second ledger for them beside the
one every model has: an id space of its own (the window pools are sized by
``nb`` here, not by the full kind's count) and a table of its own.

The table is a RING of ``width = ceil(W / bs) + 1`` columns: logical block
``b`` of a slot lies in column ``b % width``. Slot ``s`` OWNS blocks ``[1 +
s * width, 1 + (s + 1) * width)`` for as long as the engine lives: the
pools hold every slot's whole ring (that is what a deployment has to hold
anyway, since every slot may be past its first ``W`` tokens at once), so
there is no free list, nothing to reserve at admission, nothing that can
run dry and no table that changes. Once a slot's context is round the ring
a new logical block is WRITTEN AGAIN IN PLACE over the one ``width``
blocks before it, which lies wholly behind the window of every position
still to come (block ``b - width`` ends ``(width - 1) * bs + 1 > W``
positions before block ``b`` begins). The decode walk reads the ring as it
lies (softmax does not care in which order blocks arrive) and the prefill's
ONE scatter a pool takes the ring's columns as its targets. Giving a block
back to a free list and taking another would be the same contract at the
price of a table that changes every ``bs`` tokens a slot and of an
allocator that is never contended at this size; ``recycled`` counts the
blocks written again in place, which is what would have been given back.

Stale rows: the columns of a block that is being written again hold, past
the newest token, rows of the block ``width`` before, and a slot taken by a
new request holds its predecessor's rows. Every reader masks by position:
the walk and the banded history by ``[start, length)``, the dense gather
off a TPU by the position each row of the ring holds (``models/window_kv.py``
``ring_positions``).
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

__all__ = ["WindowLedger"]


class WindowLedger:
    def __init__(self, slots: int, window: int, block_size: int):
        if window < 1:
            raise ValueError(f"a window of {window} tokens")
        self.W = int(window)
        self.bs = int(block_size)
        self.width = -(-self.W // self.bs) + 1
        self.nb = slots * self.width + 1         # block 0 is the trash block
        self.table = (1 + np.arange(slots * self.width, dtype=np.int32)
                      ).reshape(slots, self.width)
        self.top = np.zeros(slots, np.int64)     # logical blocks reached
        self.recycled = 0

    def note_written(self, slot: int, n_logical: int) -> None:
        """The slot's context now reaches into logical block ``n_logical -
        1``: every block past the ring's width that it newly reached was
        written again in place."""
        n_logical, top = int(n_logical), int(self.top[slot])
        if n_logical > top:
            self.recycled += max(0, n_logical - max(top, self.width))
            self.top[slot] = n_logical

    def release(self, slot: int) -> None:
        self.top[slot] = 0

    # -- what the programs are given -----------------------------------------
    def write_ids(self, slot: int, b0: int, nblk: int, width: int
                  ) -> np.ndarray:
        """Targets of a piece's scatter: the ring's columns of logical
        blocks ``[b0, b0 + nblk)``, padded with the trash block to
        ``width``. Of a piece longer than the ring only the last ``width``
        blocks are kept (the others are behind the window of everything
        after the piece) and the rest go to the trash block, so no two
        rows of one scatter name one block."""
        ids = np.zeros(width, np.int32)
        kept = np.arange(max(0, nblk - self.width), nblk)
        ids[kept] = self.table[slot, (b0 + kept) % self.width]
        return ids

    def history(self, slot: int, hist: int) -> Tuple[np.ndarray, int]:
        """The blocks that hold what a piece starting at ``hist`` may see
        of its history, positions ``[max(0, hist - W + 1), hist)``, in
        logical order and padded with the trash block to the ring's width,
        and the position of the first of them."""
        first = max(0, hist - self.W + 1) // self.bs
        last = -(-hist // self.bs)
        tbl = np.zeros(self.width, np.int32)
        tbl[:last - first] = self.table[
            slot, np.arange(first, last) % self.width]
        return tbl, first * self.bs

    def walk_blocks(self, length: int) -> int:
        """Blocks a decode step's walk reads for a query at ``length``."""
        if length <= 0:
            return 0
        first = max(0, length - self.W + 1) // self.bs
        return -(-length // self.bs) - first

    # -- the invariant ---------------------------------------------------------
    def accounting(self) -> Dict[str, int]:
        """free + backed == total: a slot's ring columns that its context
        has reached are backed, the rest of its ring is free."""
        backed = int(np.minimum(self.top, self.width).sum())
        return {"total": self.nb - 1, "free": self.nb - 1 - backed,
                "backed": backed}
