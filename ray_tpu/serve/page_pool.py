"""HOST side of the paged KV cache: which physical pages each slot holds.

The device side (`ops/paged_kv.py`) knows how a page is laid out; this class
knows who holds which. The engine asks it for a reservation's size, for the
pages, and for the block table the decode program is handed; it never does
page arithmetic of its own.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np


class PagePool:
    """Pages 1..n_pages-1 of the arena, handed out to slots. Page 0 is the
    NULL page and is never granted: a slot's table row is padded with it.

    A request RESERVES everything it can ever need (`pages_for`) before it
    is admitted, so growth never fails mid-decode and there is no
    preemption path (vLLM's watermark policy, made strict). A 50-token
    request holds ceil(50/page) pages, not a max_seq strip: concurrency is
    bounded by TOKENS in flight, not by worst-case sequences."""

    def __init__(self, n_slots: int, max_seq: int, page_size: int,
                 n_pages: Optional[int] = None):
        self.max_seq = max_seq
        self.page = min(page_size, max_seq)
        self.maxp = -(-max_seq // self.page)
        if n_pages is None:
            # Null page + half the worst case: density comes from short
            # requests reserving only what len+max_tokens needs.
            n_pages = 1 + max(self.maxp, (n_slots * self.maxp + 1) // 2)
        if n_pages < 1 + self.maxp:
            raise ValueError(
                f"n_pages={n_pages} cannot hold one max_seq request "
                f"({self.maxp} pages of {self.page} tokens) + null page")
        self.n_pages = n_pages
        # Popped from the end and returned to the end: the order in which
        # physical pages are granted is part of a run's reproducibility.
        self._free: List[int] = list(range(n_pages - 1, 0, -1))
        self._held: List[List[int]] = [[] for _ in range(n_slots)]
        # [n_slots, maxp] physical page ids, mutated in place: a dispatch
        # hands the device a COPY.
        self.block_table = np.zeros((n_slots, self.maxp), np.int32)

    def pages_for(self, prompt_len: int, max_tokens: int) -> int:
        """Pages a request reserves: every position it can reach."""
        return -(-min(prompt_len + max_tokens, self.max_seq) // self.page)

    @property
    def free(self) -> int:
        return len(self._free)

    def in_use(self) -> int:
        return (self.n_pages - 1) - len(self._free)

    def grant(self, slot: int, need: int) -> np.ndarray:
        """Give `slot` `need` pages; -> its table row, int32[maxp], padded
        with the null page (a copy). Refused, with nothing changed, when the
        pages are not there or the slot still holds some."""
        if need > len(self._free) or self._held[slot]:
            raise RuntimeError(
                f"slot {slot}: {need} pages asked, {len(self._free)} free, "
                f"{len(self._held[slot])} held")
        pages = [self._free.pop() for _ in range(need)]
        self._held[slot] = pages
        self.block_table[slot, :] = 0
        self.block_table[slot, :need] = pages
        return self.block_table[slot].copy()

    def release(self, slot: int) -> None:
        """Return the slot's pages; its table row goes back to the null
        page."""
        self._free.extend(self._held[slot])
        self._held[slot] = []
        self.block_table[slot, :] = 0
