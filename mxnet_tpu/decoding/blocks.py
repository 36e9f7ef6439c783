"""Block allocator + page tables: the host half of the paged KV cache.

The decode tier's memory problem (Ragged Paged Attention, PAPERS.md):
in-flight sequences have wildly different lengths and grow one token
per step, so a rectangular (batch, max_len) KV buffer wastes most of
its rows and forces the worst-case length on every sequence. Instead
the device holds ONE pool of fixed-size pages (`MXNET_DECODE_PAGE_SIZE`
tokens each) and every sequence owns a *page table* — an ordered list
of page ids covering its context. Allocation quantum = one page, so
per-sequence waste is bounded by page_size-1 tokens regardless of
length mix.

This module is pure host-side bookkeeping (no jax import): a free-list
allocator with reference counts. Ref counts make prefix sharing and
fork cheap: `fork()` returns a table aliasing every page (ref++), and
`make_writable()` implements copy-on-write — the first write to a
shared page allocates a private copy (the caller performs the actual
device page copy; the allocator only decides).

Page 0 is RESERVED as the scratch page: padding page-table entries and
inactive batch rows point at it, so the device kernel can always
gather/scatter a full (max_batch, pages_bucket) grid with no branch —
garbage lands in (or comes from) page 0 and is masked out by sequence
length. Page 0 is never handed to a sequence.

A model whose layers do not all keep the same positions states several
page GROUPS (`PageGroup`): each has a pool, an allocator and, for every
sequence, a page table of its own, all indexed alike (entry i holds
positions i*page_size ..). A WINDOWED group's layers read only a row's
last `window` positions: a page that falls wholly behind the window
goes back to the allocator while the row lives (`release_behind`) and
its table entry becomes the scratch page, which no query reads
unmasked.
"""
from __future__ import annotations

import threading
from typing import NamedTuple, Optional

from ..base import MXNetError

SCRATCH_PAGE = 0


class PageError(MXNetError):
    """Base class of paged-KV allocator errors."""


class PagePoolExhausted(PageError):
    """No free pages: the caller should preempt or shed load, never
    crash (CI gate iii proves the scheduler does)."""


def pages_needed(num_tokens, page_size):
    """Pages covering `num_tokens` positions (ceil division; 0 -> 0)."""
    return (int(num_tokens) + page_size - 1) // page_size


class PageGroup(NamedTuple):
    """One page table of a model's pool, as its configuration states
    it: the planes that name it share its page ids. `window` is how
    many positions a query of the group's layers reads, its own
    included (None: every earlier one)."""

    name: str = ""
    window: Optional[int] = None

    def first_page(self, position, page_size):
        """Index of the first page a query at `position` reads."""
        if not self.window:
            return 0
        return max(0, position - self.window + 1) // page_size


def cover(allocator, table, num_tokens, first=0):
    """Extend the position-indexed `table` to the pages of `num_tokens`
    positions: new entries before index `first` (behind a window) are
    the scratch page, the rest fresh pages. All or nothing."""
    need = pages_needed(num_tokens, allocator.page_size) - len(table)
    if need > 0:
        behind = max(0, min(first - len(table), need))
        fresh = allocator.alloc(need - behind)
        table.extend([SCRATCH_PAGE] * behind + fresh)


def release_behind(allocator, table, first):
    """Give back the pages of `table` before index `first` (wholly
    behind the window of the row's next query), leaving the scratch
    page in their place; returns how many. What is released is the run
    of held pages that ends there, so the cost is the run's."""
    i = min(first, len(table))
    gone = []
    while i > 0 and table[i - 1] != SCRATCH_PAGE:
        i -= 1
        gone.append(table[i])
        table[i] = SCRATCH_PAGE
    if gone:
        allocator.free(gone)
    return len(gone)


class BlockAllocator:
    """Free-list allocator over a pool of `num_pages` fixed-size pages.

    Thread-safe; all operations are O(pages touched). Invariants
    (checked by `check()` and tests/test_decoding.py):

      * every page is free XOR has refcount >= 1,
      * page 0 (scratch) is permanently pinned, never allocated,
      * free pages hold refcount 0 and appear exactly once in the
        free list.
    """

    def __init__(self, num_pages, page_size):
        if num_pages < 2:
            raise PageError(
                f"pool needs >= 2 pages (1 is reserved scratch), "
                f"got {num_pages}")
        if page_size < 1:
            raise PageError(f"invalid page_size {page_size}")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self._lock = threading.Lock()
        # LIFO free list: recently-freed pages are reused first, which
        # keeps the working set of touched pages small
        self._free = list(range(self.num_pages - 1, 0, -1))
        self._refs = [0] * self.num_pages
        self._refs[SCRATCH_PAGE] = 1  # pinned forever
        self._low_watermark = len(self._free)
        # cumulative pages ever allocated (alloc + COW copies): the
        # work-avoided evidence of prefix sharing — a cache hit refs
        # instead of allocating, so this counter, not occupancy, is
        # what the decode-gate's shared-prefix arm compares
        self._allocated_total = 0
        # every allocator of the pool this one belongs to, itself
        # first: the engine of a model with several page groups sets
        # it on the first group's allocator, so that `check()` there
        # covers them all
        self.groups = (self,)

    # ------------------------------------------------------------ state
    def free_pages(self):
        with self._lock:
            return len(self._free)

    def pages_in_use(self):
        with self._lock:
            return (self.num_pages - 1) - len(self._free)

    def capacity(self):
        """Allocatable pages (pool minus the pinned scratch page)."""
        return self.num_pages - 1

    def occupancy(self):
        """Fraction of allocatable pages currently owned."""
        with self._lock:
            used = (self.num_pages - 1) - len(self._free)
        return used / max(1, self.num_pages - 1)

    def low_watermark(self):
        """Fewest free pages ever observed (capacity-planning signal)."""
        with self._lock:
            return self._low_watermark

    def refcount(self, page):
        with self._lock:
            return self._refs[page]

    # ------------------------------------------------------- operations
    def alloc(self, n=1):
        """n fresh pages with refcount 1, or PagePoolExhausted (the
        allocation is all-or-nothing: no partial grab to roll back)."""
        n = int(n)
        with self._lock:
            if n > len(self._free):
                raise PagePoolExhausted(
                    f"need {n} pages, {len(self._free)} free "
                    f"(pool {self.num_pages - 1}); preempt or wait")
            out = [self._free.pop() for _ in range(n)]
            for p in out:
                self._refs[p] = 1
            self._allocated_total += n
            if len(self._free) < self._low_watermark:
                self._low_watermark = len(self._free)
            return out

    def ref(self, pages):
        """Share: refcount++ on each page of an allocated table."""
        with self._lock:
            for p in pages:
                if p == SCRATCH_PAGE:
                    continue
                if self._refs[p] <= 0:
                    raise PageError(f"ref of free page {p}")
                self._refs[p] += 1

    def free(self, pages):
        """Release ownership: refcount--, returning pages whose count
        hit zero to the free list. Scratch entries are ignored, so a
        padded table can be freed wholesale."""
        with self._lock:
            for p in pages:
                if p == SCRATCH_PAGE:
                    continue
                if self._refs[p] <= 0:
                    raise PageError(f"double free of page {p}")
                self._refs[p] -= 1
                if self._refs[p] == 0:
                    self._free.append(p)

    def fork(self, table):
        """Copy-on-write fork: a new table aliasing every page of
        `table` (refcount++ each). Writes through either table must go
        via `make_writable` first."""
        self.ref(table)
        return list(table)

    def make_writable(self, table, idx):
        """Ensure table[idx] is exclusively owned before a write.

        Returns (page, copy_from): `page` is the id now safe to write
        (table is updated in place); `copy_from` is the old page id
        when a copy-on-write allocation happened (the CALLER must copy
        the device page copy_from -> page before writing), else None.
        """
        page = table[idx]
        if page == SCRATCH_PAGE:
            raise PageError("cannot write through a scratch entry")
        with self._lock:
            if self._refs[page] <= 0:
                raise PageError(f"write through freed page {page}")
            if self._refs[page] == 1:
                return page, None
            # shared: break the alias with a private copy
            if not self._free:
                raise PagePoolExhausted(
                    "copy-on-write needs a free page; preempt or wait")
            fresh = self._free.pop()
            self._refs[fresh] = 1
            self._refs[page] -= 1
            self._allocated_total += 1
            if len(self._free) < self._low_watermark:
                self._low_watermark = len(self._free)
        table[idx] = fresh
        return fresh, page

    # ------------------------------------------------------- validation
    def check(self):
        """Raise PageError on any broken invariant, in this allocator
        or another group's of the same pool (test hook)."""
        for a in self.groups:
            a._check()

    def _check(self):
        with self._lock:
            free = set(self._free)
            if len(free) != len(self._free):
                raise PageError("duplicate pages in free list")
            if SCRATCH_PAGE in free or self._refs[SCRATCH_PAGE] < 1:
                raise PageError("scratch page escaped its pin")
            for p in range(1, self.num_pages):
                if (p in free) == (self._refs[p] > 0):
                    raise PageError(
                        f"page {p}: free={p in free} "
                        f"refs={self._refs[p]}")

    def stats(self):
        with self._lock:
            free = len(self._free)
        return {
            "pages_total": self.num_pages - 1,
            "pages_free": free,
            "pages_in_use": (self.num_pages - 1) - free,
            "free_low_watermark": self._low_watermark,
            "page_size": self.page_size,
            "pages_allocated": self._allocated_total,
        }
