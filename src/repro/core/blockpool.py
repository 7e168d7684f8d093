"""Ref-counted block allocator for the paged device KV pool.

The paged pool (PR 2) replaces per-request dense KV rows with one shared
pool of fixed-size token blocks: request r's cache is a *block table* —
a fixed-width vector of pool block ids — and a block may appear in many
tables at once.  This module is the host-side bookkeeping for that pool:

  * ``alloc()``      hand out a free block with refcount 1
  * ``ref(b)``       another holder (a table or the radix tier) shares b
  * ``unref(b)``     drop one holder; refcount 0 returns b to the free list

Block 0 is the **sentinel**: block tables are padded with it so inactive
pool rows and not-yet-allocated table entries route their (masked) writes
into one harmless scratch block.  It is pinned — never allocated, never
freed, never counted as live.

Invariants (property-tested in tests/test_paged_pool.py):

  I1  refcounts are >= 0; live blocks (refcount > 0) have refcount equal
      to the number of holders that acquired them
  I2  free-list and live sets are disjoint and together cover every
      non-sentinel block
  I3  a block is handed out at most once between free()s (no aliasing)

A **reserved-but-unfilled** block (speculative pre-allocation: decode
reserves the next table entry before the row's write position reaches it)
is indistinguishable from any other refcount-1 holding at this layer —
it is live, named by exactly one table, and returns through the same
``unref`` when its row releases, so I1-I3 cover it with no extra state.
What makes it "reserved" is purely that the owning row's fill has not
reached its positions yet, and the implicit-position masking upstream
guarantees nothing ever reads them.

Copy-on-write lives one level up (serving/paged.py): a shared block is
never written in place — divergence materializes a fresh block and the
new holder's table points at the copy.  The allocator only guarantees the
accounting that makes "is this block exclusively mine?" a cheap question
(``refcount(b) == 1``).
"""
from __future__ import annotations

from typing import List, Set

SENTINEL = 0


class BlockPoolExhausted(RuntimeError):
    """alloc() found no free block (caller should evict or reject)."""


class PoolSaturated(RuntimeError):
    """Admission cannot be covered RIGHT NOW but in-flight rows will free
    blocks as they finish — a transient, not a permanent reject.  The
    scheduler keeps the request queued and retries on a later step;
    ``AdmissionRejected`` is the permanent "can never fit" reject."""


class AdmissionRejected(ValueError):
    """The request can never be served: it needs more positions than a
    pool row holds, or more blocks than the whole pool can ever offer.
    The scheduler records it as ERRORED and serves the rest of the
    queue; any other error raised during admission propagates."""


class BlockAllocator:
    """Free-list + refcount accounting over ``num_blocks`` pool blocks."""

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (sentinel + 1 usable)")
        self.num_blocks = num_blocks
        self.block_size = block_size
        # LIFO free list: recently freed blocks are re-used first (their
        # pool pages are warm); sentinel 0 is excluded for good.
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        self._refs: List[int] = [0] * num_blocks
        self.stats = {"allocs": 0, "frees": 0, "shares": 0, "peak_live": 0}
        # optional core.faults.FaultPlan; "alloc" site simulates exhaustion
        self.fault_plan = None

    # ------------------------------------------------------------------
    def alloc(self) -> int:
        """A fresh block with refcount 1; raises BlockPoolExhausted."""
        if self.fault_plan is not None and self.fault_plan.should_fire("alloc"):
            raise BlockPoolExhausted("injected: alloc fault")
        if not self._free:
            raise BlockPoolExhausted(
                f"no free blocks (pool={self.num_blocks}, "
                f"live={self.num_live()})")
        b = self._free.pop()
        self._refs[b] = 1
        self.stats["allocs"] += 1
        self.stats["peak_live"] = max(self.stats["peak_live"],
                                      self.num_live())
        return b

    def alloc_many(self, n: int):
        """``n`` fresh blocks with refcount 1, atomically: the free-list
        check happens before anything is popped, so either all ``n`` are
        handed out or none is — a multi-block reservation can never
        strand a partial grab.  The batched analogue of calling ``alloc``
        n times; callers that can evict fall back to their per-block
        eviction loop when this raises."""
        if self.fault_plan is not None and self.fault_plan.should_fire("alloc"):
            raise BlockPoolExhausted("injected: alloc_many fault")
        if len(self._free) < n:
            raise BlockPoolExhausted(
                f"need {n} blocks, {len(self._free)} free "
                f"(pool={self.num_blocks}, live={self.num_live()})")
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._refs[b] = 1
        self.stats["allocs"] += n
        self.stats["peak_live"] = max(self.stats["peak_live"],
                                      self.num_live())
        return out

    def ref(self, block: int) -> int:
        """Acquire one more reference to a live block."""
        if block == SENTINEL:
            raise ValueError("cannot ref the sentinel block")
        if self._refs[block] <= 0:
            raise ValueError(f"ref of dead block {block}")
        self._refs[block] += 1
        self.stats["shares"] += 1
        return self._refs[block]

    def unref(self, block: int) -> int:
        """Drop one reference; refcount 0 frees the block.  Returns the
        remaining refcount."""
        if block == SENTINEL:
            raise ValueError("cannot unref the sentinel block")
        if self._refs[block] <= 0:
            raise ValueError(f"unref of dead block {block}")
        self._refs[block] -= 1
        if self._refs[block] == 0:
            self._free.append(block)
            self.stats["frees"] += 1
        return self._refs[block]

    def unref_many(self, blocks) -> int:
        """Drop one reference from each block in ``blocks`` — the
        speculative-rollback primitive: a rejected draft tail's reserved
        blocks leave their table and return here in one call.  Validity
        is checked for ALL blocks before any is released, so a bad id
        (sentinel, dead block) can never strand a partial rollback.
        Returns how many blocks the call actually freed (refcount hit
        0)."""
        blocks = list(blocks)
        for b in blocks:
            if b == SENTINEL:
                raise ValueError("cannot unref the sentinel block")
            if self._refs[b] <= 0:
                raise ValueError(f"unref of dead block {b}")
        freed = 0
        for b in blocks:
            if self.unref(b) == 0:
                freed += 1
        return freed

    # ------------------------------------------------------------------
    def refcount(self, block: int) -> int:
        return self._refs[block]

    def num_free(self) -> int:
        return len(self._free)

    def num_live(self) -> int:
        return self.num_blocks - 1 - len(self._free)

    def live_blocks(self) -> Set[int]:
        return {b for b in range(1, self.num_blocks) if self._refs[b] > 0}

    def free_blocks(self) -> Set[int]:
        return set(self._free)

    # ------------------------------------------------------------------
    def check(self) -> None:
        """Assert the allocator invariants (used by tests and the paged
        engine's debug mode)."""
        free = self.free_blocks()
        live = self.live_blocks()
        assert SENTINEL not in free and SENTINEL not in live
        assert not (free & live), f"aliased blocks: {free & live}"
        assert free | live == set(range(1, self.num_blocks)), \
            "free ∪ live must cover every non-sentinel block"
        assert len(self._free) == len(free), "duplicate free-list entries"
        assert all(r >= 0 for r in self._refs)
