"""Paged KV-cache manager for the serving engine.

Owns the *bookkeeping* of the shared block pool — block tables, sequence
lengths, reference counts — while the pool tensors themselves (one
``[num_blocks, block_size, Hkv, D]`` pair per layer) live on the engine as
:class:`~paddle_tpu.ops.paged_attention.PagedCache` state threaded through
the jitted step.  This is the Ragged-Paged-Attention shape (PAPERS.md): a
ragged batch of sequences at different lengths indexes one block pool
through per-sequence tables, so admission/eviction never reshapes anything
the compiler sees.

Graceful degradation contract: allocation never partially succeeds, and
exhaustion is a *scheduling event*, not an error — the engine preempts the
lowest-priority running request (freeing its blocks for recompute later)
instead of failing anyone.  Block 0 is the reserved null page that padding
rows of a bucketed batch write into.

Two kinds of memory (ISSUE 33): beside pages, which grow a token at a
time, a layer may declare state of FIXED size a sequence (a selective scan's
recurrent state: ``CacheSpec.state``), kept in ``state_slots`` slots.  One
manager owns both: a sequence takes its slot with its first block and gives
it back with its last, a slot is never shared, and running out of either is
the same scheduling event.  The slot of a sequence IS the id of its first
block: with ``state_slots = S`` the ids ``1..S`` are handed out as FIRST
blocks only and every later block comes from ``S+1..``, so the step
programs find a row's slot in the block table they already take
(``tables[:, 0]``; 0, the null page, is the null slot of padding rows).

Multi-chip (ISSUE 5): this manager is **per-process host state and stays
replicated** when the engine serves tensor-parallel over the ``mp`` mesh
axis.  The pool tensors shard along the head dim on device, but a block
index means the same page on every shard, so the same table/refcount/
hash bookkeeping routes all N shards — capacity, admission, preemption
and prefix-cache math are all mp-invariant (per-shard block bytes =
``block_size * Hkv/mp * D * itemsize``).
"""

from __future__ import annotations

from typing import List, Optional, Tuple

from ..ops.paged_attention import (  # noqa: F401  (PoolExhausted re-export)
    BlockPool,
    PoolExhausted,
)


class KVCacheManager(BlockPool):
    """Refcounted block-pool bookkeeping (no device tensors).

    The free-list / refcount / fork core is
    :class:`~paddle_tpu.ops.paged_attention.BlockPool` — the same
    implementation :class:`~paddle_tpu.ops.paged_attention.BlockKVCache`
    uses, so the invariants cannot drift.  Here one pool is shared across
    *all* layers: every layer's tensors use the same block index for a
    given (sequence, position), which is what lets one routing array drive
    the whole decoder stack.  This subclass adds the serving-loop surface:
    decode-slot reservation (``append_slot``/``commit``) and gauges.

    With ``enable_prefix_cache=True`` (the serving default) the base
    pool's automatic prefix caching is active: full prompt blocks are
    content-hashed after prefill, refcount-0 cached blocks park in a
    bounded reuse LRU instead of being clobbered, and admission forks the
    longest cached block-prefix of a new prompt for free
    (``fork_prefix``).  Capacity planning must then use
    :attr:`num_available` (free + evictable-cached), not ``num_free``.

    With ``state_slots=S`` (a model whose layers declare per-sequence
    state) the manager owns ``S`` slots beside the pages (module
    docstring): blocks ``1..S`` are first blocks only (a sequence's slot
    is ``table(seq)[0]``), :attr:`state_slots_held` is how many are taken,
    and :attr:`num_available` counts the blocks any token can take
    (``S+1..``).  A recurrent state cannot be forked from a block prefix,
    so ``state_slots`` and the prefix cache exclude each other.
    """

    def __init__(self, num_blocks: int, block_size: int,
                 enable_prefix_cache: bool = True, state_slots: int = 0):
        super().__init__(num_blocks, block_size,
                         enable_prefix_cache=enable_prefix_cache)
        self.state_slots = int(state_slots)
        self._free_slots: list = []
        if self.state_slots:
            if enable_prefix_cache:
                raise ValueError(
                    "state_slots with the prefix cache on: a recurrent state "
                    "cannot be forked from a block prefix")
            if num_blocks < 2 * self.state_slots + 1:
                raise ValueError(
                    f"{self.state_slots} state slots need at least "
                    f"{2 * self.state_slots + 1} blocks (the null page, a "
                    "first block a slot and one more a sequence)")
            # ids 1..S leave the common free list: first blocks only
            self._free = list(range(num_blocks - 1, self.state_slots, -1))
            self._free_slots = list(range(self.state_slots, 0, -1))
        # fault injection (ISSUE 12): while True, the pool reports zero
        # available capacity — the `pool_exhaust` injection point.  The
        # engine arms it for exactly ONE scheduler-planning pass, so the
        # refusal surfaces as a preemption/deferral scheduling event
        # (token-identical recompute), never as a failed launch.
        self.refuse_allocations = False

    # --- capacity ----------------------------------------------------------
    @property
    def num_free(self) -> int:
        """Free blocks of both lists: the common one and, with state
        slots, the first blocks no sequence holds."""
        return len(self._free) + len(self._free_slots)

    @property
    def num_available(self) -> int:
        if self.refuse_allocations:
            return 0
        return super().num_available

    def occupancy(self) -> float:
        """Fraction of the usable pool currently held by sequences.
        Reuse-LRU blocks (cached content, no owner) count as free capacity
        — they are evictable on demand."""
        usable = self.num_blocks - 1
        free = self.num_available + len(self._free_slots)
        return (usable - free) / usable if usable else 0.0

    # --- per-sequence state slots ---------------------------------------------
    @property
    def state_slots_held(self) -> int:
        return self.state_slots - len(self._free_slots)

    def can_start_sequence(self) -> bool:
        """Whether a sequence that holds nothing yet can take its first
        block: always without state slots, else while a slot is free."""
        return not self.state_slots or bool(self._free_slots)

    def blocks_needed(self, seq_id, num_tokens: int) -> int:
        """Blocks off the COMMON free list: a first block comes with the
        sequence's slot and is not counted against ``num_available``."""
        need = super().blocks_needed(seq_id, num_tokens)
        if self.state_slots and need and not self._tables.get(seq_id):
            need -= 1
        return need

    def allocate(self, seq_id, num_tokens: int,
                 cause: str = "other") -> bool:
        if self.state_slots and not self._tables.get(seq_id) \
                and super().blocks_needed(seq_id, num_tokens):
            # all or nothing, the slot included: no slot, or too few blocks
            # for the rest, takes nothing
            if not self._free_slots or \
                    self.blocks_needed(seq_id, num_tokens) > self.num_available:
                return False
            first = self._free_slots.pop()
            self._ref[first] = 1
            self._tables[seq_id] = [first]
        return super().allocate(seq_id, num_tokens, cause)

    def free(self, seq_id) -> int:
        returned = super().free(seq_id)
        self._return_slots()
        return returned

    def _return_slots(self) -> None:
        """A first block just released sits at the tail of the common free
        list (a table is released last block first): back to the slots."""
        while self._free and self._free[-1] <= self.state_slots:
            self._free_slots.append(self._free.pop())

    def burst_capacity(self, rows: int) -> int:
        """Largest per-row decode-burst length N the pool can promise
        ``rows`` concurrent decode rows (ISSUE 19).  Called AFTER the
        scheduler reserved each row's next-token slot (``append_slot``),
        so a row holding blocks for ``p+1`` tokens needs at most
        ``ceil((N-1)/block_size)`` additional blocks for N total burst
        tokens, even when every row sits on the worst-case block
        boundary.  The closed form below is exactly that bound inverted:
        giving each row ``num_available // rows`` whole extra blocks
        supports ``(num_available // rows) * block_size + 1`` tokens.

        ONE accessor shared by the scheduler's plan and the engine's
        launch clamp — the PR 1 promised-blocks lesson: two copies of
        headroom math WILL disagree one preemption later."""
        if rows <= 0:
            return 0
        return (self.num_available // rows) * self.block_size + 1

    # --- allocation --------------------------------------------------------
    def append_slot(self, seq_id) -> Optional[Tuple[int, int]]:
        """(block, offset) slot for the sequence's NEXT token, allocating a
        fresh block on a boundary.  ``None`` on exhaustion — the caller
        preempts and retries.  Does not advance the length: ``commit``
        does, after the model step actually wrote the slot."""
        if not self.allocate(seq_id, 1, cause="decode_slot"):
            return None
        pos = self._lens.get(seq_id, 0)
        table = self._tables[seq_id]
        return table[pos // self.block_size], pos % self.block_size

    def commit(self, seq_id, num_tokens: int = 1):
        self._lens[seq_id] = self._lens.get(seq_id, 0) + num_tokens

    def truncate(self, seq_id, new_len: int) -> int:
        """Roll the sequence back to ``new_len`` committed tokens,
        returning surplus tail blocks to the pool (ISSUE 18: spec-decode
        rejection rollback — the preemption-recompute slot discipline
        aimed at a length instead of zero).  Tail blocks whose refcount
        hits 0 go straight to the free list: their content is a
        rejected-draft suffix, not cacheable prefix material (spec-draft
        blocks are freshly allocated and never hashed; a still-shared
        block just drops this owner's reference).  Stale K/V left in the
        KEPT tail block past ``new_len`` is dead weight the per-row
        ``lens`` routing never attends to, and the next decode/verify
        slot overwrites it.  Returns the number of blocks freed."""
        cur = self._lens.get(seq_id, 0)
        if new_len > cur:
            raise ValueError(
                f"truncate({seq_id!r}, {new_len}) extends past the "
                f"committed length {cur}")
        table = self._tables.get(seq_id)
        freed = 0
        if table is not None:
            keep = self.blocks_for(new_len)
            while len(table) > keep:
                b = table.pop()
                n = self._ref.get(b, 1) - 1
                if n > 0:
                    self._ref[b] = n
                    continue
                self._ref.pop(b, None)
                self._drop_hash(b)  # no-op for never-hashed draft blocks
                self._free.append(b)
                freed += 1
        self._lens[seq_id] = new_len
        self._return_slots()
        return freed

    # --- views -------------------------------------------------------------
    def table(self, seq_id) -> List[int]:
        return self._tables.get(seq_id, [])

    def seq_len(self, seq_id) -> int:
        return self._lens.get(seq_id, 0)

    def has(self, seq_id) -> bool:
        return seq_id in self._tables

    def num_owned_blocks(self, seq_id) -> int:
        return len(self._tables.get(seq_id, ()))
