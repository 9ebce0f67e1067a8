"""Paged (block) KV-cache attention for serving.

Capability analog of the reference's
``phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu`` (paged
KV-cache attention à la vLLM): the KV cache lives in fixed-size blocks
indexed per-sequence through a block table, so sequences share a global
block pool with no per-request contiguous allocation.

TPU-first: the cache pool is a dense ``[num_blocks, block_size, H, D]``
array updated with scatter writes (XLA keeps it resident in HBM and donates
the buffer between decode steps under jit); the gather of a sequence's
blocks is one ``take`` along the block dim — compiler-friendly static
shapes with a length mask instead of dynamic slicing.

Multi-chip (ISSUE 5): the pool tensors shard along the **head** dim over
the ``mp`` mesh axis (:func:`shard_kv_pool`) while every bookkeeping
structure — block tables, free list, refcounts, hashes — stays host-side
and replicated: one block index means the same page on every shard, so a
single scheduler decision routes N shards and only the per-block byte
footprint divides by mp.
"""

from __future__ import annotations

import hashlib
import math
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor


class PoolExhausted(RuntimeError):
    """The shared KV block pool has no free block for the request.

    A *typed* RuntimeError so serving layers can catch it and degrade
    gracefully (preempt + recompute, ``serving/kv_manager.py``) instead of
    failing the request."""


#: Root of every block-hash chain (the hash of the empty prefix).
#: Chains use SHA-256, not builtin ``hash()``: cached blocks are content-
#: addressed across tenants, so a collision silently serves one prompt's
#: KV to another — with a 64-bit non-cryptographic hash that is both
#: reachable at volume and constructible by an adversarial prompt.
_HASH_ROOT = hashlib.sha256(b"paddle_tpu.prefix_cache.v1").digest()


def _hash_block(parent: bytes, block_tokens) -> bytes:
    m = hashlib.sha256(parent)
    m.update(b"".join(int(t).to_bytes(8, "little", signed=True)
                      for t in block_tokens))
    return m.digest()


def prefix_chain_hashes(token_ids, block_size: int,
                        max_blocks: Optional[int] = None) -> List[bytes]:
    """Chain hashes of the leading FULL blocks of ``token_ids`` —
    ``out[i]`` commits to every token in blocks ``0..i`` (the same
    ``h_i = sha256(h_{i-1} || block_tokens_i)`` chain the prefix cache
    registers).  This is the shareable form of the hash walk: a router
    can compute it ONCE per request for prefix-affinity placement and
    hand it to :meth:`BlockPool.match_prefix` via ``precomputed=`` so
    admission does not re-hash the same leading blocks."""
    n = len(token_ids) // block_size
    if max_blocks is not None:
        n = min(n, max_blocks)
    out: List[bytes] = []
    h = _HASH_ROOT
    for i in range(n):
        h = _hash_block(h, token_ids[i * block_size:(i + 1) * block_size])
        out.append(h)
    return out


class BlockPool:
    """Refcounted block-pool bookkeeping (no device tensors) — the ONE
    implementation of the free-list / refcount / fork invariants, shared
    by :class:`BlockKVCache` (op layer) and the serving layer's
    :class:`~paddle_tpu.serving.KVCacheManager`.  Block 0 is the reserved
    null page that padding rows of a bucketed batch write into.

    **Prefix caching** (``enable_prefix_cache=True``): a FULL block whose
    content is the KV of a known token chain carries a chain hash
    ``h_i = sha256(h_{i-1} || block_tokens_i)`` registered via
    :meth:`record_block_hashes`.  When its last owner frees it, the block
    parks in a reuse LRU instead of the free list — content intact,
    revivable by :meth:`fork_prefix` at zero recompute cost — and is
    evicted (clobbered) only when an allocation cannot be covered by the
    free list alone.  All hash/LRU structures are bounded by the pool
    itself: at most ``num_blocks`` entries each, ever.
    """

    def __init__(self, num_blocks: int, block_size: int,
                 enable_prefix_cache: bool = False):
        if num_blocks < 2:
            raise ValueError("need at least 2 blocks (block 0 is the null page)")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.prefix_cache_enabled = enable_prefix_cache
        self._free: list = list(range(num_blocks - 1, 0, -1))
        self._ref: dict = {}     # block -> owner count (shared prefixes)
        self._tables: dict = {}  # seq_id -> list[int]
        self._lens: dict = {}    # seq_id -> int
        # prefix-cache state — every structure is pool-bounded (≤ one
        # entry per block), enforced by the invariants above
        self._block_hash: dict = {}   # unbounded-ok: ≤ num_blocks entries (block -> chain hash)
        self._hash_index: dict = {}   # unbounded-ok: ≤ num_blocks entries (chain hash -> block)
        self._chain_state: dict = {}  # unbounded-ok: ≤ live seqs (seq -> (blocks_hashed, last_hash)) so per-chunk re-registration hashes only NEW blocks
        self.cache_epoch = 0  # bumped whenever _hash_index changes, so
                              # callers may memoize match_prefix results
                              # keyed by (token_ids, epoch)
        self._reuse: "OrderedDict" = OrderedDict()  # unbounded-ok: ≤ num_blocks entries (refcount-0 cached blocks, LRU)
        self.reuse_evictions = 0  # monotonic: cached blocks clobbered for allocation
        self.reuse_hits = 0       # monotonic: blocks served from the prefix cache
        # --- observability hooks (ISSUE 13) --------------------------------
        # host-side, fired synchronously on the mutating thread; a hook
        # exception is swallowed — telemetry must never tear the pool's
        # free-list/refcount bookkeeping mid-mutation.
        self.on_evict = None   # fn(block, chain_depth, lifetime_steps, cause)
        self.on_revive = None  # fn(block, chain_depth, lru_depth, lifetime_steps)
        self.clock = 0         # caller-advanced step clock (the serving
                               # engine stamps step_seq) — park lifetimes
                               # are measured in these ticks
        self._block_depth: dict = {}  # unbounded-ok: ≤ num_blocks entries (block -> chain depth)
        self._park_step: dict = {}    # unbounded-ok: ≤ num_blocks entries (block -> clock at refcount-0 park)
        # --- block transfer (ISSUE 20) -------------------------------------
        # per-registered-block content identity: the tokens the chain hash
        # committed to, and the parent digest — what export_blocks /
        # export_chain serialize so a RECIPIENT pool can re-verify the
        # chain from _HASH_ROOT before admitting foreign KV content
        self._block_tokens: dict = {}  # unbounded-ok: ≤ num_blocks entries (block -> token tuple)
        self._block_parent: dict = {}  # unbounded-ok: ≤ num_blocks entries (block -> parent chain hash)

    @property
    def num_free(self) -> int:
        """Blocks on the free list proper (never held cached content)."""
        return len(self._free)

    @property
    def num_available(self) -> int:
        """Blocks an allocation can take: free list + evictable reuse LRU.
        The capacity number schedulers must plan against — a drained pool
        with a warm prefix cache has ``num_free < num_available``."""
        return len(self._free) + len(self._reuse)

    def blocks_for(self, num_tokens: int) -> int:
        return -(-num_tokens // self.block_size)

    def blocks_needed(self, seq_id, num_tokens: int) -> int:
        cur = self._lens.get(seq_id, 0)
        held = len(self._tables.get(seq_id, ()))
        return max(0, self.blocks_for(cur + num_tokens) - held)

    def can_allocate(self, seq_id, num_tokens: int) -> bool:
        return self.blocks_needed(seq_id, num_tokens) <= self.num_available

    def _take_block(self, cause: str = "other") -> int:
        """One block for a fresh allocation: free list first; then evict
        the LRU-oldest reusable cached block (its hash entries die with
        its content — a later prompt with that prefix just recomputes).
        An eviction fires :attr:`on_evict` with the clobbered block's
        chain depth, its park lifetime (in :attr:`clock` ticks), and the
        ``cause`` of the allocation (ISSUE 13 event-driven accounting —
        no more per-step counter diffing)."""
        if self._free:
            return self._free.pop()
        b, _ = self._reuse.popitem(last=False)
        depth = self._block_depth.get(b, 0)
        lifetime = self.clock - self._park_step.pop(b, self.clock)
        self._drop_hash(b)
        self.reuse_evictions += 1
        cb = self.on_evict
        if cb is not None:
            try:
                cb(b, depth, lifetime, cause)
            except Exception:
                pass  # swallow-ok: telemetry must never tear the pool bookkeeping mid-allocation
        return b

    def _drop_hash(self, b: int) -> None:
        h = self._block_hash.pop(b, None)
        self._block_depth.pop(b, None)
        self._block_tokens.pop(b, None)
        self._block_parent.pop(b, None)
        if h is not None and self._hash_index.get(h) == b:
            del self._hash_index[h]
            self.cache_epoch += 1

    def allocate(self, seq_id, num_tokens: int,
                 cause: str = "other") -> bool:
        """All-or-nothing reservation of blocks for ``num_tokens`` more
        tokens; returns False (taking nothing) when the pool can't cover
        it, so the state stays clean for the caller's preemption/retry.
        ``cause`` labels any reuse-LRU eviction this allocation forces
        (``decode_slot`` / ``prefill_chunk`` / ``other``)."""
        need = self.blocks_needed(seq_id, num_tokens)
        if need > self.num_available:
            return False
        table = self._tables.setdefault(seq_id, [])
        for _ in range(need):
            b = self._take_block(cause)
            self._ref[b] = 1
            table.append(b)
        return True

    def fork(self, src_seq, dst_seq) -> int:
        """Share ``src_seq``'s FULL blocks with ``dst_seq`` (refcount++, no
        copy).  Only whole blocks are shared — appends always land in
        blocks the destination owns alone, so no copy-on-write is ever
        needed.  Returns the number of tokens ``dst_seq`` starts with."""
        if dst_seq in self._tables:
            raise ValueError(f"fork target seq {dst_seq!r} already exists")
        n_full = self._lens.get(src_seq, 0) // self.block_size
        shared = self._tables.get(src_seq, [])[:n_full]
        for b in shared:
            self._ref[b] = self._ref.get(b, 1) + 1
        self._tables[dst_seq] = list(shared)
        self._lens[dst_seq] = n_full * self.block_size
        return n_full * self.block_size

    def free(self, seq_id) -> int:
        """Release the sequence; returns how many blocks became available
        again (shared blocks stay out until their last owner frees).  With
        the prefix cache on, a hashed block parks in the reuse LRU instead
        of the free list — still counted as available, but revivable.
        Within one sequence, later-chain blocks enter the LRU eviction
        side first, so a shrinking cache keeps the shortest (most
        shareable) prefixes longest."""
        returned = 0
        for b in reversed(self._tables.pop(seq_id, [])):
            n = self._ref.get(b, 1) - 1
            if n > 0:
                self._ref[b] = n
                continue
            self._ref.pop(b, None)
            returned += 1
            if self.prefix_cache_enabled and b in self._block_hash:
                self._reuse[b] = self._block_hash[b]
                self._park_step[b] = self.clock  # lifetime starts here
            else:
                self._free.append(b)
        self._lens.pop(seq_id, None)
        self._chain_state.pop(seq_id, None)
        return returned

    # --- prefix cache -------------------------------------------------------
    def match_prefix(self, token_ids,
                     precomputed: Optional[List[bytes]] = None) -> List[int]:
        """Blocks holding the longest cached block-prefix of ``token_ids``,
        capped so at least ONE token is always left to compute (the
        prefill must still produce last-token logits).  The chain hash
        ``h_i`` commits to every token in blocks 0..i, so one dict lookup
        per block walks the prefix — hashing stops at the first miss (a
        cold cache costs ONE block hash, not the whole prompt).

        ``precomputed`` (optional) carries leading chain hashes already
        computed elsewhere over the SAME leading tokens — e.g. the fleet
        router's prefix-affinity key (:func:`prefix_chain_hashes`) — so
        block ``i < len(precomputed)`` skips its hash; the walk resumes
        the chain from the last precomputed digest."""
        if not self.prefix_cache_enabled or len(token_ids) < 2:
            return []
        limit = (len(token_ids) - 1) // self.block_size
        bs = self.block_size
        blocks, h = [], _HASH_ROOT
        for i in range(limit):
            if precomputed is not None and i < len(precomputed):
                h = precomputed[i]
            else:
                h = _hash_block(h, token_ids[i * bs:(i + 1) * bs])
            b = self._hash_index.get(h)
            if b is None:
                break
            blocks.append(b)
        return blocks

    def reuse_count(self, blocks) -> int:
        """How many of ``blocks`` sit in the reuse LRU (refcount 0) —
        those leave the available set when forked, so schedulers must
        budget ``uncached_need + reuse_count``."""
        return sum(1 for b in blocks if b in self._reuse)

    def probe_prefix(self, token_ids) -> Tuple[int, int]:
        """(hit_blocks, of_which_from_reuse) for admission planning — no
        state change."""
        blocks = self.match_prefix(token_ids)
        return len(blocks), self.reuse_count(blocks)

    def fork_prefix(self, seq_id, token_ids, blocks: Optional[List[int]] = None) -> int:
        """Start ``seq_id`` on the longest cached block-prefix of
        ``token_ids``: live cached blocks gain an owner (refcount++),
        reuse-LRU blocks are revived (refcount 0 → 1) — zero recompute
        either way.  Returns the number of cached tokens the sequence
        starts with (0 on a cold miss or with the cache disabled).
        ``blocks`` skips re-hashing when the caller just ran
        :meth:`match_prefix` with NO pool mutation in between (admission
        probes then forks in one pass)."""
        if seq_id in self._tables:
            raise ValueError(f"fork target seq {seq_id!r} already exists")
        if blocks is None:
            blocks = self.match_prefix(token_ids)
        if blocks:
            self._chain_state[seq_id] = (
                len(blocks), self._block_hash[blocks[-1]])
        cb = self.on_revive
        # LRU position of each parked block BEFORE any revival mutates
        # the order: index 0 = the eviction end (would have been
        # clobbered by the very next allocation) — what the hit-depth
        # histogram records (ISSUE 13).  Built only when a subscriber
        # exists and a revive is possible: the O(len(_reuse)) walk must
        # not tax hook-less pool users or cold-miss forks.
        lru_order = ({b: i for i, b in enumerate(self._reuse)}
                     if cb is not None and blocks else None)
        for i, b in enumerate(blocks):
            if b in self._reuse:
                del self._reuse[b]
                self._ref[b] = 1
                lifetime = self.clock - self._park_step.pop(b, self.clock)
                if cb is not None:
                    try:
                        cb(b, self._block_depth.get(b, i + 1),
                           lru_order[b], lifetime)
                    except Exception:
                        pass  # swallow-ok: telemetry must never tear the pool bookkeeping mid-revive
            else:
                self._ref[b] = self._ref.get(b, 0) + 1
        self.reuse_hits += len(blocks)
        self._tables[seq_id] = list(blocks)
        self._lens[seq_id] = len(blocks) * self.block_size
        return len(blocks) * self.block_size

    def record_block_hashes(self, seq_id, token_ids,
                            num_tokens: Optional[int] = None) -> int:
        """Index ``seq_id``'s full blocks covered by the first
        ``num_tokens`` of ``token_ids`` (default: all — only tokens whose
        KV has been WRITTEN: callers register after the compute that fills
        the pages).  Idempotent; first block to claim a chain hash keeps
        it.  Returns how many new blocks were indexed.

        Incremental: the per-sequence chain state remembers how far this
        sequence has already been hashed, so a chunked prefill that
        registers after every chunk hashes each block ONCE over the whole
        prompt, not once per chunk (O(L) total, not O(L²))."""
        if not self.prefix_cache_enabled:
            return 0
        table = self._tables.get(seq_id, [])
        upto = len(token_ids) if num_tokens is None else num_tokens
        n_full = min(upto // self.block_size, len(table))
        done, h = self._chain_state.get(seq_id, (0, _HASH_ROOT))
        if done > n_full:  # recompute path restarted shorter: re-walk
            done, h = 0, _HASH_ROOT
        bs = self.block_size
        added = 0
        for i in range(done, n_full):
            parent = h
            h = _hash_block(h, token_ids[i * bs:(i + 1) * bs])
            b = table[i]
            if b in self._block_hash or h in self._hash_index:
                continue
            self._block_hash[b] = h
            self._block_depth[b] = i + 1  # chain depth in blocks
            self._block_tokens[b] = tuple(
                int(t) for t in token_ids[i * bs:(i + 1) * bs])
            self._block_parent[b] = parent
            self._hash_index[h] = b
            added += 1
        self._chain_state[seq_id] = (n_full, h)
        if added:
            self.cache_epoch += 1
        return added

    def block_chain_hash(self, block: int) -> Optional[bytes]:
        """Chain hash registered for ``block`` (``None`` when unhashed)
        — the prefix-heat table's key (ISSUE 13): the DEEPEST matched
        block's hash commits to the whole cached prefix."""
        return self._block_hash.get(block)

    def block_chain_depth(self, block: int) -> int:
        """Chain depth (in blocks) ``block`` was registered at; 0 when
        unhashed."""
        return self._block_depth.get(block, 0)

    # --- block transfer (ISSUE 20) ------------------------------------------
    def export_blocks(self, hashes) -> Optional[List[dict]]:
        """Serialize the pool-side metadata of the chain addressed by
        ``hashes`` (leading chain digests, root-first — the shape
        :func:`prefix_chain_hashes` produces).  Returns one record per
        block — ``{"hash", "depth", "tokens", "block"}`` — or ``None``
        when any hash is unindexed (nothing to transfer; the recipient
        just recomputes).  Pure read: no pool mutation, no refcount
        change — the caller gathers the device payload at the returned
        ``block`` indices while the donor keeps serving."""
        records: List[dict] = []
        for h in hashes:
            b = self._hash_index.get(h)
            if b is None:
                return None
            tokens = self._block_tokens.get(b)
            if tokens is None:
                return None
            records.append({"hash": h, "depth": self._block_depth.get(b, 0),
                            "tokens": tokens, "block": b})
        return records

    def export_chain(self, chain_hash: bytes) -> Optional[List[dict]]:
        """Like :meth:`export_blocks` but addressed by the DEEPEST chain
        digest alone (the prefix-heat table's key): walks parent links
        back to the root and returns the full leading chain, root-first.
        ``None`` when the chain is broken (an ancestor was evicted)."""
        out: List[dict] = []
        h = chain_hash
        while h != _HASH_ROOT:
            b = self._hash_index.get(h)
            if b is None:
                return None
            tokens = self._block_tokens.get(b)
            parent = self._block_parent.get(b)
            if tokens is None or parent is None:
                return None
            out.append({"hash": h, "depth": self._block_depth.get(b, 0),
                        "tokens": tokens, "block": b})
            h = parent
        out.reverse()
        return out

    def chain_lead(self, chain_hash: bytes) -> Optional[List[bytes]]:
        """Leading chain digests, root-first, of the indexed chain
        ending at ``chain_hash`` — the affinity-key material a router
        needs to recompute ring placement for a cached prefix without
        the prompt tokens.  ``None`` when the chain is broken (an
        ancestor was evicted).  Pure read."""
        out: List[bytes] = []
        h = chain_hash
        while h != _HASH_ROOT:
            b = self._hash_index.get(h)
            if b is None:
                return None
            parent = self._block_parent.get(b)
            if parent is None:
                return None
            out.append(h)
            h = parent
        out.reverse()
        return out

    def import_blocks(self, records) -> Optional[Dict[bytes, int]]:
        """Admit a foreign block run (the :meth:`export_blocks` record
        shape, root-first) into THIS pool's prefix cache.  The chain is
        re-verified from ``_HASH_ROOT`` over the shipped tokens before
        anything mutates — a digest mismatch raises ``ValueError`` and
        the pool is untouched (content addressing must never trust the
        sender).  Atomic all-or-nothing: returns ``None`` (no mutation)
        when the fresh blocks outnumber ``num_available``; otherwise
        every fresh block is taken, registered, and parked in the reuse
        LRU (refcount 0, revivable by :meth:`fork_prefix` exactly like a
        locally-computed prefix), and the ``{hash: block}`` placement map
        is returned so the caller scatters the KV payload into those
        pages.  Already-indexed hashes are skipped (idempotent).

        Pool invariants hold throughout: blocks move free→reuse only, so
        ``free + reuse + allocated == num_blocks`` is preserved.  Known
        benign edge: under pressure, taking a block may evict a reuse-LRU
        ancestor of this very chain — the imported deeper blocks then sit
        unreachable until re-imported (wasted space, never corruption)."""
        if not self.prefix_cache_enabled:
            raise ValueError("import_blocks needs the prefix cache enabled")
        h = _HASH_ROOT
        parent_of: Dict[bytes, bytes] = {}
        for i, rec in enumerate(records):
            tokens = tuple(int(t) for t in rec["tokens"])
            if len(tokens) != self.block_size:
                raise ValueError(
                    f"imported block {i} carries {len(tokens)} tokens; "
                    f"this pool's block_size is {self.block_size}")
            parent = h
            h = _hash_block(h, tokens)
            if h != rec["hash"]:
                raise ValueError(
                    f"imported block {i} (depth {i + 1}) fails chain-hash "
                    "verification: content does not match its digest")
            parent_of[h] = parent
        fresh = [rec for rec in records
                 if rec["hash"] not in self._hash_index]
        if len(fresh) > self.num_available:
            return None
        placed: Dict[bytes, int] = {}
        taken = [self._take_block("kv_import") for _ in fresh]
        for b, rec in zip(taken, fresh):
            hh = rec["hash"]
            self._block_hash[b] = hh
            self._block_depth[b] = int(rec["depth"])
            self._block_tokens[b] = tuple(int(t) for t in rec["tokens"])
            self._block_parent[b] = parent_of[hh]
            self._hash_index[hh] = b
            self._reuse[b] = hh
            self._park_step[b] = self.clock
            placed[hh] = b
        if placed:
            self.cache_epoch += 1
        return placed


class BlockKVCache:
    """Host-side block-pool manager (BlockTable bookkeeping is python; the
    cache tensors live on device).

    Blocks are reference-counted so sequences can share a prefix without
    copying (``fork``): a shared block returns to the free list only when
    its last owner frees it — the copy-on-write-free reuse hook the
    serving layer's :class:`~paddle_tpu.serving.KVCacheManager` builds on.
    Bookkeeping is delegated to one shared :class:`BlockPool`; the public
    ``block_tables``/``seq_lens``/``_free`` attributes alias its state."""

    def __init__(self, num_blocks: int, block_size: int, num_heads: int,
                 head_dim: int, dtype=jnp.bfloat16):
        self.num_blocks = num_blocks
        self.block_size = block_size
        # head-dim sharded over the mp mesh axis when one is live (the
        # bookkeeping below stays host-side/replicated either way)
        self.k_cache = shard_kv_pool(
            jnp.zeros((num_blocks, block_size, num_heads, head_dim), dtype))
        self.v_cache = shard_kv_pool(
            jnp.zeros((num_blocks, block_size, num_heads, head_dim), dtype))
        self._pool = BlockPool(num_blocks, block_size)
        self._free = self._pool._free        # same objects, mutated in place
        self._ref = self._pool._ref
        self.block_tables = self._pool._tables
        self.seq_lens = self._pool._lens

    def blocks_needed(self, seq_id: int, num_tokens: int) -> int:
        return self._pool.blocks_needed(seq_id, num_tokens)

    def can_allocate(self, seq_id: int, num_tokens: int) -> bool:
        return self._pool.can_allocate(seq_id, num_tokens)

    def allocate(self, seq_id: int, num_tokens: int):
        """Reserve enough blocks for ``num_tokens`` more tokens.

        All-or-nothing: on exhaustion raises :class:`PoolExhausted`
        WITHOUT having taken any block, so the pool state stays clean for
        the caller's preemption/retry policy (``try_allocate`` is the
        non-raising form)."""
        if not self._pool.allocate(seq_id, num_tokens):
            raise PoolExhausted(
                f"KV cache pool exhausted: seq {seq_id} needs "
                f"{self._pool.blocks_needed(seq_id, num_tokens)} block(s), "
                f"{len(self._free)} free — free or preempt a sequence and "
                "retry")
        return self.block_tables[seq_id]

    def try_allocate(self, seq_id: int, num_tokens: int):
        """``allocate`` returning ``None`` instead of raising on exhaustion."""
        if not self._pool.allocate(seq_id, num_tokens):
            return None
        return self.block_tables[seq_id]

    def fork(self, src_seq: int, dst_seq: int) -> int:
        return self._pool.fork(src_seq, dst_seq)

    def free(self, seq_id: int):
        self._pool.free(seq_id)

    def write(self, seq_id: int, k: jax.Array, v: jax.Array):
        """Append [T, H, D] keys/values for one sequence."""
        T = k.shape[0]
        start = self.seq_lens.get(seq_id, 0)
        table = self.allocate(seq_id, T)
        pos = np.arange(start, start + T)
        blocks = np.asarray([table[p // self.block_size] for p in pos])
        offs = pos % self.block_size
        self.k_cache = self.k_cache.at[blocks, offs].set(k.astype(self.k_cache.dtype))
        self.v_cache = self.v_cache.at[blocks, offs].set(v.astype(self.v_cache.dtype))
        self.seq_lens[seq_id] = start + T

    def gather_view(self, seq_ids, max_blocks: Optional[int] = None):
        """Dense [B, max_blocks] block table + [B] lengths for the kernel."""
        if max_blocks is None:
            max_blocks = max(len(self.block_tables[s]) for s in seq_ids)
        bt = np.zeros((len(seq_ids), max_blocks), np.int32)
        lens = np.zeros((len(seq_ids),), np.int32)
        for i, s in enumerate(seq_ids):
            t = self.block_tables[s]
            bt[i, :len(t)] = t
            lens[i] = self.seq_lens[s]
        return jnp.asarray(bt), jnp.asarray(lens)


#: PartitionSpec entries for a ``[num_blocks, block_size, H, D]`` KV pool
#: under tensor-parallel serving: sharded along the HEAD dim over ``mp``.
#: The single source of truth — :func:`shard_kv_pool` places pools with it
#: and the engine's explicit jit in/out shardings reuse it, so placement
#: and program specs cannot drift (drift = silent full-pool resharding
#: transfers every step).
KV_POOL_SPEC = (None, None, "mp", None)


def shard_kv_pool(pool):
    """Place a ``[num_blocks, block_size, H, D]`` KV pool sharded along the
    head dim over the ``mp`` mesh axis (tensor-parallel serving, ISSUE 5).

    No-op (replicated placement semantics unchanged) when there is no
    global mesh, the mesh has no ``mp`` axis, ``mp == 1``, or the head
    count does not divide evenly — callers that require sharding must
    validate divisibility themselves (the engine does)."""
    from ..distributed import topology

    mesh = topology.get_mesh()
    if (mesh is None or "mp" not in mesh.axis_names
            or mesh.shape["mp"] == 1 or pool.shape[2] % mesh.shape["mp"]):
        return pool
    from jax.sharding import NamedSharding, PartitionSpec

    return jax.device_put(
        pool, NamedSharding(mesh, PartitionSpec(*KV_POOL_SPEC)))


# Which path the most recent dispatch took: "pallas" | "xla".
last_path: Optional[str] = None


def pallas_dispatch(kernel_fn, oracle_fn, use_pallas, tileable):
    """ONE home for the kernel-vs-oracle dispatch policy shared by the
    decode kernel (:func:`paged_attention`) and the unified ragged
    kernel (``ops.ragged_paged.ragged_paged_attention``): the operator
    kill switch (``PADDLE_TPU_DISABLE_PALLAS`` / the
    ``disable_pallas_kernels`` flag) always wins, ``use_pallas=True``
    forces the kernel past the tileability heuristic (interpret mode
    off-TPU), ``False`` pins the oracle.  The choice is made from what
    the code can see BEFORE the launch; a kernel that then fails raises —
    it is never retried on the gather path.  Returns ``(out, path)``
    with ``path`` in ``{"pallas", "xla"}`` — callers publish it as their
    module's ``last_path``."""
    import os

    from ..core import flags

    disable = (os.environ.get("PADDLE_TPU_DISABLE_PALLAS") == "1"
               or flags.flag("disable_pallas_kernels"))
    if use_pallas is False:
        tileable = False          # pin the XLA gather path
    if not disable and (tileable or use_pallas is True):
        return kernel_fn(), "pallas"
    return oracle_fn(), "xla"


class PagedCache:
    """Per-layer view of the shared block pool, handed to the model's
    attention as its ``cache`` (the model writes K/V into the slot and
    attends through the block table).  ``k_pool``/``v_pool`` are framework
    Tensors [num_blocks, block_size, Hkv, D] so the scatter write threads
    as jit state; the routing arrays are refreshed by the serving loop
    before each decode step."""

    def __init__(self, k_pool, v_pool):
        self.k_pool = k_pool
        self.v_pool = v_pool
        self.block_tables = None   # [B, max_blocks] int32
        self.seq_lens = None       # [B] int32 (AFTER this step's token)
        self.slot_blocks = None    # [B] int32 — page of this step's token
                                   # ([B, S] in chunked-prefill mode: one
                                   # slot per chunk token)
        self.slot_offsets = None   # [B] int32 — offset within the page
        self.q_start = None        # chunked prefill only: global position
                                   # of the chunk's first token (scalar or
                                   # [B] int32) — offsets the causal mask.
                                   # In ragged mode ([T] int32): the
                                   # absolute position of EVERY packed
                                   # token
        self.seg_ids = None        # unified ragged step (ISSUE 11): [T]
                                   # int32 row index of each packed token
                                   # — non-None routes the model's
                                   # attention through ops/ragged_paged.py
                                   # (one fused prefill+decode launch)
        self.use_pallas = None     # decode kernel routing hint (ISSUE 5
                                   # satellite): True forces the Pallas
                                   # kernel (interpret mode off-TPU),
                                   # False forces the XLA gather path,
                                   # None keeps the auto dispatch

    @classmethod
    def over(cls, k, v):
        """The cache over a layer's entry of each side of the pools, raw
        as a step program holds them (every cache class has this)."""
        return cls(Tensor(k), Tensor(v))

    def route(self, block_tables, seq_lens, slot_blocks, slot_offsets,
              start=None, n_valid=None, seg_ids=None):
        """A launch's routing arrays: the ONE call every cache class takes,
        each reading what it needs (``start`` is kept as ``q_start``)."""
        self.block_tables = jnp.asarray(block_tables, jnp.int32)
        self.seq_lens = jnp.asarray(seq_lens, jnp.int32)
        self.slot_blocks = jnp.asarray(slot_blocks, jnp.int32)
        self.slot_offsets = jnp.asarray(slot_offsets, jnp.int32)
        if start is not None:
            self.q_start = jnp.asarray(start, jnp.int32)
        if seg_ids is not None:
            self.seg_ids = jnp.asarray(seg_ids, jnp.int32)


def _xla_paged_attention(q, k_cache, v_cache, block_tables, seq_lens):
    """XLA gather path: materializes the padded [B, S, H, D] context (GQA
    via grouped einsum, KV never head-repeated).

    This is also the **standing differential-testing oracle** for the
    Pallas decode kernel (``pallas_paged.decode_oracle`` re-exports it):
    the interpret-mode parity tests and the online numerics auditor
    (``observability/audit.py``) both compare the kernel against this
    path, so any kernel drift is caught offline AND in production."""
    B, H, D = q.shape
    max_blocks = block_tables.shape[1]
    bs = k_cache.shape[1]
    Hkv = k_cache.shape[2]
    rep = H // Hkv
    scale = 1.0 / math.sqrt(D)

    # gather each sequence's pages: [B, max_blocks, bs, Hkv, D] → [B, S, Hkv, D]
    k = k_cache[block_tables].reshape(B, max_blocks * bs, Hkv, D)
    v = v_cache[block_tables].reshape(B, max_blocks * bs, Hkv, D)

    qg = q.reshape(B, Hkv, rep, D)
    logits = jnp.einsum("bhrd,bshd->bhrs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    pos = jnp.arange(max_blocks * bs)[None, None, None, :]
    mask = pos < seq_lens[:, None, None, None]
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhrs,bshd->bhrd", probs, v.astype(jnp.float32))
    return out.reshape(B, H, D).astype(q.dtype)


def paged_prefill_attention(q: jax.Array, k_cache: jax.Array,
                            v_cache: jax.Array, block_tables: jax.Array,
                            seq_lens: jax.Array,
                            q_start: jax.Array) -> jax.Array:
    """Chunked-prefill attention over a paged KV cache.

    q: [B, S, H, D] — ``S`` new tokens per sequence sitting at global
    positions ``q_start + [0, S)``; the chunk's own K/V has already been
    scattered into the pool, so the causal mask ``col <= q_start + row``
    covers both the previously computed prefix AND intra-chunk causality
    with one predicate.  ``seq_lens`` is the total KV length after the
    chunk (clamps pad rows away from garbage pages).  Returns
    [B, S, H, D].

    XLA gather path on purpose: a prefill chunk is compute-bound on the
    [S, K] score matmul (unlike the latency-bound single-token decode the
    Pallas kernel exists for), and the same grouped-einsum/float32-softmax
    shape as the dense prefill keeps greedy outputs token-identical
    between the chunked and one-shot programs.
    """
    B, S, H, D = q.shape
    max_blocks = block_tables.shape[1]
    bs = k_cache.shape[1]
    Hkv = k_cache.shape[2]
    rep = H // Hkv
    scale = 1.0 / math.sqrt(D)

    k = k_cache[block_tables].reshape(B, max_blocks * bs, Hkv, D)
    v = v_cache[block_tables].reshape(B, max_blocks * bs, Hkv, D)

    qg = q.reshape(B, S, Hkv, rep, D)
    logits = jnp.einsum("bqhrd,bkhd->bhrqk", qg, k,
                        preferred_element_type=jnp.float32) * scale
    col = jnp.arange(max_blocks * bs)[None, None, :]
    starts = (q_start[:, None, None] if jnp.ndim(q_start) == 1
              else q_start)                       # scalar or per-sequence
    row = starts + jnp.arange(S)[None, :, None]
    mask = (col <= row) & (col < seq_lens[:, None, None])  # [B, S, K]
    logits = jnp.where(mask[:, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhrqk,bkhd->bqhrd", probs.astype(v.dtype), v)
    return out.reshape(B, S, H, D).astype(q.dtype)


@dataclass(frozen=True)
class CacheSpec:
    """What one decoder layer keeps between steps, as the layer declares it
    (``layer.cache_spec()``) and ``EngineCore`` allocates it.  Two kinds of
    memory, and a layer may declare either:

    **Per TOKEN** (pages): ``k`` is the ``(heads, dim)`` of the token's row
    in the layer's ``k_pools`` entry (``[num_blocks, block_size, heads,
    dim]``), ``v`` the same for ``v_pools`` or ``None`` where the layer
    keeps nothing there (a latent cache: one row shared by all heads, no
    separate values).  ``kind`` names that layout for the paths that can
    take only one: ``"kv"`` shards its head dimension over ``mp`` and is
    what the ragged, burst and hand-off paths move; ``"latent"`` is refused
    by them, by name, when the engine is built, and its pool is HELD as
    ``[num_blocks, block_size, lanes]``, the row in whole lane tiles
    (:func:`latent_pool_shape`: the array a TPU lays row-major, a page
    contiguous; :func:`pool_rows` writes a row into either form).  A layer with no per-token
    row (a state-space mixer) leaves ``k`` and ``v`` ``None``.

    **Per SEQUENCE** (slots): ``state`` is ``None`` or two ``(shape,
    dtype)`` pairs, arrays of FIXED shape one live sequence holds in this
    layer whatever its length (a selective scan's recurrent state, or a
    gated delta rule's matrix a value head, and the last inputs of its
    causal convolution).  The engine allocates
    ``[max_num_seqs + 1, *shape]`` of each (slot 0 is the null slot that
    padding rows use) in the layer's ``k_pools`` / ``v_pools`` entry; a
    dtype of ``None`` is the pool's.  The slot of a sequence is the id of
    its first block (``KVCacheManager``), so the step programs take no
    argument for it.  Such state cannot be rebuilt from a block prefix of
    pages nor rolled back a token, so ``EngineCore`` refuses, by name,
    every path that would need to (prefix cache, speculative verify,
    bursts, the ragged program, hand-off, ``mp > 1``).

    The two are two LIFETIMES of one model's layers: a layer that needs
    every token of a sequence declares pages, which grow with it; a
    sliding-window layer needs the last ``window`` tokens and nothing
    older, and declares as its state a RING of them (``window`` set, two
    ``[window, heads, dim]`` arrays: ``ops/window_attention.py``), so what
    it holds a sequence never grows past the window.  ``window`` says what
    the state IS to whoever counts its use (``window_attention
    .WindowTokens``); the allocation goes by ``state`` alone.

    **Both at once, and a third rate** (``tokens_per_row`` set): a layer of
    chunk-summarised attention (``ops/eva_attention.py``) keeps a ring of
    its OPEN window a sequence AND rows that grow with the sequence at one
    row every ``tokens_per_row`` tokens (a closed window's chunk
    summaries).  It declares ``state`` (the two rings, ``window`` set) and
    ``k`` / ``v`` (a row's ``(heads, dim)``) together.  The rows live in
    the sequence's OWN blocks -- ``block_size / tokens_per_row`` rows a
    block, pools ``[num_blocks, rows a block, heads, dim]`` -- so the
    block table ``KVCacheManager`` keeps already is the rows' table and
    there is no second manager; the engine refuses a block size the rate
    does not divide.  Such a layer's entry of ``k_pools`` / ``v_pools`` is
    the pair ``(ring slots, rows)``.  Without ``tokens_per_row`` a layer
    declares pages OR slots, not both, as before."""

    k: Optional[Tuple[int, int]] = None
    v: Optional[Tuple[int, int]] = None
    kind: str = "kv"
    state: Optional[Tuple[Tuple[Tuple[int, ...], Optional[str]], ...]] = None
    window: Optional[int] = None
    tokens_per_row: Optional[int] = None
    # the class of the ``cache`` object a step program hands the layer
    # (``cls.over(k, v)``, then one ``route`` call with the launch's arrays)
    cache: type = field(default=PagedCache, compare=False)

    def __post_init__(self):
        if self.tokens_per_row is not None:
            if not (self.state and self.window and self.k and self.v) \
                    or self.tokens_per_row < 1:
                raise ValueError(
                    "rows at one every tokens_per_row tokens stand beside "
                    "a window's ring: declare state, window, k and v")
        elif self.state is not None and (self.k or self.v):
            raise ValueError("a layer declares per-token rows or "
                             "per-sequence state, not both")
        if self.window is not None and self.state is None:
            raise ValueError("a window's ring is per-sequence state: "
                             "declare it under state")
        if self.state is not None and len(self.state) != 2:
            raise ValueError("per-sequence state is two (shape, dtype) "
                             "pairs, one a side of the pools")
        if self.state is None and self.k is None:
            raise ValueError("a layer that keeps nothing declares no cache")

    @property
    def ring_and_rows(self) -> bool:
        """A ring a sequence AND rows that grow with it, in one layer."""
        return self.tokens_per_row is not None

    def rows_per_block(self, block_size: int) -> int:
        """Rows a block of ``block_size`` tokens holds in this layer."""
        per = self.tokens_per_row or 1
        if block_size % per:
            raise ValueError(
                f"block_size {block_size} is no multiple of the "
                f"{per} tokens a row of this layer stands for")
        return block_size // per

    def values_per_token(self) -> int:
        return sum(r[0] * r[1] for r in (self.k, self.v) if r) \
            // (self.tokens_per_row or 1)

    def state_bytes_per_sequence(self, pool_dtype) -> int:
        """Bytes one live sequence holds in this layer's slots."""
        return sum(math.prod(shape) * jnp.dtype(dtype or pool_dtype).itemsize
                   for shape, dtype in self.state or ())


@dataclass(slots=True)
class LaunchView:
    """What a kind's telemetry may read of the engine: given when it is
    built and with every launch, whose ``program`` it names; ``span`` is
    the first position and the tokens of the prompt chunk last BUILT (set
    inside its ``engine.build``: read it from ``fetch_ints``); ``kv`` is
    the KV manager (block size and count are its)."""

    registry: object
    labels: Dict[str, str]
    kv: object
    pool_dtype: object
    program: str = ""
    span: Optional[Tuple[int, int]] = None


class LaunchTelemetry:
    """What a decoder-layer kind brings to a launch beside its cache:
    four methods ``EngineCore`` calls, knowing no kind, on one object a
    class the model's layers name (``layer.telemetry``; ``LlamaForCausalLM
    .launch_telemetry`` makes it over the ``layers`` that do).  A kind
    registers its own series on ``view.registry`` and lists its integers
    in its docstring: their names are ``benchmarks/*_spans.py``'s."""

    def __init__(self, layers, view: LaunchView):
        self.layers, self.view = layers, view

    def traced(self):
        """Inside a step program, after the forward: the array that rides
        the launch beside the logit sentinel (or ``None``), popped off the
        layers: a traced value does not outlive its trace."""
        return None

    def build_ints(self, view: LaunchView, rows: int, reqs) -> Dict[str, int]:
        """``engine.build``'s integers for ``rows`` real rows, ``reqs``."""
        return {}

    def fetch_ints(self, program: str, host_array) -> Dict[str, int]:
        """``engine.fetch``'s integers, from what :meth:`traced` sent (or
        ``None``); runs under ``engine.device_wait``."""
        return {}

    def forget(self, request_id) -> None:
        """A row retired or preempted."""


def _block_queries(fn, q, block: int = 1024):
    """``fn(q_block, first_row)`` over blocks of the query axis (axis 1)
    where it is long, so that a prefill's ``[heads, S, M]`` float32 scores
    are never whole in memory (4,096 x 4,096 x 20 heads is 1.3 GB)."""
    S = q.shape[1]
    if S <= block or S % block:
        return fn(q, 0)
    n = S // block
    qs = jnp.moveaxis(q.reshape(q.shape[0], n, block, *q.shape[2:]), 1, 0)
    out = jax.lax.map(lambda a: fn(a[0], a[1] * block),
                      (qs, jnp.arange(n, dtype=jnp.int32)))
    out = jnp.moveaxis(out, 0, 1)
    return out.reshape(out.shape[0], S, *out.shape[3:])


# Which form the most recent :func:`latent_expanded_attention` was traced
# with: "pallas" (``pallas_flash.flash_prefill``) | "xla", and the query
# rows a grid step of that kernel takes (0: the XLA form).
last_latent_prefill_path: Optional[str] = None
last_latent_prefill_block_q: int = 0


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _mesh_mp() -> int:
    """Shards of the ``mp`` axis of the global mesh (1: none): a kernel of
    one shard does not go into a program laid over more."""
    from ..distributed import topology

    mesh = topology.get_mesh()
    return 1 if mesh is None or "mp" not in mesh.axis_names \
        else int(mesh.shape["mp"])


def latent_expanded_attention(q, lat, w_ukv, rank: int, scale: float,
                              q_start=0, lens=None,
                              use_pallas: Optional[bool] = None):
    """Latent attention with keys and values REBUILT from the cache rows.

    q: ``[B, S, heads, nope + rope]`` (rope part rotated), at positions
    ``q_start + [0, S)``; lat: ``[B, M, rank + rope]`` — per token the
    normalised latent ``c_kv`` beside the rotated shared key ``k_r``;
    ``w_ukv = (W_UK [heads, rank, nope], W_UV [heads, rank, v])``.  A query
    sees the columns up to its own position, and under ``lens`` (``[B]``)
    where given.  Returns ``[B, S, heads * v]``.

    Keys and values are rebuilt by two einsums either way.  The core —
    scores, mask, softmax, weighted sum — is ``pallas_flash.flash_prefill``
    on a TPU where ``S`` and ``M`` are whole blocks of it
    (``pallas_flash.prefill_blocks``: the engine's prefill buckets are),
    and :func:`_latent_expanded_core` elsewhere, which is also the kernel's
    oracle; :func:`pallas_dispatch` decides (``use_pallas`` forces or
    pins, the operator's kill switch wins), and the form traced is
    published as :data:`last_latent_prefill_path` (the kernel's query
    block as :data:`last_latent_prefill_block_q`)."""
    global last_latent_prefill_path, last_latent_prefill_block_q

    from .pallas_flash import flash_prefill, prefill_blocks

    w_uk, w_uv = w_ukv
    B, S, heads, _ = q.shape
    M = lat.shape[1]
    c_kv = lat[..., :rank].astype(q.dtype)
    k_r = lat[..., rank:].astype(q.dtype)
    blocks = prefill_blocks(S, M)

    def kernel():
        # heads before tokens: the layout the kernel's blocks want
        k_nope = jnp.einsum("bmr,hrn->bhmn", c_kv, w_uk)
        v = jnp.einsum("bmr,hrv->bhmv", c_kv, w_uv)
        starts = jnp.broadcast_to(jnp.asarray(q_start, jnp.int32), (B,))
        n = jnp.full((B,), M, jnp.int32) if lens is None else lens
        # forced past the rule, the whole launch is one block
        return flash_prefill(q, k_nope, k_r, v, scale, starts, n,
                             blocks or (S, M))

    def oracle():
        return _latent_expanded_core(q, c_kv, k_r, w_ukv, scale, q_start,
                                     lens)

    out, last_latent_prefill_path = pallas_dispatch(
        kernel, oracle, use_pallas, _on_tpu() and blocks is not None)
    last_latent_prefill_block_q = (blocks or (S, M))[0] \
        if last_latent_prefill_path == "pallas" else 0
    return out.reshape(B, S, heads * w_uv.shape[-1]).astype(q.dtype)


def _latent_expanded_core(q, c_kv, k_r, w_ukv, scale, q_start, lens):
    """The XLA form of :func:`latent_expanded_attention`'s core, and the
    oracle of ``pallas_flash.flash_prefill``: float32 scores written whole,
    a block of query rows at a time where there are many.  Returns
    ``[B, S, heads, v]``."""
    w_uk, w_uv = w_ukv
    M, nope = c_kv.shape[1], w_uk.shape[-1]
    k_nope = jnp.einsum("bmr,hrn->bmhn", c_kv, w_uk)
    v = jnp.einsum("bmr,hrv->bmhv", c_kv, w_uv)
    col = jnp.arange(M)[None, None, :]
    starts = (q_start[:, None, None] if jnp.ndim(q_start) == 1 else q_start)

    def block(qb, first):
        s = (jnp.einsum("bqhn,bkhn->bhqk", qb[..., :nope], k_nope,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bqhr,bkr->bhqk", qb[..., nope:], k_r,
                          preferred_element_type=jnp.float32)) * scale
        row = starts + first + jnp.arange(qb.shape[1])[None, :, None]
        mask = col <= row
        if lens is not None:
            mask = mask & (col < lens[:, None, None])
        probs = jax.nn.softmax(jnp.where(mask[:, None], s, -1e30), axis=-1)
        return jnp.einsum("bhqk,bkhv->bqhv", probs.astype(v.dtype), v)

    return _block_queries(block, q)


def latent_paged_prefill_attention(q, pool, w_ukv, block_tables, seq_lens,
                                   q_start, rank: int, scale: float):
    """A prefill chunk over a paged LATENT cache: gather the rows of each
    sequence's pages (``pool [num_blocks, block_size, 1, rank + rope]`` or
    the resident form of :func:`latent_pool_shape`, the chunk's own rows
    already written) and attend expanded
    (:func:`latent_expanded_attention`).  XLA gather path, like
    :func:`paged_prefill_attention`."""
    latent = rank + q.shape[-1] - w_ukv[0].shape[-1]
    return latent_expanded_attention(
        q, _latent_context(pool, block_tables, latent), w_ukv, rank, scale,
        q_start, seq_lens)


# the gathered context of one decode launch of the XLA form (the kernel's
# oracle, the CPU's and the kill switch's path; a TPU walks the pages and
# gathers nothing) is held to this size by
# splitting the ROWS of the batch into groups run one after another: above
# it (128 rows of 4,096 tokens are 604 MB) the TPU compiler gave every
# layer's context a buffer of its own, 6.2 GB of temporaries in a 7-layer
# step program against 0.8 GB at half the width (compiled for a v5e, PR 29)
_LATENT_CONTEXT_BYTES = 320 * 2 ** 20

#: lanes a latent pool's rows are a whole number of, as the engine holds it
LATENT_LANES = 128


def latent_pool_shape(num_blocks: int, block_size: int, row) -> tuple:
    """The RESIDENT shape of a ``kind="latent"`` layer's pool, whose
    ``CacheSpec.k`` is ``row = (1, latent)``: ``[num_blocks, block_size,
    lanes]``, ``lanes`` the row's values rounded up to whole tiles of 128
    (576 -> 640, the padding zeros that nothing reads).  The TPU compiler
    lays an array out by its shape to pad least: ``[19200, 16, 1, 576]`` and
    ``[19200, 16, 576]`` both get the BLOCK dimension minor-most, so every
    program that writes a token's row or reads a page first copied the
    whole pool into row-major tiles and copied it back after (two copies
    of 354 MB a layer a step; compiled for a v5e, PR 46).  A last dimension
    of whole tiles is laid out row-major: the scatter writes in place, and
    a page ``[bs, lanes]`` is contiguous tiles the decode kernel's copies
    take as they lie."""
    heads, dim = row
    return (num_blocks, block_size,
            -(-heads * dim // LATENT_LANES) * LATENT_LANES)


def pool_rows(rows, pool):
    """Token rows ``[..., heads, dim]`` as ``pool`` holds them, in its type:
    as they are for ``[blocks, bs, heads, dim]``; for a latent layer's
    resident ``[blocks, bs, lanes]`` (:func:`latent_pool_shape`) flat and
    zero-padded to its lanes."""
    rows = rows.astype(pool.dtype)
    if pool.ndim == 4:
        return rows
    rows = rows.reshape(rows.shape[:-2] + (-1,))
    pad = pool.shape[-1] - rows.shape[-1]
    return jnp.pad(rows, ((0, 0),) * (rows.ndim - 1) + ((0, pad),))


def _latent_context(pool, tables, latent: int):
    """The rows of each sequence's pages, gathered: ``[B, W * bs, latent]``
    out of a pool ``[blocks, bs, 1, latent]`` or the resident
    ``[blocks, bs, lanes]``."""
    ctx = pool[tables].reshape(tables.shape[0], -1, pool.shape[-1])
    return ctx if ctx.shape[-1] == latent else ctx[..., :latent]


def latent_paged_decode_attention(q, pool, w_ukv, block_tables, seq_lens,
                                  rank: int, scale: float,
                                  use_pallas: Optional[bool] = None):
    """One decode token a row over a paged latent cache, ABSORBED: ``W_UK``
    is folded into the query (``q~_h = W_UK,h^T q_nope_h``), scores and the
    weighted sum run on the 576-wide rows themselves, and ``W_UV`` comes
    after — the mathematics of :func:`latent_expanded_attention` with no
    key or value ever built.  q: ``[B, heads, nope + rope]``; pool:
    ``[blocks, bs, 1, rank + rope]`` or the engine's resident ``[blocks,
    bs, lanes]`` (:func:`latent_pool_shape`); returns ``[B, heads * v]``.

    The core between the two foldings is ``pallas_paged
    .latent_decode_attention`` on a TPU — ONE kernel that walks the pages a
    row holds where they lie and stops at the row's length, at every batch
    size — where the shapes allow (a resident pool of 16- or 32-bit values
    in whole sublane tiles a page, ``rank`` whole lane tiles, a rope part
    of one tile at most), and the XLA gather form elsewhere (the CPU, the
    operator's kill switch, a mesh with ``mp > 1``), which materialises the
    padded ``[rows, W * block_size, 576]`` context a group of rows at a time
    and is the kernel's oracle.  :func:`pallas_dispatch` decides
    (``use_pallas`` forces or pins) and the form traced is published as
    the module's :data:`last_path`, as :func:`paged_attention` does."""
    global last_path

    w_uk, w_uv = w_ukv
    B, W = block_tables.shape
    nope = w_uk.shape[-1]
    latent = rank + q.shape[-1] - nope
    q_lat = jnp.einsum("bhn,hrn->bhr", q[..., :nope], w_uk,
                       preferred_element_type=jnp.float32).astype(q.dtype)
    qc = jnp.concatenate([q_lat, q[..., nope:]], axis=-1)

    def rows(args):
        qg, tables, lens = args
        ctx = _latent_context(pool, tables, latent).astype(q.dtype)
        s = jnp.einsum("bhd,bmd->bhm", qg, ctx,
                       preferred_element_type=jnp.float32) * scale
        mask = jnp.arange(ctx.shape[1])[None, None, :] < lens[:, None, None]
        probs = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
        # the weighted sum runs over the whole 576-wide row and the rope
        # part is dropped after: slicing the context first would copy it
        return jnp.einsum("bhm,bmd->bhd", probs.astype(ctx.dtype), ctx,
                          preferred_element_type=jnp.float32)[..., :rank]

    def oracle():
        per_row = W * pool.shape[1] * latent * jnp.dtype(q.dtype).itemsize
        groups = 1
        while B * per_row > groups * _LATENT_CONTEXT_BYTES \
                and B % (2 * groups) == 0:
            groups *= 2
        # unrolled, not a loop: two groups at most in practice, and a
        # ``while`` in the program is an event the accepted scope readers
        # count twice
        n = B // groups
        return jnp.concatenate([rows((qc[i:i + n], block_tables[i:i + n],
                                      seq_lens[i:i + n]))
                                for i in range(0, B, n)], axis=0)

    def kernel():
        from .pallas_paged import latent_decode_attention

        flat = pool if pool.ndim == 3 else pool.reshape(pool.shape[:2] + (-1,))
        return latent_decode_attention(qc, flat, block_tables, seq_lens,
                                       rank, scale)

    itemsize = pool.dtype.itemsize
    tileable = (_on_tpu() and _mesh_mp() == 1 and pool.ndim == 3
                and pool.shape[-1] % LATENT_LANES == 0
                and rank % LATENT_LANES == 0
                and latent - rank <= LATENT_LANES
                and itemsize in (2, 4)
                and pool.shape[1] % (32 // itemsize) == 0)
    u, last_path = pallas_dispatch(kernel, oracle, use_pallas, tileable)
    o = jnp.einsum("bhr,hrv->bhv", u.astype(q.dtype), w_uv)
    return o.reshape(B, -1)


def paged_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                    block_tables: jax.Array, seq_lens: jax.Array,
                    use_pallas: Optional[bool] = None) -> jax.Array:
    """Decode-step attention over a paged KV cache.

    q: [B, H, D] (one new token per sequence); k/v_cache:
    [num_blocks, block_size, Hkv, D]; block_tables: [B, max_blocks] int32;
    seq_lens: [B] int32.  Returns [B, H, D].

    Dispatches to the Pallas kernels (``pallas_paged.py`` — the pages a
    row holds copied by its scalar-prefetched table, small pages in groups,
    no dense context copy) when shapes are TPU-tileable, to
    the XLA gather path otherwise; a kernel failure raises.

    ``use_pallas`` overrides the auto dispatch (``EngineConfig.
    use_pallas_paged``, ISSUE 5): ``True`` routes through the Pallas
    kernel even when the tileability heuristic says no (off-TPU the
    kernel runs in interpret mode — the CPU smoke-test path); ``False``
    pins the XLA gather path (the mp>1 choice for the LEGACY programs:
    GSPMD partitions the gather einsums, while this kernel is
    single-shard — the unified ragged kernel spans the mesh instead).
    The operator kill switch (``PADDLE_TPU_DISABLE_PALLAS`` / the
    ``disable_pallas_kernels`` flag) still wins over ``use_pallas=True``
    (:func:`pallas_dispatch` is the one policy implementation).
    """
    global last_path

    B, H, D = q.shape
    # ONE KV head (multi-query) still goes down the gather path: the
    # kernel's step at 16-token pages is a group of 128 tokens of a row,
    # and with one head that is 64 KB of K and V -- the step's fixed cost,
    # not the copy, sets its time (20 query heads on 1 KV head at 256 rows
    # x 4,096-token tables, mean length 2,143: the group walk 3.60 ms, the
    # gather 1.07 ms, the kernel of a step a (row, page) before it 24.2 ms;
    # my chip run, PR 38)
    tileable = (D % 128 == 0 and k_cache.shape[1] % 8 == 0
                and k_cache.shape[2] > 1)

    def kernel():
        from .pallas_paged import paged_attention_decode

        return paged_attention_decode(q, k_cache, v_cache, block_tables,
                                      seq_lens)

    out, last_path = pallas_dispatch(
        kernel,
        lambda: _xla_paged_attention(q, k_cache, v_cache, block_tables,
                                     seq_lens),
        use_pallas, tileable)
    return out
