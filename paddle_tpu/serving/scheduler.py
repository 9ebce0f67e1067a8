"""Continuous-batching scheduler.

Request-level scheduling over the ragged paged KV pool (the Ragged Paged
Attention shape, PAPERS.md): every engine step the scheduler

1. **admits** waiting requests into the running set while (a) the running
   set is under ``max_num_seqs`` and (b) the pool can cover the request's
   *uncached* prompt tail *plus one decode block of headroom* without
   preempting anyone — admission never steals blocks from running work.
   Admission first **forks the longest cached block-prefix** of the
   prompt from the prefix cache (``KVCacheManager.fork_prefix``:
   refcount++, zero recompute), so a cache hit both skips prefill work
   AND shrinks the admission charge;
2. **plans prefill chunks** under the per-step token budget
   (``max_prefill_tokens_per_step``): a long prompt advances in chunks
   across engine steps — continuing partial prefills outrank new
   admissions — so prefill work shares steps with the running decode
   batch instead of stalling it.  ``None`` (the default) keeps the
   one-shot behaviour;
3. **reserves** this step's decode slot for every fully-prefilled running
   request, and on exhaustion **preempts** — the least-important running
   request (highest ``(priority, arrival_seq)``) is evicted, its blocks
   freed (shared prefix blocks stay with their other owners), and it is
   re-enqueued at the FRONT of the waiting queue for prefill-recompute.
   Exhaustion is a scheduling event, not an error.

The serving loop plans one step ahead of the device: while a decode launch
is in flight, :meth:`ContinuousBatchingScheduler.plan_ahead` plans the next
step on the assumption that every row of it yields one token, and only if
that step is a pure continuation (steps 1 and 2 above would do nothing, step
3 would preempt nobody); otherwise it names the rule that stood in the way
and changes nothing, and :meth:`~ContinuousBatchingScheduler.schedule` plans
the step once the launch has been read.

Invariants (tested by ``tests/test_serving_engine.py``):

* slot reservation is all-or-nothing per request — a preemption pass never
  leaves a half-allocated sequence behind;
* a preempted request keeps its generated tokens, so recompute costs one
  prefill over ``prompt + output_tokens`` and produces token-identical
  continuations (greedy);
* a request whose total footprint can never fit the pool (prompt blocks >
  usable pool) is finished as ABORT instead of live-locking the queue;
* batch composition changes NEVER change tensor shapes the compiler sees —
  the engine pads each batch to a size bucket (``bucket_size``), so the
  jitted decode step compiles once per bucket (MPK's fixed-shape
  mega-program argument, PAPERS.md);
* the scheduler is **mesh-oblivious** (ISSUE 5): under tensor-parallel
  serving the KV pools shard over the ``mp`` axis but the block pool
  bookkeeping this scheduler plans against is host-side and replicated —
  one plan drives every shard, admission math is unchanged (the pool is
  logically ONE pool; only the per-shard byte footprint divides by mp),
  and the bucket sets (hence the jit trace bound) are mp-invariant.
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import Deque, List, Optional, Tuple

from ..observability.tracer import (
    SETTLE_ADMIT,
    SETTLE_FINISH,
    SETTLE_PREEMPT,
    SETTLE_PREFILL,
)
from .kv_manager import KVCacheManager
from .request import FinishReason, Request, RequestState


def bucket_size(n: int, cap: Optional[int] = None) -> int:
    """Next power of two ≥ n (≥1); optionally clamped to ``cap``.  The
    shape-bucketing that bounds jit trace count: any batch/width in the
    same bucket replays the same compiled program."""
    b = 1
    while b < n:
        b <<= 1
    return min(b, cap) if cap is not None else b


@dataclass
class SchedulerConfig:
    """Per-step planning knobs.  Rides ``EngineConfig.scheduler`` in the
    one-object engine construction form, or the legacy
    ``EngineCore(scheduler_config=...)`` keyword."""

    max_num_seqs: int = 8            # running-set cap (decode batch ≤ this)
    max_prefills_per_step: int = 1   # admission throttle: prefill is the
                                     # expensive fixed-shape program; decode
                                     # latency of running requests is
                                     # protected by not batching many
                                     # prefills into one engine step
    max_prefill_tokens_per_step: Optional[int] = None
                                     # chunked prefill: per-step token
                                     # budget shared by ALL prefill work
                                     # (continuations + admissions) so a
                                     # long prompt advances in bucketed
                                     # chunks alongside the decode batch
                                     # instead of stalling it.  None =
                                     # unlimited (one-shot prefill).
    max_tokens_per_step: Optional[int] = None
                                     # unified ragged packing (ISSUE 11):
                                     # ONE token budget for the whole
                                     # step — decode rows (1 token each)
                                     # claim it first (they are NEVER
                                     # split across steps), prefill work
                                     # (continuations + admissions)
                                     # competes for the remainder.  The
                                     # packed token bucket is therefore
                                     # bounded by bucket_size(max(this,
                                     # max_num_seqs)) — a decode batch
                                     # larger than the budget still runs
                                     # whole.  None = no combined cap
                                     # (prefill still honours its own
                                     # budget).

    def __post_init__(self):
        if (self.max_prefill_tokens_per_step is not None
                and self.max_prefill_tokens_per_step < 1):
            # a zero/negative budget plans NO prefill ever: requests would
            # queue forever while has_work() stays True — fail fast instead
            raise ValueError(
                "max_prefill_tokens_per_step must be None or >= 1, got "
                f"{self.max_prefill_tokens_per_step}")
        if (self.max_tokens_per_step is not None
                and self.max_tokens_per_step < 1):
            raise ValueError(
                "max_tokens_per_step must be None or >= 1, got "
                f"{self.max_tokens_per_step}")


@dataclass
class SchedulerOutput:
    """One step's plan: prefill chunks to run, the decode set, and who
    was preempted to make room."""

    prefills: List[Request] = field(default_factory=list)
    admitted: List[Request] = field(default_factory=list)  # ⊆ prefills:
                                     # newly admitted this step (the
                                     # engine counts their cache hits)
    decodes: List[Request] = field(default_factory=list)
    preempted: List[Request] = field(default_factory=list)
    aborted: List[Request] = field(default_factory=list)
    # speculative-decode headroom (ISSUE 18): tokens left of
    # ``max_tokens_per_step`` after this plan's decode rows + prefill
    # chunks — the engine may pack at most this many DRAFT tokens into
    # the unified launch, so the packed token count never outgrows the
    # same ``max(total, decode rows)`` bucket bound the plain plan has.
    # 0 when no combined budget is configured (spec requires one).
    draft_budget: int = 0
    # decode-burst headroom (ISSUE 19): the largest per-row burst length
    # the pool can back for THIS plan's decode rows, from the ONE
    # `KVCacheManager.burst_capacity` accessor — the engine's launch
    # clamp reads this field instead of re-deriving headroom, so the
    # planning math and the clamp can never disagree.
    burst_capacity: int = 0


class ContinuousBatchingScheduler:
    """Owns the waiting queue and the running set; pure bookkeeping — the
    engine executes the plan this object returns."""

    def __init__(self, config: SchedulerConfig, kv: KVCacheManager):
        self.config = config
        self.kv = kv
        self.waiting: Deque[Request] = deque()  # unbounded-ok: live work queue (admission drains it); not telemetry
        self.running: List[Request] = []
        # exact planned-work ledger (ISSUE 9): every prefill token and
        # decode row this scheduler ever put in a plan.  The engine
        # executes plans verbatim, so the StepProfiler's scheduled-token
        # sum must equal these — the bucket-utilization invariant tests
        # and bench assert.
        self.tokens_planned_prefill = 0
        self.tokens_planned_decode = 0
        # blocks pledged to the MOST RECENT planning pass's prefill
        # chunks — a planning-pressure indicator the pool-timeline
        # sampler (ISSUE 13) records per step.  NOTE: the engine
        # executes the plan within the same step, so by the time the
        # end-of-step sample reads this the pledged blocks are
        # typically already materialized into the pool's allocated
        # count — promised is NOT extra unaccounted capacity and must
        # not be summed with `allocated`.
        self.promised_blocks = 0
        # hard sequence-length cap beyond the pool's own capacity
        # (ISSUE 15): an AOT-bound engine can only dispatch buckets
        # inside the artifact's saved universe, so admission must
        # reject a request whose prompt + max_new_tokens outgrows the
        # manifest's max_seq_len HONESTLY (finish_reason=abort + error)
        # instead of letting AotBucketMissing kill the engine thread
        # mid-stream — in a supervised fleet a re-dispatched oversize
        # request would otherwise cascade replica deaths.  The unified
        # program on a TPU caps it too: its kernel's block table must
        # fit scalar memory (EngineCore._cap_ragged_context).  None = no
        # cap (traced engines bucket anything the pool holds).
        # ``seq_len_cap_why`` names the limit in the rejection.
        self.seq_len_cap: Optional[int] = None
        self.seq_len_cap_why = ""

    # --- queue ops ----------------------------------------------------------
    def add(self, req: Request) -> None:
        req.state = RequestState.WAITING
        self.waiting.append(req)

    def remove(self, req: Request) -> None:
        if req in self.running:
            self.running.remove(req)
        try:
            self.waiting.remove(req)
        except ValueError:
            pass  # swallow-ok: remove() contract is idempotent — "not queued" is a normal state (running, or already removed), not a fault

    @property
    def queue_depth(self) -> int:
        return len(self.waiting)

    @property
    def num_running(self) -> int:
        return len(self.running)

    def has_work(self) -> bool:
        return bool(self.waiting or self.running)

    # --- planning -----------------------------------------------------------
    def _usable_blocks(self) -> int:
        return self.kv.num_blocks - 1  # block 0 = null page

    def _needs_prefill(self, req: Request) -> bool:
        """True while ``req``'s prompt (+ kept output, on recompute) is
        not yet in the pool.  The newest generated token's KV is written
        by the decode step that consumes it, so a recompute that reaches
        ``prompt + output - 1`` committed tokens resumes straight into
        decode — the decode step IS its final prefill position."""
        target = len(req.prompt_ids) + len(req.output_tokens)
        if req.output_tokens:
            target -= 1
        return self.kv.seq_len(req.request_id) < target

    def _chunk_capacity(self, req: Request, want: int, promised: int) -> int:
        """Clamp a continuation chunk to what the pool can actually back
        right now (``promised`` = blocks already pledged this pass): the
        pool may have drained since this request was admitted, and a
        chunk the engine cannot allocate must never be planned."""
        rid = req.request_id
        free_slots = (self.kv.num_owned_blocks(rid) * self.kv.block_size
                      - self.kv.seq_len(rid))
        avail = max(0, self.kv.num_available - promised)
        return min(want, free_slots + avail * self.kv.block_size)

    def _prefill_budget(self, decode_rows: int):
        """Tokens this step's prefill work may take, after ``decode_rows``
        decode rows claimed theirs."""
        budget = self.config.max_prefill_tokens_per_step
        remaining = float("inf") if budget is None else int(budget)
        total = self.config.max_tokens_per_step
        if total is not None:
            # unified packing (ISSUE 11): this step's decode rows (slots
            # reserved before prefill planning) already claimed one
            # packed token each — prefill work competes for the rest of
            # the SINGLE budget, so decode latency is protected.  Decode
            # rows themselves are never split across steps, so the
            # packed token count is bounded by max(total, num decode
            # rows), not by total alone.
            remaining = min(remaining, max(0, int(total) - decode_rows))
        return remaining

    def _admission(self, req: Request, available: int, slot_free: bool):
        """What admission does with ``req``, the head of ``waiting``, when
        ``available`` blocks are not yet pledged and ``slot_free`` says a
        new sequence can take its first block
        (``kv.can_start_sequence``): ``("abort", error)`` for
        a request no pool or program of this engine can ever take,
        ``("wait", None)`` while the pool (or the state slots) cannot cover
        it without preempting, else ``("admit", (hit, need))``.  It frees,
        forks and queues nothing — the ONE copy of the admission math, for
        the planning pass that admits and for the plan made while a launch
        is in flight, which may only say that it would."""
        ids = req.prompt_ids + req.output_tokens
        prompt_blocks = self.kv.blocks_for(len(ids))
        target_len = len(req.prompt_ids) + req.sampling.max_new_tokens
        if self.seq_len_cap is not None and target_len > self.seq_len_cap:
            # a sequence no program of this engine can take (outside the
            # AOT artifact's saved buckets, or a block table the ragged
            # kernel cannot prefetch)
            return "abort", (
                f"request targets {target_len} tokens (prompt "
                f"{len(req.prompt_ids)} + max_new_tokens "
                f"{req.sampling.max_new_tokens}) but "
                f"{self.seq_len_cap_why}")
        if prompt_blocks > self._usable_blocks():
            # can never fit, even with the whole pool
            return "abort", (f"request needs {prompt_blocks} KV blocks; "
                             f"pool has {self._usable_blocks()} usable")
        # admit on the UNCACHED tail, not the whole prompt: blocks
        # already in the prefix cache cost nothing new (live shares)
        # or only their reuse-LRU slot (``from_reuse`` — those leave
        # the available set when forked, so they are charged).  This
        # is what makes a warm cache raise admission capacity.
        if req._probe_epoch != self.kv.cache_epoch:
            # leading-block hashes the fleet router already computed
            # (req.prefix_hashes) are reused, not re-hashed
            req._probe_blocks = self.kv.match_prefix(
                ids, precomputed=req.prefix_hashes)
            req._probe_epoch = self.kv.cache_epoch
        hit = req._probe_blocks
        from_reuse = self.kv.reuse_count(hit)
        uncached = prompt_blocks - len(hit)
        # +1 decode-slot headroom, but never demand more than the pool
        # HAS: a prompt filling the pool exactly is still servable when
        # its decode tokens fit the last block's free slots
        need = min(uncached + 1, self._usable_blocks())
        if need + from_reuse > available or not slot_free:
            return "wait", None
        return "admit", (hit, need)

    def _plan_prefills(self, out: SchedulerOutput) -> None:
        """Plan this step's prefill work under the chunk token budget:
        first continue partial prefills (most-important first — finishing
        an in-flight prompt beats admitting a new one), then admit from
        the waiting queue."""
        remaining = self._prefill_budget(len(out.decodes))
        promised = 0  # blocks pledged to prefills planned THIS pass: the
                      # engine allocates them only when it runs the chunk,
                      # so kv.num_available alone would double-count
        for req in sorted(self.running, key=lambda r: r.preempt_key):
            if req.state is not RequestState.RUNNING:
                continue
            if not self._needs_prefill(req):
                continue
            if remaining <= 0:
                break
            want = (len(req.prompt_ids) + len(req.output_tokens)
                    - self.kv.seq_len(req.request_id))
            n = self._chunk_capacity(req, min(want, remaining), promised)
            if n <= 0:
                continue  # pool pressure: wait for decode-side churn
            req._chunk_tokens = int(n)
            promised += self.kv.blocks_needed(req.request_id, n)
            remaining -= n
            out.prefills.append(req)

        admitted = 0
        while (self.waiting
               and len(self.running) < self.config.max_num_seqs
               and admitted < self.config.max_prefills_per_step
               and remaining > 0):
            req = self.waiting[0]
            verdict, detail = self._admission(
                req, self.kv.num_available - promised,
                self.kv.can_start_sequence())
            if verdict == "abort":
                # fail THIS request honestly AT ADMISSION rather than
                # raising from the engine thread mid-stream or live-locking
                # everyone behind it
                self.waiting.popleft()
                req.state = RequestState.FINISHED
                req.finish_reason = FinishReason.ABORT
                req.error = detail
                out.aborted.append(req)
                continue
            if verdict == "wait":
                break  # admission never preempts running work; a model
                       # with per-sequence state also waits for a free slot
            ids = req.prompt_ids + req.output_tokens
            hit, need = detail
            self.waiting.popleft()
            cached = self.kv.fork_prefix(req.request_id, ids, blocks=hit)
            req.num_cached_tokens = cached
            promised += need  # the fork itself already moved from_reuse
                              # blocks out of num_available
            req.state = RequestState.RUNNING
            self.running.append(req)
            n = min(len(ids) - cached, remaining)
            req._chunk_tokens = int(n)
            remaining -= n
            out.prefills.append(req)
            out.admitted.append(req)
            admitted += 1
        self.promised_blocks = promised

    def _preempt(self, victim: Request) -> None:
        """Evict ``victim``: free its blocks, and with them its state slot
        where the model keeps per-sequence state (a recurrent state cannot
        be kept without its sequence: re-admission recomputes it from
        zero) (shared prefix blocks stay
        with their other owners — refcounts guarantee a preemption never
        clobbers a block someone else forked), re-enqueue at the FRONT of
        the waiting queue (a preempted request outranks new arrivals, so
        it is re-admitted and recomputed as soon as blocks free up)."""
        self.running.remove(victim)
        self.kv.free(victim.request_id)
        victim.state = RequestState.PREEMPTED
        victim.num_preemptions += 1
        victim.num_cached_tokens = 0
        victim._chunk_tokens = None
        victim._probe_blocks = None  # re-admission hashes prompt + output,
        victim._probe_epoch = -1     # not the ids this match was for
        self.waiting.appendleft(victim)

    def _pick_victim(self, exclude) -> Optional[Request]:
        # only block-holding requests relieve pressure, and a request
        # that already reserved its slot this step (= more important in
        # the iteration order) is never stolen from
        candidates = [r for r in self.running if r not in exclude
                      and self.kv.num_owned_blocks(r.request_id) > 0]
        if not candidates:
            return None
        return max(candidates, key=lambda r: r.preempt_key)

    def _reserve_decode_slots(self, out: SchedulerOutput) -> None:
        """Reserve one decode slot per running request, preempting the
        least-important block-holding requests on exhaustion.  Iterates
        most-important first so preemption pressure lands on the tail."""
        granted: List[Request] = []
        for req in sorted(list(self.running), key=lambda r: r.preempt_key):
            if req.state is not RequestState.RUNNING:
                continue  # preempted by an earlier iteration
            if self._needs_prefill(req):
                continue  # mid-(chunked)-prefill: no decode slot yet —
                          # the chunk planner advances it instead
            while True:
                slot = self.kv.append_slot(req.request_id)
                if slot is not None:
                    req._slot = slot
                    granted.append(req)
                    out.decodes.append(req)
                    break
                victim = self._pick_victim(exclude=granted + [req])
                if victim is None:
                    # nothing evictable below it: this request itself
                    # yields (it is the least important slot-seeker left)
                    self._preempt(req)
                    out.preempted.append(req)
                    break
                self._preempt(victim)
                out.preempted.append(victim)

    def schedule(self) -> SchedulerOutput:
        """Plan one engine step.  Decode slots are reserved BEFORE
        prefill planning, so blocks promised to a freshly planned chunk
        can never be consumed by this step's decode appends.  A request
        whose prefill completes samples its first token from the final
        chunk's last-position logits within the same step, so it is not
        in ``decodes``."""
        out = SchedulerOutput()
        self._reserve_decode_slots(out)
        self._plan_prefills(out)
        # burst headroom (ISSUE 19): computed AFTER slot reservation and
        # chunk planning, so it reflects the pool this plan leaves behind
        out.burst_capacity = self.kv.burst_capacity(len(out.decodes))
        self.tokens_planned_prefill += sum(
            r._chunk_tokens or 0 for r in out.prefills)
        self.tokens_planned_decode += len(out.decodes)
        total = self.config.max_tokens_per_step
        if total is not None:
            # leftover of the SINGLE step budget after decode rows and
            # planned prefill chunks: the spec-decode draft allowance
            # (the engine ledgers any drafts it actually packs)
            used = len(out.decodes) + sum(
                r._chunk_tokens or 0 for r in out.prefills)
            out.draft_budget = max(0, int(total) - used)
        return out

    def plan_ahead(self, flying) -> Tuple[Optional[SchedulerOutput], str]:
        """Plan the next step WHILE a decode launch is in flight, on the
        assumption that every row of it (``flying``: its requests) yields
        one token the host has not read yet.  Only a pure continuation is
        planned: every running row that goes on gets its decode slot from
        blocks that are free now, nothing is preempted, nothing admitted.
        Anything else is for :meth:`schedule`, after the launch has been
        read, and this pass says which rule stood in the way BEFORE it
        changes anything: ``(None, reason)`` with ``reason`` one of
        ``observability.tracer.SETTLE_REASONS``:
        ``prefill`` (a running request still has prompt to compute),
        ``finish`` (every row ends with the token in flight), ``preempt``
        (the rows need more blocks than are free) and ``admit`` (the head
        of ``waiting`` could be admitted, or is to be refused there: a
        queue that is merely non-empty does not stand in the way).  A row
        whose token in flight is its ``max_new_tokens``-th ends by length
        and gets no slot; what it gives back when it retires (its blocks,
        its place in the running set, its state slot) is counted as free
        for the admission test, so that an admission the synchronous order
        would make in this step is not put off by one.  A row that ends on
        an EOS token cannot be known and keeps its row, whose result the
        engine drops."""
        flying = {r.request_id for r in flying}
        rows: List[Request] = []
        need = ending = freed = 0
        for req in sorted(self.running, key=lambda r: r.preempt_key):
            if self._needs_prefill(req):
                return None, SETTLE_PREFILL
            rid = req.request_id
            if rid in flying and (len(req.output_tokens) + 1
                                  >= req.sampling.max_new_tokens):
                ending += 1
                freed += self.kv.num_owned_blocks(rid)
                continue
            need += self.kv.blocks_needed(rid, 1)
            rows.append(req)
        if not rows:
            return None, SETTLE_FINISH
        if need > self.kv.num_available:
            return None, SETTLE_PREEMPT
        if (self.waiting
                and len(self.running) - ending < self.config.max_num_seqs
                and self.config.max_prefills_per_step > 0
                and self._prefill_budget(len(rows)) > 0
                and self._admission(
                    self.waiting[0], self.kv.num_available - need + freed,
                    bool(ending) or self.kv.can_start_sequence()
                )[0] != "wait"):
            return None, SETTLE_ADMIT
        out = SchedulerOutput()
        for req in rows:
            req._slot = self.kv.append_slot(req.request_id)
            out.decodes.append(req)
        self.promised_blocks = 0
        self.tokens_planned_decode += len(rows)
        return out, ""

    @property
    def tokens_planned(self) -> int:
        """Total tokens ever planned (prefill chunk tokens + one per
        decode row) — the scheduler side of the scheduled-token
        invariant."""
        return self.tokens_planned_prefill + self.tokens_planned_decode
