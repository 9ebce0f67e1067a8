"""Prefix-cache block reuse + chunked prefill (ISSUE 4).

Covers the tentpole's correctness bar:

* greedy outputs token-identical with the cache/chunking ON vs OFF —
  including across a preemption and across a reuse-LRU eviction;
* shared-prefix fork safety when the PARENT is preempted (a preempted
  request must never free blocks another request forked);
* eviction-then-reuse round trip on the bounded LRU;
* jit trace count still bounded by the bucket sets with chunking on;
* the admission fix: a warm cache admits prompts a cold pool cannot
  (charging the uncached tail, not the whole prompt);
* the shared-prefix stream's exact counters cache on vs off (hit and
  computed prefill tokens, trace counts unchanged).
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (
    ContinuousBatchingScheduler,
    EngineCore,
    FinishReason,
    KVCacheManager,
    Request,
    SamplingParams,
    SchedulerConfig,
)

PROMPTS = [[5, 9, 23, 7], [40, 2, 11], [1, 2, 3, 4, 5, 6], [100, 101]]


def _model(layers=2):
    paddle.seed(0)
    return LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=layers))


def _engine(model, num_blocks=64, block_size=4, max_num_seqs=4,
            budget=None, prefix_cache=True, **kw):
    return EngineCore(
        model, num_blocks=num_blocks, block_size=block_size,
        scheduler_config=SchedulerConfig(
            max_num_seqs=max_num_seqs,
            max_prefill_tokens_per_step=budget),
        prefix_cache=prefix_cache, **kw)


def _solo(model, prompt, n):
    """Reference output: fresh cache-off engine, one-shot prefill."""
    eng = _engine(model, prefix_cache=False)
    req = eng.add_request(prompt, SamplingParams(max_new_tokens=n))
    eng.run(max_steps=300)
    return req.output_tokens


# --------------------------------------------------------------------------
# BlockPool bookkeeping (no model, no jit)
# --------------------------------------------------------------------------
class TestBlockPoolPrefixCache:
    def test_record_match_fork_roundtrip(self):
        kv = KVCacheManager(num_blocks=8, block_size=4)
        ids = list(range(12))                      # 3 full blocks
        assert kv.allocate("a", 12) and not kv.free("missing")
        kv.commit("a", 12)
        assert kv.record_block_hashes("a", ids) == 3
        assert kv.record_block_hashes("a", ids) == 0   # idempotent
        # live share: the longest USABLE prefix is capped one token short
        # of the prompt (the prefill must still produce logits)
        assert kv.fork_prefix("b", ids) == 8           # 2 of 3 blocks
        assert kv.table("b") == kv.table("a")[:2]
        assert kv._ref[kv.table("a")[0]] == 2
        # parent leaves: shared blocks stay out (b owns them); only the
        # exclusive hashed block returns — parked in the reuse LRU, still
        # counted available
        before = kv.num_available
        kv.free("a")
        assert kv.num_available == before + 1
        assert kv.num_free < kv.num_available           # one block parked
        assert kv._ref[kv.table("b")[0]] == 1

    def test_reuse_lru_revival_counts_as_hit(self):
        kv = KVCacheManager(num_blocks=6, block_size=4)   # 5 usable
        ids = list(range(8))                               # 2 full blocks
        kv.allocate("warm", 8)
        kv.commit("warm", 8)
        kv.record_block_hashes("warm", ids)
        kv.free("warm")
        assert kv.num_available == 5
        hit_blocks, from_reuse = kv.probe_prefix(ids)
        assert (hit_blocks, from_reuse) == (1, 1)          # capped at len-1
        assert kv.fork_prefix("again", ids) == 4
        assert kv.reuse_hits == 1
        # the revived block left the LRU and is refcounted again
        assert kv._ref[kv.table("again")[0]] == 1
        assert kv.probe_prefix(ids) == (1, 0)              # now a live share

    def test_allocation_evicts_lru_and_drops_hash(self):
        kv = KVCacheManager(num_blocks=6, block_size=4)    # 5 usable
        ids = list(range(8))
        kv.allocate("warm", 8)
        kv.commit("warm", 8)
        kv.record_block_hashes("warm", ids)
        kv.free("warm")
        assert kv.num_free == 3 and kv.num_available == 5
        # a 5-block allocation must clobber both cached blocks
        assert kv.allocate("big", 20)
        assert kv.reuse_evictions == 2
        assert kv.probe_prefix(ids) == (0, 0)              # hashes died
        kv.free("big")
        assert kv.num_available == 5

    def test_eviction_order_keeps_shortest_prefixes_longest(self):
        kv = KVCacheManager(num_blocks=8, block_size=4)    # 7 usable
        ids = list(range(12))                              # 3 full blocks
        kv.allocate("a", 12)
        kv.commit("a", 12)
        kv.record_block_hashes("a", ids)
        kv.free("a")                                       # 3 parked
        probe_ids = ids + [99]         # 13 tokens: all 3 blocks matchable
        assert kv.probe_prefix(probe_ids) == (3, 3)
        # free list has 4; taking 5 evicts exactly ONE cached block — the
        # LRU-oldest, which free() made the DEEPEST chain block, so the
        # short (most shareable) prefix survives
        assert kv.allocate("big", 20)
        assert kv.reuse_evictions == 1
        assert kv.probe_prefix(probe_ids) == (2, 2)

    def test_preempted_parent_never_frees_forked_blocks(self):
        """Fork safety: freeing the parent (preemption) must leave every
        block the child forked intact and owned."""
        kv = KVCacheManager(num_blocks=8, block_size=4)
        ids = list(range(12))
        kv.allocate("parent", 12)
        kv.commit("parent", 12)
        kv.record_block_hashes("parent", ids)
        assert kv.fork_prefix("child", ids) == 8
        shared = list(kv.table("child"))
        kv.free("parent")                                  # preemption
        assert kv.table("child") == shared
        for b in shared:
            assert kv._ref[b] == 1
            assert b not in kv._free
        # exhaust the pool: the child's blocks are never handed out
        assert kv.allocate("churn", 4 * kv.num_available)
        assert all(b not in kv.table("churn") for b in shared)

    def test_fork_prefix_disabled_cache_is_noop(self):
        kv = KVCacheManager(num_blocks=8, block_size=4,
                            enable_prefix_cache=False)
        ids = list(range(8))
        kv.allocate("a", 8)
        kv.commit("a", 8)
        assert kv.record_block_hashes("a", ids) == 0
        kv.free("a")
        assert kv.num_free == kv.num_available == 7
        assert kv.fork_prefix("b", ids) == 0


# --------------------------------------------------------------------------
# token identity: cache on vs off
# --------------------------------------------------------------------------
class TestPrefixCacheTokenIdentity:
    def test_warm_prompt_identical_and_skips_compute(self):
        m = _model()
        prompt = list(range(3, 15))                 # 12 tokens = 3 blocks
        ref = _solo(m, prompt, 6)
        eng = _engine(m)
        r1 = eng.add_request(prompt, SamplingParams(max_new_tokens=6))
        eng.run(max_steps=200)
        computed_cold = eng.metrics.counters["prefill_tokens_computed"]
        r2 = eng.add_request(prompt, SamplingParams(max_new_tokens=6))
        eng.run(max_steps=200)
        c = eng.metrics.counters
        assert r1.output_tokens == ref
        assert r2.output_tokens == ref
        assert c["prefix_cache_hit_tokens"] > 0
        assert r2.num_cached_tokens > 0
        # the warm prefill computed strictly fewer tokens than the cold
        assert (c["prefill_tokens_computed"] - computed_cold
                < computed_cold)

    def test_shared_prefix_batch_on_vs_off(self):
        m = _model()
        rng = np.random.default_rng(7)
        prefix = rng.integers(0, 256, 8).tolist()
        prompts = [prefix + rng.integers(0, 256, 5).tolist()
                   for _ in range(4)]

        def run(prefix_cache):
            eng = _engine(m, prefix_cache=prefix_cache)
            reqs = [eng.add_request(p, SamplingParams(max_new_tokens=5))
                    for p in prompts]
            eng.run(max_steps=500)
            return [r.output_tokens for r in reqs], eng

        off, _ = run(False)
        on, eng = run(True)
        assert on == off
        assert eng.metrics.counters["prefix_cache_hit_tokens"] > 0
        g = eng.metrics._gauges["prefix_cached_token_ratio"]
        assert g.value > 0.0

    def test_identity_across_preemption_with_cache_on(self):
        """A pool too small for both requests forces preemption; with the
        prefix cache ON the preempted request must still recompute to
        token-identical output (its own freed blocks may satisfy the
        re-admission fork)."""
        m = _model(layers=4)
        refs = [_solo(m, p, 8) for p in PROMPTS[:2]]
        eng = _engine(m, num_blocks=10, block_size=2, max_num_seqs=4)
        reqs = [eng.add_request(p, SamplingParams(max_new_tokens=8))
                for p in PROMPTS[:2]]
        eng.run(max_steps=300)
        assert eng.metrics.counters["preemptions"] >= 1
        for req, ref in zip(reqs, refs):
            assert req.finish_reason == FinishReason.LENGTH
            assert req.output_tokens == ref
        assert eng.kv.num_available == 9            # nothing leaked

    def test_identity_across_eviction(self):
        """Warm the cache, churn the pool until cached blocks are
        CLOBBERED (reuse_evictions > 0), then re-run the warm prompt:
        output must still be token-identical (cold recompute)."""
        m = _model()
        prompt = list(range(10, 22))                # 3 blocks at bs=4
        ref = _solo(m, prompt, 5)
        eng = _engine(m, num_blocks=10, block_size=4)  # 9 usable
        r1 = eng.add_request(prompt, SamplingParams(max_new_tokens=5))
        eng.run(max_steps=200)
        assert r1.output_tokens == ref
        rng = np.random.default_rng(3)
        for i in range(4):                          # churn: distinct prompts
            churn = (200 + rng.integers(0, 50, 12)).tolist()
            eng.add_request(churn, SamplingParams(max_new_tokens=4))
            eng.run(max_steps=300)
        assert eng.kv.reuse_evictions > 0
        r2 = eng.add_request(prompt, SamplingParams(max_new_tokens=5))
        eng.run(max_steps=200)
        assert r2.output_tokens == ref
        c = eng.metrics.counters
        assert c["prefix_cache_evictions"] == eng.kv.reuse_evictions

    def test_parent_preempted_while_child_shares(self):
        """Engine-level fork safety: the LOW-priority parent is preempted
        while the child still shares its prompt blocks — both must finish
        token-identical (the preemption frees only the parent's exclusive
        ownership, refcounts protect the share)."""
        m = _model()
        prompt = list(range(30, 42))                # 12 tokens
        ref_long = _solo(m, prompt, 10)
        ref_child = _solo(m, prompt, 4)
        eng = _engine(m, num_blocks=14, block_size=2, max_num_seqs=4)
        parent = eng.add_request(prompt, SamplingParams(max_new_tokens=10),
                                 priority=5)        # preemption victim
        eng.step()                                  # parent prefills
        child = eng.add_request(prompt, SamplingParams(max_new_tokens=4),
                                priority=0)
        eng.run(max_steps=500)
        assert child.output_tokens == ref_child
        assert parent.output_tokens == ref_long
        assert child.num_cached_tokens > 0          # the fork happened
        assert eng.kv.num_available == 13


# --------------------------------------------------------------------------
# chunked prefill
# --------------------------------------------------------------------------
class TestChunkedPrefill:
    def test_long_prompt_chunked_vs_one_shot(self):
        m = _model()
        prompt = list(range(3, 16))                 # 13 tokens
        ref = _solo(m, prompt, 6)
        for budget in (4, 5, 8):
            eng = _engine(m, budget=budget, prefix_cache=False)
            req = eng.add_request(prompt, SamplingParams(max_new_tokens=6))
            eng.run(max_steps=200)
            assert req.output_tokens == ref, f"budget={budget}"
            assert eng.metrics.counters["chunked_prefill_steps"] >= 2

    def test_chunked_with_cache_on_vs_off(self):
        m = _model()
        prompt = list(range(50, 64))
        ref = _solo(m, prompt, 5)
        eng = _engine(m, budget=4)                  # cache AND chunking
        r1 = eng.add_request(prompt, SamplingParams(max_new_tokens=5))
        eng.run(max_steps=300)
        r2 = eng.add_request(prompt, SamplingParams(max_new_tokens=5))
        eng.run(max_steps=300)
        assert r1.output_tokens == ref
        assert r2.output_tokens == ref
        assert r2.num_cached_tokens > 0

    def test_chunk_shares_steps_with_running_decode(self):
        """The point of chunking: while a long prompt advances chunk by
        chunk, an already-running request keeps emitting tokens in the
        SAME engine steps instead of stalling behind a solo prefill."""
        m = _model()
        short_ref = _solo(m, PROMPTS[0], 12)
        long_prompt = list(range(100, 117))         # 17 tokens, 5 chunks
        long_ref = _solo(m, long_prompt, 3)
        eng = _engine(m, budget=4)
        short = eng.add_request(PROMPTS[0], SamplingParams(max_new_tokens=12))
        eng.step()                                  # short prefills
        long = eng.add_request(long_prompt, SamplingParams(max_new_tokens=3))
        overlapped = 0
        for _ in range(30):
            before = len(short.output_tokens)
            eng.step()
            if (not long.output_tokens               # still prefilling
                    and len(short.output_tokens) > before):
                overlapped += 1
            if long.output_tokens:
                break
        assert overlapped >= 2, "decode stalled behind the chunked prefill"
        eng.run(max_steps=300)
        assert short.output_tokens == short_ref
        assert long.output_tokens == long_ref

    def test_trace_count_bounded_with_chunking(self):
        """MPK discipline with chunking on: chunk widths and table widths
        come from the same power-of-two buckets, so the prefill program
        compiles once per (chunk-bucket, table-bucket) pair — never per
        request — and the in-trace counters prove it."""
        m = _model()
        eng = _engine(m, num_blocks=256, budget=4, max_num_seqs=4)
        rng = np.random.default_rng(0)
        reqs = []
        for i in range(15):
            plen = int(rng.integers(2, 15))
            reqs.append(eng.add_request(
                rng.integers(0, 256, plen).tolist(),
                SamplingParams(max_new_tokens=int(rng.integers(2, 6)))))
        eng.run(max_steps=2000)
        assert all(r.finished for r in reqs)
        assert eng.prefill_trace_count <= len(eng.prefill_buckets)
        assert eng.decode_trace_count <= len(eng.decode_buckets)
        assert eng.prefill_trace_count + eng.decode_trace_count <= 20

    def test_zero_or_negative_budget_rejected_at_config_time(self):
        """A budget of 0 would plan no prefill ever — requests queue
        forever while has_work() stays True — so the config fails fast."""
        for bad in (0, -1):
            with pytest.raises(ValueError, match="max_prefill_tokens"):
                SchedulerConfig(max_prefill_tokens_per_step=bad)

    def test_blocked_admission_probe_memoized_across_steps(self):
        """A head-of-queue request blocked on capacity must not re-hash
        its whole prompt every engine step: the match is memoized on the
        request, keyed by the pool's cache_epoch."""
        kv = KVCacheManager(num_blocks=6, block_size=4)  # 5 usable
        sched = ContinuousBatchingScheduler(
            SchedulerConfig(max_num_seqs=8, max_prefills_per_step=4), kv)
        kv.allocate("tenant", 16)                        # 4 of 5 blocks
        kv.commit("tenant", 16)
        req = Request(prompt_ids=list(range(20)))        # 5 blocks: fits
                                                         # the pool but not
                                                         # the 1 free block
        sched.add(req)
        assert sched.schedule().prefills == []
        epoch = kv.cache_epoch
        assert req._probe_epoch == epoch                 # probed once
        probed = req._probe_blocks
        assert sched.schedule().prefills == []           # still blocked
        assert req._probe_blocks is probed               # NOT re-hashed
        kv.record_block_hashes("tenant", list(range(16)))
        assert kv.cache_epoch != epoch                   # index changed →
        sched.schedule()                                 # re-probe happens
        assert req._probe_epoch == kv.cache_epoch

    def test_budget_none_keeps_one_shot_program(self):
        """Default config: no chunking, the dense one-shot prefill path
        (and its bucket keys) are byte-for-byte the PR-1 behaviour."""
        m = _model()
        eng = _engine(m, prefix_cache=False)
        eng.add_request(PROMPTS[2], SamplingParams(max_new_tokens=2))
        eng.run(max_steps=50)
        assert eng.metrics.counters["chunked_prefill_steps"] == 0
        assert all(k[0] == "prefill" for k in eng.prefill_buckets)


# --------------------------------------------------------------------------
# admission capacity (ISSUE 4 satellite)
# --------------------------------------------------------------------------
class TestAdmissionCapacity:
    def _setup(self, warm: bool):
        kv = KVCacheManager(num_blocks=12, block_size=4)   # 11 usable
        sched = ContinuousBatchingScheduler(
            SchedulerConfig(max_num_seqs=8, max_prefills_per_step=4), kv)
        prefix = list(range(20))                    # 5 full blocks
        # a live tenant holds the prefix blocks (it is mid-decode)
        kv.allocate("tenant", 20)
        kv.commit("tenant", 20)
        if warm:
            kv.record_block_hashes("tenant", prefix)
        return kv, sched, prefix + [77, 78, 79, 80]  # 24 tokens, 6 blocks

    def test_cold_prompt_misses_admission(self):
        kv, sched, prompt = self._setup(warm=False)
        req = Request(prompt_ids=prompt)
        sched.add(req)
        plan = sched.schedule()
        # cold charge: 6 prompt blocks + 1 headroom = 7 > 6 free
        assert plan.prefills == [] and sched.waiting[0] is req

    def test_warm_cache_admits_what_cold_cannot(self):
        """The satellite regression: an identical prompt that warmed the
        cache makes the SAME pool admit — admission charges only the
        uncached tail (1 block + headroom ≤ 6 free)."""
        kv, sched, prompt = self._setup(warm=True)
        req = Request(prompt_ids=prompt)
        sched.add(req)
        plan = sched.schedule()
        assert plan.prefills == [req]
        assert plan.admitted == [req]
        assert req.num_cached_tokens == 20          # forked, not recomputed
        assert kv.table(req.request_id)[:5] == kv.table("tenant")


# --------------------------------------------------------------------------
# the shared-prefix stream, cache on vs off: exact counters
# --------------------------------------------------------------------------
class TestSharedPrefixStream:
    def test_shared_prefix_phase_counters(self):
        """Six prompts sharing two full blocks, chunk budget 8 (prefix =
        one chunk, so the chunk buckets coincide cache on and off):
        FEWER prefill tokens computed with the cache on, greedy outputs
        identical, jit trace counts unchanged — each as the exact count
        this stream gives."""
        rng = np.random.default_rng(0)
        prefix = rng.integers(0, 256, 8).tolist()
        prompts = [prefix + rng.integers(0, 256, 8).tolist()
                   for _ in range(6)]

        def run(prefix_cache):
            eng = _engine(_model(), num_blocks=128, budget=8,
                          prefix_cache=prefix_cache)
            # 6 new tokens keep requests alive long enough that BOTH
            # runs sweep the same decode batch buckets {1,2,4}
            reqs = [eng.add_request(p, SamplingParams(max_new_tokens=6),
                                    slo_ms=60_000.0) for p in prompts]
            eng.run(max_steps=2000)
            assert all(r.finished for r in reqs)
            return eng, [list(r.output_tokens) for r in reqs]

        (on, on_out), (off, off_out) = run(True), run(False)
        assert on_out == off_out
        c_on, c_off = on.metrics.counters, off.metrics.counters
        # cached-token ratio 40 / (40 + 56): five followers fork the
        # 8-token prefix, and the saving is exactly those 40 tokens
        assert c_on["prefix_cache_hit_tokens"] == 40
        assert c_on["prefill_tokens_computed"] == 56
        assert c_off["prefix_cache_hit_tokens"] == 0
        assert c_off["prefill_tokens_computed"] == 96
        # fixed-shape discipline: the cache changes WHICH tokens run, not
        # which programs compile
        for eng in (on, off):
            assert eng.prefill_trace_count == 2
            assert eng.decode_trace_count == 3
            # every request was scored against its SLO, and the step
            # profiler's scheduled-token sum is the scheduler's ledger
            good = eng.metrics.slo_breakdown()["goodput"]
            assert good["slo_total"] == 6 and good["slo_good"] == 6
            rep = eng.stepprof.utilization_report()
            assert rep["padding_ratio"] is not None
            assert rep["scheduled_tokens"] == eng.scheduler.tokens_planned
            # TTFT/ITL histograms ride in the registry snapshot
            snap = eng.metrics.snapshot()
            assert "serving_time_to_first_token_seconds" in snap
            assert "serving_inter_token_latency_seconds" in snap
        # per-request cache attribution sums to the hit counter
        attr = on.cachestat.snapshot()["attribution"]
        assert attr["cached_tokens_total"] == 40
