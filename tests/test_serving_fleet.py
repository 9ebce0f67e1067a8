"""Data-parallel serving fleet tests (ISSUE 6).

A real :class:`FleetRouter` over N live engine threads, CPU-provable:

* dp=2 greedy output token-identical to dp=1 — across preemption-with-
  recompute, chunked prefill, and warm prefix-cache forks — with every
  replica's jit trace count inside the single-engine bucket bound;
* prefix-affinity consistent-hash routing: same-prefix requests
  concentrate on ONE replica (affinity-hit counter), distinct prefixes
  spread, dead replicas only remap their own keys;
* abort/timeout routed through the OWNING replica (the router's
  request→replica map), returning that replica's pool to zero occupancy;
* replica-death failover: the fleet serves on with one engine thread
  dead, excluded from routing and visible on /metrics; FleetDown (HTTP
  503) only when ALL replicas die;
* fleet-wide graceful drain with zero pool occupancy on every replica.

HTTP-level coverage drives a real :class:`CompletionServer` over a dp=2
fleet on a loopback socket, like ``test_serving_server.py``.
"""

import asyncio
import http.client
import json
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.ops.paged_attention import BlockPool, prefix_chain_hashes
from paddle_tpu.serving import (
    EngineCore,
    FleetConfig,
    FleetDown,
    FleetRouter,
    FleetSaturated,
    SamplingParams,
    SchedulerConfig,
)
from paddle_tpu.serving.fleet import affinity_replica_index
from paddle_tpu.serving.server import CompletionServer, ServerConfig

BS = 4  # block size everywhere in this file


def _prompts(n=6, prefix_tokens=8, tail_tokens=8, seed=0):
    """n prompts sharing one prefix of full blocks, distinct tails."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, 256, prefix_tokens).tolist()
    return [prefix + rng.integers(0, 256, tail_tokens).tolist()
            for _ in range(n)]


def _factory(num_blocks=64, max_num_seqs=4, chunk=None):
    def make(i, registry):
        paddle.seed(0)  # every replica gets identical weights
        model = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=2))
        return EngineCore(
            model, num_blocks=num_blocks, block_size=BS,
            scheduler_config=SchedulerConfig(
                max_num_seqs=max_num_seqs,
                max_prefill_tokens_per_step=chunk),
            registry=registry, metrics_labels={"replica": str(i)})
    return make


def _fleet(dp, num_blocks=64, max_num_seqs=4, chunk=None, max_queue=64,
           affinity_blocks=2):
    f = FleetRouter.build(
        _factory(num_blocks=num_blocks, max_num_seqs=max_num_seqs,
                 chunk=chunk),
        dp=dp,
        config=FleetConfig(max_queue=max_queue,
                           affinity_blocks=affinity_blocks))
    return f.start()


def _prompt_targeting(fleet, replica_index, tail_tokens=8, prefix_tokens=8):
    """Deterministically find a shared-prefix-shaped prompt whose
    affinity target (all replicas eligible) is ``replica_index``."""
    for seed in range(1000):
        p = _prompts(n=1, prefix_tokens=prefix_tokens,
                     tail_tokens=tail_tokens, seed=1000 + seed)[0]
        if fleet.predict_replica(p) == replica_index:
            return p
    raise AssertionError("no prompt found for target replica")


# --- routing-layer unit tests ------------------------------------------------

class TestPrefixHashHooks:
    def test_match_prefix_precomputed_equivalent(self):
        """match_prefix with router-precomputed leading hashes returns
        exactly what the self-hashing walk returns."""
        pool = BlockPool(32, BS, enable_prefix_cache=True)
        ids = list(range(40, 60))
        assert pool.allocate("a", len(ids))
        pool._lens["a"] = len(ids)
        pool.record_block_hashes("a", ids)
        pre = prefix_chain_hashes(ids, BS, max_blocks=2)
        assert len(pre) == 2
        for probe in (ids, ids[:9], ids + [1, 2, 3]):
            assert (pool.match_prefix(probe, precomputed=pre)
                    == pool.match_prefix(probe))

    def test_prefix_chain_hashes_matches_cache_chain(self):
        """The routing hash IS the prefix-cache chain: a cached block's
        registered hash equals prefix_chain_hashes at that depth."""
        pool = BlockPool(32, BS, enable_prefix_cache=True)
        ids = list(range(16))
        assert pool.allocate("a", len(ids))
        pool._lens["a"] = len(ids)
        pool.record_block_hashes("a", ids)
        chain = prefix_chain_hashes(ids, BS)
        table = pool._tables["a"]
        for depth, h in enumerate(chain):
            assert pool._hash_index[h] == table[depth]

    def test_ring_is_consistent_on_death(self):
        """Excluding one replica only remaps ITS keys: every key whose
        target survives keeps its target."""
        fleet = _fleet(3)
        try:
            keys = [int.from_bytes(
                fleet.affinity_key(p)[-1][:8], "big")
                for p in _prompts(n=24, seed=7)]
            before = [fleet._ring_target(k, fleet.replicas).index
                      for k in keys]
            survivors = [r for r in fleet.replicas if r.index != 0]
            after = [fleet._ring_target(k, survivors).index for k in keys]
            for b, a in zip(before, after):
                if b != 0:
                    assert a == b  # unaffected key did not move
                else:
                    assert a != 0  # dead replica's keys remapped
        finally:
            fleet.shutdown(drain_timeout=1.0)


class TestFleetConstruction:
    def test_duplicate_request_id_rejected_synchronously(self):
        """A reused in-flight request id must fail the CALLER — routed
        through, it would either orphan the first request's owner-map
        entry or raise inside the owning engine thread and kill the
        replica."""
        fleet = _fleet(2)
        try:
            h = fleet.submit_request(
                _prompts(n=1, seed=21)[0],
                SamplingParams(max_new_tokens=5000), request_id="dup")
            with pytest.raises(ValueError, match="already in flight"):
                fleet.submit_request(
                    _prompts(n=1, seed=22)[0],
                    SamplingParams(max_new_tokens=2), request_id="dup")
            fleet.abort(h.rid)
            fleet.wait([h], timeout=60)
            # finished ids are evicted from the owner map: reuse is fine
            deadline = time.monotonic() + 30
            while "dup" in fleet._owner and time.monotonic() < deadline:
                time.sleep(0.005)
            h2 = fleet.submit_request(
                _prompts(n=1, seed=23)[0],
                SamplingParams(max_new_tokens=2), request_id="dup")
            fleet.wait([h2], timeout=60)
            assert h2.finish_reason == "length"
        finally:
            fleet.shutdown(drain_timeout=1.0)

    def test_shared_registry_requires_distinct_labels(self):
        """Two replicas on one registry without distinct metrics_labels
        would silently merge every per-replica serving series — refused
        at construction."""
        from paddle_tpu.observability.metrics import MetricsRegistry

        registry = MetricsRegistry(max_series=4096)

        def make(i, reg):
            paddle.seed(0)
            model = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=2))
            return EngineCore(model, num_blocks=16, block_size=BS,
                              registry=reg)  # no metrics_labels: collide

        with pytest.raises(ValueError, match="distinct metrics_labels"):
            FleetRouter.build(make, dp=2, registry=registry)


# --- token identity ----------------------------------------------------------

class TestDpTokenIdentity:
    def _run_waves(self, fleet, prompts, max_new_tokens=10):
        """Two waves of the same prompts: wave 2 hits a warm prefix
        cache on whichever replica owns the prefix.  Returns outputs
        keyed (wave, prompt_index)."""
        out = {}
        for wave in range(2):
            handles = [
                fleet.submit_request(
                    p, SamplingParams(max_new_tokens=max_new_tokens),
                    request_id=f"w{wave}-r{i}")
                for i, p in enumerate(prompts)]
            fleet.wait(handles, timeout=300)
            for i, h in enumerate(handles):
                assert h.finish_reason == "length", (wave, i,
                                                     h.finish_reason)
                out[(wave, i)] = h.output_tokens
        return out

    def test_dp2_token_identical_to_dp1_with_preemption_and_warm_forks(self):
        """The acceptance contract: dp=2 greedy output token-identical
        to dp=1 across preemption-with-recompute (pool sized to
        preempt), chunked prefill (token budget 8), and warm
        prefix-cache forks (second wave) — per-replica jit trace counts
        inside the single-engine bucket bound."""
        prompts = _prompts(n=6)
        fleets = {}
        outs = {}
        try:
            for dp in (1, 2):
                # 14 usable blocks of 4 cannot hold 4 concurrent
                # 16+9-token sequences: preemption + recompute fires
                fleets[dp] = _fleet(dp, num_blocks=15, chunk=8)
                outs[dp] = self._run_waves(fleets[dp], prompts)
            assert outs[1] == outs[2], \
                "dp=2 greedy output diverged from dp=1"
            preempt = {
                dp: sum(r.engine.metrics.counters["preemptions"]
                        for r in fleets[dp].replicas)
                for dp in fleets}
            assert preempt[1] and preempt[2], \
                f"sized to preempt, but none fired: {preempt}"
            # warm prefix forks: wave 2 hit the cache somewhere
            for dp, fleet in fleets.items():
                hits = sum(
                    r.engine.metrics.counters["prefix_cache_hit_tokens"]
                    for r in fleet.replicas)
                assert hits > 0, f"dp={dp}: no warm prefix fork hit"
            # per-replica trace counts obey the single-engine bound, so
            # fleet total <= replicas x single-engine bound
            bound1 = (len(fleets[1].replicas[0].engine.prefill_buckets)
                      + len(fleets[1].replicas[0].engine.decode_buckets))
            total2 = 0
            for r in fleets[2].replicas:
                e = r.engine
                assert e.prefill_trace_count <= len(e.prefill_buckets)
                assert e.decode_trace_count <= len(e.decode_buckets)
                assert e.prefill_buckets <= fleets[1].replicas[0].engine.prefill_buckets
                assert e.decode_buckets <= fleets[1].replicas[0].engine.decode_buckets
                total2 += e.prefill_trace_count + e.decode_trace_count
            assert total2 <= len(fleets[2].replicas) * bound1
        finally:
            for fleet in fleets.values():
                fleet.shutdown(drain_timeout=2.0)
        # drain left every replica's pool empty
        for fleet in fleets.values():
            for r in fleet.replicas:
                assert r.engine.kv.occupancy() == 0.0, \
                    f"replica {r.index} leaked blocks"


# --- affinity keeps the cache warm -------------------------------------------

class TestAffinityKeepsCachedRatio:
    """Two shared-prefix families, picked so the dp=2 ring sends one to
    each replica, through a FIXED total capacity: dp=1 serves the stream
    on one engine with the combined pool (29 blocks, 8 seqs), dp=2
    halves both per replica (15 blocks, 4 seqs).  Either pool is too
    small for its concurrent 16 + 10-token sequences, so every engine
    preempts.  Consistent-hash affinity keeps each family on ONE replica,
    so no replica's cached-token ratio falls under the dp=1 ratio
    (round-robin would recompute every prefix on every replica it
    touched).  Ratios are asserted as the hit and computed token counts
    they are made of, exact on this stream."""

    @pytest.fixture(scope="class")
    def runs(self, hold_intake):
        rng = np.random.default_rng(0)
        fam_a = rng.integers(0, 256, 8).tolist()
        target_a = affinity_replica_index(fam_a, dp=2, block_size=BS)
        while True:
            fam_b = rng.integers(0, 256, 8).tolist()
            if affinity_replica_index(fam_b, dp=2, block_size=BS) \
                    != target_a:
                break
        prompts = []
        for _ in range(4):
            prompts.append(fam_a + rng.integers(0, 256, 8).tolist())
            prompts.append(fam_b + rng.integers(0, 256, 8).tolist())

        def replica_row(eng):
            c = eng.metrics.counters
            return {"admitted": c["requests_admitted"],
                    "hit": c["prefix_cache_hit_tokens"],
                    "computed": c["prefill_tokens_computed"],
                    "preempted": c["preemptions"] > 0,
                    "bounded": (eng.prefill_trace_count
                                <= len(eng.prefill_buckets)
                                and eng.decode_trace_count
                                <= len(eng.decode_buckets))}

        out = {}
        for dp, num_blocks, max_seqs in ((1, 29, 8), (2, 15, 4)):
            fleet = _fleet(dp, num_blocks=num_blocks,
                           max_num_seqs=max_seqs, chunk=8)
            try:
                gates = [hold_intake(r) for r in fleet.replicas]
                handles = [fleet.submit_request(
                    p, SamplingParams(max_new_tokens=10),
                    request_id=f"r{i}") for i, p in enumerate(prompts)]
                for gate in gates:
                    gate.set()
                fleet.wait(handles, timeout=600)
                out[dp] = {
                    "outputs": {h.rid: h.output_tokens for h in handles},
                    "routing": dict(fleet.routing_counts),
                    "evaluations": fleet.alerts.snapshot()["evaluations"],
                    "transitioned": sorted(
                        name for name, trs in
                        fleet.alerts.transitions_report().items() if trs),
                    "replicas": [replica_row(r.engine)
                                 for r in fleet.replicas]}
            finally:
                fleet.shutdown(drain_timeout=2.0)
        return out

    def test_outputs_identical(self, runs):
        assert runs[1]["outputs"] == runs[2]["outputs"]

    def test_every_request_routed_by_affinity(self, runs):
        assert runs[2]["routing"] == {"affinity_hit": 8,
                                      "fallback_routed": 0}

    @pytest.mark.parametrize("dp", [1, 2])
    def test_fault_free_run_never_alerts_on_restarts(self, runs, dp):
        # the router's default-on history and rule set saw the whole
        # stream, and nothing restarted
        assert runs[dp]["evaluations"] > 0
        assert "restart_churn" not in runs[dp]["transitioned"]

    @pytest.mark.parametrize("dp,replica,want", [
        (1, 0, {"admitted": 8, "hit": 60, "computed": 86}),
        (2, 0, {"admitted": 4, "hit": 48, "computed": 57}),
        (2, 1, {"admitted": 4, "hit": 48, "computed": 57})])
    def test_replica_counts(self, runs, dp, replica, want):
        row = runs[dp]["replicas"][replica]
        assert {k: row[k] for k in want} == want
        assert row["preempted"] and row["bounded"]

    def test_no_replica_under_the_dp1_ratio(self, runs):
        base = runs[1]["replicas"][0]
        for row in runs[2]["replicas"]:
            # hit/(hit+computed) >= base's, without the division
            assert row["hit"] * (base["hit"] + base["computed"]) \
                >= base["hit"] * (row["hit"] + row["computed"])


# --- affinity routing --------------------------------------------------------

class TestAffinityRouting:
    def test_same_prefix_concentrates_distinct_prefixes_spread(self):
        fleet = _fleet(2)
        try:
            # one shared prefix -> ONE replica, all affinity hits
            shared = _prompts(n=4, seed=3)
            handles = [fleet.submit_request(
                p, SamplingParams(max_new_tokens=2)) for p in shared]
            fleet.wait(handles, timeout=120)
            owners = {h.replica.index for h in handles}
            assert len(owners) == 1, \
                f"shared-prefix requests split across replicas: {owners}"
            assert fleet.routing_counts == {
                "affinity_hit": len(shared), "fallback_routed": 0}
            # distinct prefixes -> both replicas see traffic
            distinct = [_prompts(n=1, seed=100 + i)[0] for i in range(12)]
            handles = [fleet.submit_request(
                p, SamplingParams(max_new_tokens=2)) for p in distinct]
            fleet.wait(handles, timeout=120)
            spread = {h.replica.index for h in handles}
            assert spread == {0, 1}, \
                f"distinct prefixes did not spread: {spread}"
        finally:
            fleet.shutdown(drain_timeout=2.0)

    def test_short_prompt_routes_least_loaded(self):
        """A prompt under one full block has no affinity key: it routes
        least-loaded and counts as fallback."""
        fleet = _fleet(2)
        try:
            h = fleet.submit_request([7, 9], SamplingParams(max_new_tokens=2))
            fleet.wait([h], timeout=60)
            assert h.prefix_hashes is None
            assert fleet.routing_counts["fallback_routed"] == 1
        finally:
            fleet.shutdown(drain_timeout=2.0)

    def test_saturated_affinity_target_falls_back(self):
        """When the affinity replica is at its admission cap, the
        request lands on the least-loaded eligible replica instead of
        being rejected; FleetSaturated only when EVERYONE is full."""
        fleet = _fleet(2, max_queue=2)
        try:
            target_prompt = _prompt_targeting(fleet, 0)
            # fill replica 0's cap with slow requests
            slow = [fleet.submit_request(
                target_prompt, SamplingParams(max_new_tokens=400),
                request_id=f"slow-{i}") for i in range(2)]
            assert {h.replica.index for h in slow} == {0}
            # affinity target saturated: same prefix now falls back to 1
            h = fleet.submit_request(
                target_prompt, SamplingParams(max_new_tokens=2),
                request_id="fallback")
            assert h.replica.index == 1
            assert fleet.routing_counts["fallback_routed"] >= 1
            # fill replica 1 too: now the whole fleet rejects
            h2 = fleet.submit_request(
                target_prompt, SamplingParams(max_new_tokens=400),
                request_id="fill-1")
            assert h2.replica.index == 1
            with pytest.raises(FleetSaturated):
                fleet.submit_request(
                    target_prompt, SamplingParams(max_new_tokens=2),
                    request_id="reject")
        finally:
            fleet.shutdown(drain_timeout=0.2)


# --- abort through the owning replica (satellite bugfix) ---------------------

class TestOwningReplicaAbort:
    def test_abort_reaches_owner_and_frees_its_pool(self):
        fleet = _fleet(2)
        try:
            h = fleet.submit_request(
                _prompts(n=1, seed=11)[0],
                SamplingParams(max_new_tokens=100000))
            owner = h.replica
            other = fleet.replicas[1 - owner.index]
            # wait until the request actually holds blocks on its owner
            deadline = time.monotonic() + 60
            while (owner.engine.kv.occupancy() == 0.0
                   and time.monotonic() < deadline):
                time.sleep(0.005)
            assert owner.engine.kv.occupancy() > 0.0
            assert fleet._owner[h.rid] is owner  # request→replica map
            assert fleet.abort(h.rid)            # routed via that map
            fleet.wait([h], timeout=60)
            assert h.finish_reason == "abort"
            # the OWNING replica's pool returns to zero occupancy
            deadline = time.monotonic() + 60
            while (owner.engine.kv.occupancy() != 0.0
                   and time.monotonic() < deadline):
                time.sleep(0.005)
            assert owner.engine.kv.occupancy() == 0.0
            assert other.engine.kv.occupancy() == 0.0  # never touched
            # evicted on finish: a second abort has nowhere to route
            deadline = time.monotonic() + 60
            while h.rid in fleet._owner and time.monotonic() < deadline:
                time.sleep(0.005)
            assert fleet.abort(h.rid) is False
        finally:
            fleet.shutdown(drain_timeout=1.0)


# --- replica death failover --------------------------------------------------

def _kill_replica(fleet, index):
    """Crash replica ``index``'s engine thread by poisoning the loop's step and
    feeding it work routed to it; waits for the thread to die."""
    replica = fleet.replicas[index]

    def boom():
        raise RuntimeError(f"induced crash on replica {index}")

    replica.engine.step_ahead = boom
    prompt = _prompt_targeting(fleet, index)
    h = fleet.submit_request(prompt, SamplingParams(max_new_tokens=4))
    assert h.replica is replica
    fleet.wait([h], timeout=60)
    assert h.finish_reason == "abort" and h.output_tokens == []
    deadline = time.monotonic() + 30
    while replica.alive and time.monotonic() < deadline:
        time.sleep(0.005)
    assert not replica.alive
    assert f"replica {index}" in replica.error
    return prompt


class TestReplicaDeathFailover:
    def test_fleet_serves_on_with_one_replica_dead(self):
        fleet = _fleet(2)
        try:
            dead_prompt = _kill_replica(fleet, 0)
            assert fleet.alive
            # traffic whose affinity was the dead replica fails over
            h = fleet.submit_request(dead_prompt,
                                     SamplingParams(max_new_tokens=4))
            assert h.replica.index == 1
            fleet.wait([h], timeout=120)
            assert h.finish_reason == "length"
            assert len(h.output_tokens) == 4
            # the exclusion is visible on /metrics
            fleet.sample_gauges()
            text = fleet.registry.prometheus_text()
            assert 'serving_fleet_replica_alive{replica="0"} 0' in text
            assert 'serving_fleet_replica_alive{replica="1"} 1' in text
            assert "serving_fleet_replicas_alive 1" in text
            # whole fleet down only when the LAST replica dies
            _kill_replica(fleet, 1)
            assert not fleet.alive
            with pytest.raises(FleetDown):
                fleet.submit_request([1, 2, 3, 4, 5],
                                     SamplingParams(max_new_tokens=2))
        finally:
            fleet.shutdown(drain_timeout=0.5)


# --- fleet drain -------------------------------------------------------------

class TestFleetDrain:
    def test_drain_aborts_stragglers_and_empties_every_pool(self):
        fleet = _fleet(2)
        try:
            # long-running work on (very likely) both replicas
            handles = [fleet.submit_request(
                _prompts(n=1, seed=40 + i)[0],
                SamplingParams(max_new_tokens=100000),
                request_id=f"long-{i}") for i in range(6)]
            busy = {h.replica.index for h in handles}
            fleet.shutdown(drain_timeout=0.3)
            for h in handles:
                assert h.finished
                assert h.finish_reason == "timeout"  # drain-deadline abort
            for r in fleet.replicas:
                assert not r.alive  # engine threads exited
                assert r.engine.kv.occupancy() == 0.0, \
                    f"replica {r.index} left blocks after drain"
                assert (r.engine.kv.num_available
                        == r.engine.kv.num_blocks - 1)
            assert busy  # sanity: the drain actually had work to abort
            with pytest.raises(FleetDown):
                fleet.submit_request([1, 2, 3, 4, 5])
        finally:
            fleet.shutdown(drain_timeout=0.1)  # idempotent


# --- HTTP frontend over a dp=2 fleet ----------------------------------------

def _request(port, method, path, body=None, timeout=120):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    payload = None if body is None else json.dumps(body)
    conn.request(method, path, payload,
                 {"Content-Type": "application/json"} if payload else {})
    resp = conn.getresponse()
    data = resp.read()
    status, headers = resp.status, dict(resp.getheaders())
    conn.close()
    return status, headers, data


class Harness:
    """A live CompletionServer on an asyncio loop in a daemon thread."""

    def __init__(self, fleet, cfg=None):
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self.loop.run_forever,
                                       daemon=True)
        self.thread.start()
        self.server = CompletionServer(fleet, cfg or ServerConfig())
        self.run(self.server.start())
        self.port = self.server.port

    def run(self, coro, timeout=120):
        return asyncio.run_coroutine_threadsafe(
            coro, self.loop).result(timeout)

    def close(self):
        try:
            self.run(self.server.shutdown(drain_timeout=1.0), timeout=60)
        finally:
            self.loop.call_soon_threadsafe(self.loop.stop)
            self.thread.join(10)
            self.loop.close()


@pytest.fixture
def dp2_harness():
    fleet = _fleet(2)
    h = Harness(fleet)
    try:
        yield h, fleet
    finally:
        h.close()


class TestHTTPFleet:
    def test_readyz_reports_fleet_shape_and_metrics_labels(self, dp2_harness):
        h, fleet = dp2_harness
        status, _, data = _request(h.port, "GET", "/readyz")
        assert status == 200
        assert data == b"ok dp=2 mp=1\n"
        status, _, data = _request(
            h.port, "POST", "/v1/completions",
            {"prompt": _prompts(n=1, seed=5)[0], "max_tokens": 3})
        assert status == 200
        assert len(json.loads(data)["choices"][0]["token_ids"]) == 3
        status, _, page = _request(h.port, "GET", "/metrics")
        assert status == 200
        text = page.decode()
        # per-replica-labeled serving series + the fleet family
        assert 'replica="0"' in text and 'replica="1"' in text
        assert "serving_fleet_replicas 2" in text
        assert "serving_fleet_affinity_hit_total" in text
        assert "serving_fleet_fallback_routed_total" in text
        assert "serving_fleet_replica_occupancy" in text
        assert "serving_fleet_replica_queue_depth" in text

    def test_timeout_abort_frees_owning_replica_over_http(self, dp2_harness):
        """A deadline abort must traverse router→owning replica: the
        response comes back with finish_reason=timeout (it would hang
        forever if the abort were mis-routed) and every replica's pool
        is empty right after."""
        h, fleet = dp2_harness
        t0 = time.monotonic()
        status, _, data = _request(
            h.port, "POST", "/v1/completions",
            {"prompt": _prompts(n=1, seed=6)[0], "max_tokens": 60000,
             "timeout": 0.4})
        assert status == 200
        choice = json.loads(data)["choices"][0]
        assert choice["finish_reason"] == "timeout"
        assert time.monotonic() - t0 < 60
        deadline = time.monotonic() + 30
        while (any(r.engine.kv.occupancy() for r in fleet.replicas)
               and time.monotonic() < deadline):
            time.sleep(0.01)
        for r in fleet.replicas:
            assert r.engine.kv.occupancy() == 0.0

    def test_replica_death_failover_503_only_when_all_die(self,
                                                          dp2_harness):
        h, fleet = dp2_harness
        _kill_replica(fleet, 0)
        assert _request(h.port, "GET", "/readyz")[0] == 200  # still up
        status, _, data = _request(
            h.port, "POST", "/v1/completions",
            {"prompt": _prompts(n=1, seed=8)[0], "max_tokens": 2})
        assert status == 200
        assert (json.loads(data)["choices"][0]["finish_reason"]
                == "length")
        _kill_replica(fleet, 1)
        assert _request(h.port, "GET", "/readyz")[0] == 503
        status, _, data = _request(
            h.port, "POST", "/v1/completions",
            {"prompt": _prompts(n=1, seed=9)[0], "max_tokens": 2})
        assert status == 503
        assert (json.loads(data)["error"]["message"]
                == "engine is not running")


# --- the stream hand-off across replicas (ISSUE 32) --------------------------

class _Req:
    """What a handler reads of an engine request."""

    def __init__(self, tokens=0, finished=False):
        self.output_tokens = list(range(tokens))
        self.finished = finished
        self.finish_reason = None


def _unstarted_server():
    """A CompletionServer on a dp=2 fleet whose engine threads never
    start, with a loop of its own that the test turns or runs."""
    fleet = FleetRouter.build(_factory(), dp=2)
    server = CompletionServer(fleet)
    loop = server._loop = asyncio.new_event_loop()
    return server, fleet, loop


def _open_handle(server, rid, replica, req=None, done=False):
    from paddle_tpu.serving.protocol import parse_completion_request
    from paddle_tpu.serving.server import _Handle

    h = _Handle(rid, parse_completion_request(b'{"prompt": [1, 2, 3]}'),
                asyncio.Event())
    h.replica, h.req, h.done = replica, req, done
    server._handles[rid] = h
    return h


class TestStreamWakeScope:
    """``CompletionServer._notify`` on a dp=2 fleet whose loop the test
    turns by hand: which events one replica's wake sets, what ``None``
    sets, what coalesces, what a closed loop swallows."""

    @pytest.fixture
    def wired(self):
        server, fleet, loop = _unstarted_server()

        def handle(rid, replica, req=None, done=False):
            return _open_handle(server, rid, fleet.replicas[replica],
                                req, done)

        def turn():
            """Run what has been posted to the loop, once."""
            loop.call_soon(loop.stop)
            loop.run_forever()

        def series(replica):
            c = server.registry.counter
            lb = {} if replica is None else {"replica": str(replica)}
            return tuple(int(c(name, **lb).value) for name in (
                "serving_stream_wakes_total",
                "serving_stream_wakes_coalesced_total",
                "serving_stream_handles_woken_total"))

        yield server, fleet, handle, turn, series
        if not loop.is_closed():
            loop.close()
        fleet.stop()

    def test_a_replica_wakes_its_own_handles_with_news(self, wired):
        server, fleet, handle, turn, series = wired
        token = handle("a", 0, _Req(tokens=1))
        ended = handle("b", 0, _Req(tokens=0, finished=True))
        gone = handle("c", 0, None, done=True)
        queued = handle("d", 0, None)
        silent = handle("e", 0, _Req(tokens=0))
        other = handle("f", 1, _Req(tokens=3, finished=True))
        server._notify(fleet.replicas[0])
        assert not token.event.is_set()       # nothing on this thread
        turn()
        assert [h.event.is_set() for h in (token, ended, gone)] == [True] * 3
        assert not queued.event.is_set() and not silent.event.is_set()
        assert not other.event.is_set()       # replica 1's, with news
        assert series(0) == (1, 0, 3) and series(1) == (0, 0, 0)
        # the same state again is no news; one more token is
        for h in (token, ended, gone):
            h.event.clear()
        silent.req.output_tokens.append(7)
        server._notify(fleet.replicas[0])
        turn()
        assert silent.event.is_set()
        assert not any(h.event.is_set() for h in (token, ended, gone,
                                                  queued, other))
        assert series(0) == (2, 0, 4)

    def test_none_wakes_every_handle(self, wired):
        server, fleet, handle, turn, series = wired
        hs = [handle("a", 0, _Req(tokens=0)), handle("b", 1, None),
              handle("c", 1, _Req(tokens=2))]
        server._notify(None)
        turn()
        assert all(h.event.is_set() for h in hs)
        assert series(None) == (1, 0, 3)
        assert series(0) == series(1) == (0, 0, 0)

    def test_notifies_coalesce_until_the_walk_starts(self, wired):
        server, fleet, handle, turn, series = wired
        h0 = handle("a", 0, _Req(tokens=1))
        h1 = handle("b", 1, _Req(tokens=1))
        r0, r1 = fleet.replicas
        for _ in range(3):
            server._notify(r0)
        server._notify(r1)                    # a mark each replica
        assert series(0) == (1, 2, 0) and series(1) == (1, 0, 0)
        # a step that ends DURING the walk posts anew: the mark is
        # cleared before the first handle is looked at
        real_set = h0.event.set

        def set_and_step():
            real_set()
            h0.req.output_tokens.append(9)
            server._notify(r0)

        h0.event.set = set_and_step
        turn()
        assert h0.event.is_set() and h1.event.is_set()
        assert series(0) == (2, 2, 1)
        h0.event.set = real_set
        h0.event.clear()
        turn()                                # the second callback
        assert h0.event.is_set() and series(0) == (2, 2, 2)

    def test_a_closed_loop_swallows_the_notify(self, wired):
        server, fleet, handle, turn, series = wired
        h = handle("a", 0, _Req(tokens=1))

        def closing(*a, **kw):                # closed under the caller
            raise RuntimeError("Event loop is closed")

        real, server._loop.call_soon_threadsafe = \
            server._loop.call_soon_threadsafe, closing
        server._notify(fleet.replicas[0])
        server._notify(None)
        server._loop.call_soon_threadsafe = real
        assert series(0) == (0, 0, 0) and series(None) == (0, 0, 0)
        server._notify(fleet.replicas[0])     # no mark was left behind
        turn()
        assert h.event.is_set() and series(0) == (1, 0, 1)
        server._loop.close()
        h.event.clear()
        server._notify(fleet.replicas[0])
        server._notify(None)
        assert not h.event.is_set() and series(0) == (1, 0, 1)


class TestStreamWakeStress:
    def test_no_news_is_lost_between_notifying_threads_and_the_loop(
            self, monkeypatch):
        """The pending mark is shared by the notifying threads and the
        loop thread with no lock.  Two threads a replica (a replica and
        the incarnation that replaces it) append tokens and notify as
        fast as they can, under a switch interval that makes every
        interleaving likely; with the handlers' poll at 30 s each of
        them must still read every token: a wake lost between the mark
        and the walk would leave its handler asleep."""
        import sys

        from paddle_tpu.serving import server as server_mod

        monkeypatch.setattr(server_mod, "_POLL_S", 30.0)
        server, fleet, loop = _unstarted_server()
        thread = threading.Thread(target=loop.run_forever, daemon=True)
        thread.start()
        rounds, per_replica, got = 400, 3, {}

        async def open_handle(rid, replica):
            h = _open_handle(server, rid, replica, _Req())
            got[rid] = []

            async def sink(new):
                got[rid].extend(new)

            return h, loop.create_task(server._collect(h, None, sink))

        def on_loop(coro):
            return asyncio.run_coroutine_threadsafe(coro, loop).result(30)

        opened = [on_loop(open_handle(f"r{r.index}-{i}", r))
                  for r in fleet.replicas for i in range(per_replica)]

        def engine(replica, base):
            mine = [h for h, _ in opened if h.replica is replica]
            for k in range(rounds):
                for h in mine:
                    h.req.output_tokens.append(base + k)
                server._notify(replica)

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=engine, args=(r, base))
                       for r in fleet.replicas for base in (0, 10_000)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
            assert not any(t.is_alive() for t in threads)
            deadline = time.monotonic() + 10
            while (any(len(v) < 2 * rounds for v in got.values())
                   and time.monotonic() < deadline):
                time.sleep(0.005)
        finally:
            sys.setswitchinterval(old)
        try:
            assert {rid: len(v) for rid, v in got.items()} == \
                {rid: 2 * rounds for rid in got}
            for h, _ in opened:
                assert got[h.rid] == h.req.output_tokens  # and in order
                h.req.finished = True
            server._notify(None)

            async def results():
                return await asyncio.wait_for(
                    asyncio.gather(*(task for _, task in opened)), 10)

            ended = on_loop(results())
            assert [len(tokens) for tokens, _ in ended] == \
                [2 * rounds] * len(opened)
            c = server.registry.counter
            for r in fleet.replicas:
                lb = {"replica": str(r.index)}
                assert (c("serving_stream_wakes_total", **lb).value
                        + c("serving_stream_wakes_coalesced_total",
                            **lb).value) == 2 * rounds
        finally:
            loop.call_soon_threadsafe(loop.stop)
            thread.join(10)
            loop.close()
            fleet.stop()


# --- lint coverage -----------------------------------------------------------

class TestFleetLintCoverage:
    def test_fleet_module_in_bounded_metrics_scan(self):
        """ISSUE 6 tooling: serving/fleet.py is pinned in the lint's
        file list (per-replica queues/maps bounded or waived) and scans
        clean."""
        import os
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        sys.path.insert(0, os.path.join(repo, "tools"))
        try:
            import check_bounded_metrics as lint
        finally:
            sys.path.pop(0)
        covered = {os.path.relpath(p, repo) for p in lint.SCAN_FILES}
        assert "paddle_tpu/serving/fleet.py" in covered
        assert lint.scan(dirs=(), files=lint.SCAN_FILES) == []
