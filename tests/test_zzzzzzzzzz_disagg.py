"""Prefill/decode disaggregation (ISSUE 20).

The contract under test: the fleet splits into **prefill-specialist**
and **decode-specialist** replicas joined by a verified KV-cache
hand-off at the first-token boundary — serialized block runs keyed by
the chain hashes, content-digest checked, placed atomically, with the
pool invariant (``free + reuse + held + null == num_blocks``) intact on
BOTH pools across every transfer and ZERO new jit traces (hand-off is
eager host/device work only).  Disaggregated greedy streams must be
token-identical to unified ones; corrupted/truncated block-stream
frames raise TYPED errors and a worker answering them SURVIVES; a
decode-specialist death re-dispatches its recoverable requests to a
same-role (or unified) replica and NEVER to a prefill specialist; and
the hot-prefix migration satellite moves heat-table-hot chains to
their post-reweight ring target so the target serves the prefix from
cache with zero recompute.

(Named ``zzzzzzzzzz`` — 10 z's — to sort after
``test_zzzzzzzzz_burst.py``: the tier-1 suite overruns its timeout, so
new dots must only append.)
"""

import copy
import os
import signal
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed import topology
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (
    CacheRebalancer,
    EngineConfig,
    EngineCore,
    FaultPlan,
    FaultSpec,
    FleetConfig,
    FleetRouter,
    FleetSupervisor,
    HandoffError,
    ProcessFleet,
    ProcessFleetConfig,
    RebalancerConfig,
    SamplingParams,
    SchedulerConfig,
    SupervisorConfig,
    parse_roles,
)
from paddle_tpu.serving import handoff, wire
from paddle_tpu.serving.procfleet import WorkerHandle

BS = 4
_RNG = np.random.default_rng(5)
PREFIX = _RNG.integers(0, 256, 8).tolist()   # 2 full shared blocks
PROMPTS = [PREFIX + _RNG.integers(0, 256, 6).tolist() for _ in range(4)]

SUP = dict(backoff_initial_s=0.02, backoff_max_s=0.5,
           poll_interval_s=0.01)


def _engine(role="unified", layers=2, num_blocks=32, max_num_seqs=4,
            registry=None, labels=None, burst_steps=0):
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=layers))
    return EngineCore(model, config=EngineConfig(
        num_blocks=num_blocks, block_size=BS, role=role,
        burst_steps=burst_steps,
        scheduler=SchedulerConfig(max_num_seqs=max_num_seqs)),
        registry=registry, metrics_labels=labels)


def _pool(engine):
    kv = engine.kv
    return kv.pool if hasattr(kv, "pool") else kv


def _check_invariant(engine):
    pool = _pool(engine)
    free, reuse, held = (len(pool._free), len(pool._reuse),
                         len(pool._ref))
    assert free + reuse + held + 1 == pool.num_blocks, (
        f"pool invariant broken: {free}+{reuse}+{held}+1 "
        f"!= {pool.num_blocks}")


def _traces(engine):
    return tuple(
        (getattr(engine, f"{f}_trace_count"),
         frozenset(getattr(engine, f"{f}_buckets")))
        for f in ("prefill", "decode", "ragged", "burst"))


def _wait(predicate, timeout=60.0, interval=0.01, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {msg}")


# --------------------------------------------------------------------------
# --roles CLI parsing (pure)
# --------------------------------------------------------------------------
class TestParseRoles:
    def test_counts_expand_in_spec_order(self):
        assert parse_roles("prefill:1,decode:2") == \
            ["prefill", "decode", "decode"]
        assert parse_roles("unified:2") == ["unified", "unified"]
        assert parse_roles("decode") == ["decode"]  # count defaults to 1

    def test_rejects_garbage(self):
        with pytest.raises(ValueError):
            parse_roles("draft:2")
        with pytest.raises(ValueError):
            parse_roles("prefill:x")
        with pytest.raises(ValueError):
            parse_roles("")

    def test_procfleet_roles_must_cover_every_index(self):
        # the length check fires in _SharedState.__init__, BEFORE any
        # worker process spawns — a short roles list never boots a fleet
        from paddle_tpu.serving.procfleet import ProcessFleet

        with pytest.raises(ValueError, match="roles"):
            ProcessFleet(ProcessFleetConfig(dp=2, roles=["prefill"]))


# --------------------------------------------------------------------------
# KV-run export/import round trip (two direct engines, no fleet)
# --------------------------------------------------------------------------
class TestRunRoundTrip:
    @pytest.fixture(scope="class")
    def pair(self):
        """A donor engine mid-decode with its exported run, and a
        pristine recipient sharing the deployment shape."""
        topology.set_mesh(None)
        donor = _engine()
        recipient = _engine()
        req = donor.add_request(
            PROMPTS[0], SamplingParams(max_new_tokens=8,
                                       temperature=0.0),
            request_id="d0")
        while not req.output_tokens:
            donor.step()
        before = (_traces(donor), _traces(recipient))
        run = donor.export_kv_run("d0")
        return donor, recipient, run, req, before

    def test_export_is_pure_read(self, pair):
        donor, _, run, req, _ = pair
        assert run is not None
        # the full prompt's hashed blocks travel (14 tokens → 3 full
        # blocks; the partial tail block is never hashed)
        assert len(run["blocks"]) == len(PROMPTS[0]) // BS
        assert run["tokens_total"] == len(run["blocks"]) * BS
        _check_invariant(donor)
        assert donor.kv.has("d0")  # still running here until detach

    def test_import_places_atomically_then_dedups(self, pair):
        donor, recipient, run, _, _ = pair
        placed = recipient.import_kv_run(run)
        assert placed == len(run["blocks"])
        _check_invariant(recipient)
        # idempotent: every block is already cached → zero fresh
        assert recipient.import_kv_run(copy.deepcopy(run)) == 0
        _check_invariant(recipient)

    def test_handoff_adds_zero_traces(self, pair):
        donor, recipient, _, _, before = pair
        assert (_traces(donor), _traces(recipient)) == before, (
            "export/import moved a trace counter or bucket set — "
            "hand-off must stay eager")

    def test_recipient_resumes_token_identical(self, pair):
        donor, recipient, _, req, _ = pair
        resume = [int(t) for t in req.output_tokens]
        donor.run(max_steps=2000)          # donor-side reference
        expected = list(req.output_tokens)
        res = recipient.add_request(
            PROMPTS[0], SamplingParams(max_new_tokens=8,
                                       temperature=0.0),
            request_id="res", resume_tokens=resume)
        recipient.run(max_steps=2000)
        assert list(res.output_tokens) == expected
        # the imported prefix served from cache, zero recompute
        attr = recipient.cachestat.attribution()
        row = [r for r in attr["recent"] + attr["active"]
               if r["id"] == "res"]
        assert row and row[0]["cached_tokens"] >= \
            (len(PROMPTS[0]) // BS) * BS, row
        # the run ships only FULL verified blocks, so the sub-block
        # tail (partial prompt block + resume tokens) re-prefills on
        # the recipient in exactly ONE recompute admission — the full
        # blocks themselves served from cache (asserted above)
        assert row[0]["recomputes"] == 1, row

    def test_corrupt_payload_refused_pool_untouched(self, pair):
        donor, recipient, run, _, _ = pair
        bad = copy.deepcopy(run)
        bad["payload"] = np.array(bad["payload"], copy=True)
        bad["payload"].reshape(-1)[0] += 1  # flip content, keep digest
        pool = _pool(recipient)
        state = (len(pool._free), len(pool._reuse), len(pool._ref))
        with pytest.raises(HandoffError, match="digest"):
            recipient.import_kv_run(bad)
        assert (len(pool._free), len(pool._reuse),
                len(pool._ref)) == state
        _check_invariant(recipient)

    def test_shape_mismatch_refused(self, pair):
        _, recipient, run, _, _ = pair
        for key, val in (("block_size", 8), ("layers", 99),
                         ("dtype", "float64"), ("version", 0)):
            bad = copy.deepcopy(run)
            bad[key] = val
            with pytest.raises(HandoffError):
                recipient.import_kv_run(bad)
        _check_invariant(recipient)


# --------------------------------------------------------------------------
# wire form: typed errors for corrupt / truncated frame streams
# --------------------------------------------------------------------------
class TestWireFrames:
    @pytest.fixture(scope="class")
    def frames(self, request):
        topology.set_mesh(None)
        eng = _engine()
        req = eng.add_request(
            PROMPTS[1], SamplingParams(max_new_tokens=4,
                                       temperature=0.0),
            request_id="w0")
        while not req.output_tokens:
            eng.step()
        run = eng.export_kv_run("w0")
        return run, handoff.run_to_frames(run)

    def test_roundtrip_is_lossless(self, frames):
        run, fr = frames
        back = handoff.run_from_frames(fr[0], fr[1:])
        assert back["digest"] == run["digest"]
        assert back["blocks"] == run["blocks"]
        assert np.array_equal(np.asarray(back["payload"]),
                              np.asarray(run["payload"]))

    def test_truncated_stream_is_typed(self, frames):
        _, fr = frames
        with pytest.raises(wire.FrameError) as e:
            handoff.run_from_frames(fr[0], fr[1:-1])
        assert e.value.kind == "truncated"

    def test_misordered_chunk_is_typed(self, frames):
        _, fr = frames
        if len(fr) < 3:
            pytest.skip("run fits one chunk")
        swapped = [fr[2], fr[1]] + fr[3:]
        with pytest.raises(wire.FrameError) as e:
            handoff.run_from_frames(fr[0], swapped)
        assert e.value.kind == "protocol"

    def test_bad_base64_is_typed(self, frames):
        _, fr = frames
        bad = copy.deepcopy(fr)
        bad[1]["data"] = "!!!not-base64!!!"
        with pytest.raises(wire.FrameError) as e:
            handoff.run_from_frames(bad[0], bad[1:])
        assert e.value.kind == "malformed"

    def test_byte_shortfall_is_typed(self, frames):
        _, fr = frames
        bad = copy.deepcopy(fr)
        bad[0]["bytes"] = int(bad[0]["bytes"]) + 1
        with pytest.raises(wire.FrameError) as e:
            handoff.run_from_frames(bad[0], bad[1:])
        assert e.value.kind == "truncated"

    def test_lying_meta_is_handoff_error(self, frames):
        _, fr = frames
        bad = copy.deepcopy(fr)
        bad[0]["meta"]["shape"] = [1, 2, 3]
        with pytest.raises(HandoffError):
            handoff.run_from_frames(bad[0], bad[1:])


# --------------------------------------------------------------------------
# dp=2 disaggregated fleet: token identity + pool/trace discipline
# --------------------------------------------------------------------------
class TestDisaggIdentity:
    def _run(self, hold_intake, roles, burst=0):
        from paddle_tpu.observability.metrics import MetricsRegistry
        reg = MetricsRegistry()

        def factory(i, registry):
            role = roles[i] if roles else "unified"
            return _engine(role=role, layers=1, registry=registry,
                           labels={"replica": str(i)},
                           burst_steps=0 if role == "prefill" else burst)

        fleet = FleetRouter.build(
            factory, dp=2, config=FleetConfig(roles=roles),
            registry=reg).start()
        try:
            gates = [hold_intake(r) for r in fleet.replicas]
            hs = [fleet.submit_request(
                p, SamplingParams(max_new_tokens=10, temperature=0.0),
                request_id=f"r{i}")
                for i, p in enumerate(PROMPTS)]
            # prefill-only jobs (they finish at their first token and
            # never hand off) on the same prefix, so that the role-less
            # fleet routes them to the replica that decodes PROMPTS
            noise = [fleet.submit_request(
                PREFIX + [200 + i] * 6,
                SamplingParams(max_new_tokens=1, temperature=0.0),
                request_id=f"noise{i}")
                for i in range(4 if burst else 0)]
            gates[0].set()
            if roles:
                # the decode specialist starts on the whole migrated
                # cohort, not on whichever hand-off reached it first
                _wait(lambda: reg.snapshot().get(
                    "serving_handoff_total", {}).get("value") == len(hs),
                    msg="every first token handed off")
            gates[1].set()
            fleet.wait(hs + noise, timeout=300)
            assert all(h.finish_reason == "length" for h in hs + noise)
            for r in fleet.replicas:
                _check_invariant(r.engine)
                for f in ("prefill", "decode", "ragged", "burst"):
                    assert getattr(r.engine, f"{f}_trace_count") == \
                        len(getattr(r.engine, f"{f}_buckets"))
            snap = reg.snapshot()
            hand = snap.get("serving_handoff_total",
                            {}).get("value", 0.0)
            by_replica = {r.index: sum(
                1 for h in hs if h.replica is r)
                for r in fleet.replicas}
            launches = {r.index: {
                k: int(r.engine._burst_counters[k].value)
                for k in ("roundtrips", "launches")}
                for r in fleet.replicas}
            return ([list(h.output_tokens) for h in hs], hand,
                    by_replica, launches)
        finally:
            fleet.shutdown(drain_timeout=5.0)

    @pytest.mark.parametrize("burst", [0, 8])
    def test_disaggregated_matches_unified_greedy(self, hold_intake, burst):
        topology.set_mesh(None)
        uni, uni_hand, _, uni_launches = self._run(hold_intake, None, burst)
        dis, dis_hand, finished_on, launches = self._run(
            hold_intake, ["prefill", "decode"], burst)
        assert uni == dis, "disaggregation changed greedy tokens"
        assert uni_hand == 0.0
        # every request prefilled on replica 0, migrated at its first
        # token — exactly once — and FINISHED on the decode specialist
        assert dis_hand == float(len(PROMPTS))
        assert finished_on == {0: 0, 1: len(PROMPTS)}
        if not burst:
            assert launches[1]["launches"] == 0
            return
        # bursts belong to the decode specialist, which never sees a
        # prefill: it emits its 36 tokens (all but each request's
        # first) in 11 host round-trips, one of them an 8-step burst.
        # The role-less replica that took the same stream keeps the
        # four prefill-only jobs waiting behind its four decoders, a
        # waiting queue gates bursts off, and its 44 tokens cost 20.
        assert launches[0]["launches"] == 0
        emitted = sum(len(t) for t in dis) - len(PROMPTS)
        assert (launches[1], emitted) == (
            {"roundtrips": 11, "launches": 1}, 36)
        owner = uni_launches[max(
            uni_launches, key=lambda i: uni_launches[i]["roundtrips"])]
        served = sum(len(t) for t in uni) + 4  # + one token a noise job
        assert (owner, served) == ({"roundtrips": 20, "launches": 0}, 44)
        # fewer round-trips per token, without the division
        assert launches[1]["roundtrips"] * served \
            < owner["roundtrips"] * emitted


# --------------------------------------------------------------------------
# role-aware supervisor re-dispatch (the ISSUE 20 bugfix)
# --------------------------------------------------------------------------
class TestRoleAwareRedispatch:
    def test_decode_death_never_lands_on_prefill_specialist(self):
        """Kill the decode specialist mid-decode at dp=2
        (prefill:1,decode:1): the recovered request must WAIT for the
        restarted decode replica — the prefill specialist is never
        eligible for a mid-decode resume — and finish token-identical
        with exactly one re-dispatch."""
        topology.set_mesh(None)
        # fault-free greedy reference from one direct engine
        ref_eng = _engine(layers=1)
        ref = ref_eng.add_request(
            PROMPTS[0], SamplingParams(max_new_tokens=16,
                                       temperature=0.0))
        ref_eng.run(max_steps=2000)
        expected = list(ref.output_tokens)

        plan = FaultPlan(faults=(
            FaultSpec(point="engine_step_raise", step=6, replica="1"),))

        def factory(i, registry):
            return _engine(role=("prefill", "decode")[i], layers=1,
                           registry=registry,
                           labels={"replica": str(i)})

        fleet = FleetRouter.build(
            factory, dp=2,
            config=FleetConfig(roles=["prefill", "decode"],
                               fault_plan=plan))
        sup = FleetSupervisor(fleet, config=SupervisorConfig(**SUP))
        sup.start()
        fleet.start()
        try:
            h = fleet.submit_request(
                PROMPTS[0], SamplingParams(max_new_tokens=16,
                                           temperature=0.0),
                request_id="long", retryable=True)
            fleet.wait([h], timeout=300)
            assert h.finish_reason == "length"
            assert list(h.output_tokens) == expected, \
                "re-dispatch resume broke greedy identity"
            # finished on the RESTARTED decode specialist, not the
            # surviving prefill one
            assert h.replica.index == 1
            assert h.replica.role == "decode"
            assert int(sup._redis_c.value) == 1
            assert int(sup._failed_c.value) == 0
        finally:
            fleet.shutdown(drain_timeout=2.0)


# --------------------------------------------------------------------------
# hot-prefix migration satellite
# --------------------------------------------------------------------------
class TestHotPrefixMigration:
    def test_reweighted_target_serves_migrated_prefix_zero_recompute(
            self):
        from paddle_tpu.observability.metrics import MetricsRegistry
        topology.set_mesh(None)
        reg = MetricsRegistry()

        def factory(i, registry):
            return _engine(layers=1, num_blocks=64, registry=registry,
                           labels={"replica": str(i)})

        fleet = FleetRouter.build(factory, dp=2, config=FleetConfig(),
                                  registry=reg).start()
        reb = CacheRebalancer(fleet, config=RebalancerConfig(
            migrate_top_k=4, migrate_max_blocks=16))
        hot = list(range(40, 60))          # 5 full blocks
        try:
            def run(prompt, rid):
                h = fleet.submit_request(
                    prompt, SamplingParams(max_new_tokens=4,
                                           temperature=0.0),
                    request_id=rid)
                fleet.wait([h], timeout=120)
                assert h.finish_reason == "length"
                return h

            donor_ix = fleet.predict_replica(hot + [7, 8])
            for k in range(3):             # heat the prefix
                run(hot + [100 + k], f"warm{k}")
            donor = fleet.replicas[donor_ix]
            rows = []
            donor.post(lambda: rows.append(
                donor.engine.hot_prefixes(4)))
            fleet._notify(None)
            _wait(lambda: rows, msg="hot_prefixes sweep")
            assert any(r["depth"] >= 5 for r in rows[0]), rows

            other = 1 - donor_ix
            fleet.reweight_ring({donor_ix: 0.25, other: 4.0})
            assert fleet.predict_replica(hot + [7, 8]) == other

            reb._migrate_hot_prefixes()
            fleet._notify(None)
            _wait(lambda: reg.snapshot().get(
                "serving_fleet_prefix_migrations_total",
                {}).get("value", 0.0) > 0, msg="prefix migration")

            h = run(hot + [7, 8], "probe")
            assert h.replica is fleet.replicas[other]
            attr = fleet.replicas[other].engine.cachestat.attribution()
            row = [r for r in attr["recent"] + attr["active"]
                   if r["id"] == "probe"]
            assert row and row[0]["cached_tokens"] == 5 * BS, row
            assert row[0]["recomputes"] == 0, row
            for r in fleet.replicas:
                _check_invariant(r.engine)
        finally:
            reb.close()
            fleet.shutdown(drain_timeout=2.0)


# --------------------------------------------------------------------------
# mp=2: the hand-off payload is the GLOBAL (unsharded) KV
# --------------------------------------------------------------------------
@pytest.mark.slow
class TestMp2Handoff:
    def test_token_identity_and_zero_recompute_at_mp2(self):
        topology.init_mesh(mp=2)
        try:
            donor = _engine()
            req = donor.add_request(
                PROMPTS[2], SamplingParams(max_new_tokens=10,
                                           temperature=0.0),
                request_id="ref")
            while len(req.output_tokens) < 3:
                donor.step()
            run = donor.export_kv_run("ref")
            assert run and run["blocks"]
            resume = [int(t) for t in req.output_tokens]
            donor.run(max_steps=2000)
            expected = list(req.output_tokens)

            recipient = _engine()
            assert recipient.import_kv_run(run) == len(run["blocks"])
            res = recipient.add_request(
                PROMPTS[2], SamplingParams(max_new_tokens=10,
                                           temperature=0.0),
                request_id="res", resume_tokens=resume)
            recipient.run(max_steps=2000)
            assert list(res.output_tokens) == expected
            attr = recipient.cachestat.attribution()
            row = [r for r in attr["recent"] + attr["active"]
                   if r["id"] == "res"]
            assert row and row[0]["cached_tokens"] > 0
            # one recompute admission for the sub-block tail (the run
            # ships full blocks only) — the prefix itself came cached
            assert row[0]["recomputes"] == 1
            _check_invariant(donor)
            _check_invariant(recipient)
        finally:
            topology.set_mesh(None)


# --------------------------------------------------------------------------
# cross-process: worker survives hostile block streams; kill -9 chaos
# --------------------------------------------------------------------------
_SPEC = {
    "layers": 2, "num_blocks": 32, "block_size": BS, "max_num_seqs": 4,
    "max_prefill_tokens_per_step": 8, "unified_step": False, "seed": 0,
    "audit_enabled": False, "audit_sample_every": 1,
    "lifecycle_events": False, "history": False,
}


@pytest.mark.slow
class TestWorkerBlockStreamRobustness:
    @pytest.fixture(scope="class")
    def worker(self):
        wh = WorkerHandle.spawn(
            ProcessFleetConfig(dp=1, **{k: v for k, v in _SPEC.items()
                                        if k in ("layers", "num_blocks",
                                                 "block_size",
                                                 "max_num_seqs")}),
            0, _SPEC)
        try:
            yield wh
        finally:
            wh.stop()

    @pytest.fixture(scope="class")
    def frames(self):
        topology.set_mesh(None)
        eng = _engine()                    # same deployment shape
        req = eng.add_request(
            PROMPTS[3], SamplingParams(max_new_tokens=4,
                                       temperature=0.0),
            request_id="p0")
        while not req.output_tokens:
            eng.step()
        return handoff.run_to_frames(eng.export_kv_run("p0"))

    def _conn(self, worker):
        conn = wire.connect("127.0.0.1", worker.port, role="control",
                            aot_hash=None)
        conn.settimeout(20)
        return conn

    def _healthy(self, worker):
        assert worker.alive, "worker died on a hostile block stream"
        conn = self._conn(worker)
        try:
            assert conn.request({"type": "health"})["type"] == \
                "health_ok"
        finally:
            conn.close()

    def test_corrupt_digest_answered_typed_worker_survives(
            self, worker, frames):
        bad = copy.deepcopy(frames)
        bad[0]["digest"] = "00" * 32
        conn = self._conn(worker)
        try:
            for fr in bad:
                conn.send(fr)
            reply = conn.recv()
            assert reply["type"] == "error"
            assert reply["code"] == "malformed"
        finally:
            conn.close()
        self._healthy(worker)

    def test_bad_chunk_answered_typed_worker_survives(
            self, worker, frames):
        bad = copy.deepcopy(frames)
        bad[1]["data"] = "!!!not-base64!!!"
        conn = self._conn(worker)
        try:
            for fr in bad:
                conn.send(fr)
            reply = conn.recv()
            assert reply["type"] == "error"
            assert reply["code"] == "malformed"
        finally:
            conn.close()
        self._healthy(worker)

    def test_valid_run_places_after_the_hostile_ones(
            self, worker, frames):
        conn = self._conn(worker)
        try:
            for fr in frames:
                conn.send(fr)
            reply = conn.recv()
            assert reply["type"] == "kv_import_ok"
            assert reply["placed"] == len(frames[0]["blocks"])
        finally:
            conn.close()
        self._healthy(worker)


@pytest.mark.slow
class TestProcDisaggChaos:
    def _run(self, roles, kill):
        pf = ProcessFleet(ProcessFleetConfig(
            dp=2, layers=1, num_blocks=48, block_size=BS,
            max_num_seqs=4, roles=roles,
            heartbeat_interval_s=0.1, heartbeat_timeout_s=1.0))
        pf.supervise(SupervisorConfig(**SUP))
        pf.start()
        router = pf.router
        try:
            hs = [router.submit_request(
                p, SamplingParams(max_new_tokens=12, temperature=0.0),
                request_id=f"r{i}", retryable=True)
                for i, p in enumerate(PROMPTS)]
            if kill:
                # strike AFTER the first hand-off landed work on the
                # decode specialist, so the death really strands a
                # mid-decode (and possibly mid-hand-off) stream
                _wait(lambda: router.registry.snapshot().get(
                    "serving_handoff_total", {}).get("value", 0.0) > 0,
                    timeout=120, msg="first hand-off")
                os.kill(pf.worker_pid(1), signal.SIGKILL)
            router.wait(hs, timeout=300)
            lost = [h.rid for h in hs if h.finish_reason != "length"]
            assert not lost, f"requests lost under chaos: {lost}"
            return [list(h.output_tokens) for h in hs]
        finally:
            pf.stop()

    def test_kill9_decode_specialist_zero_loss_token_identity(self):
        clean = self._run(None, kill=False)
        chaos = self._run(["prefill", "decode"], kill=True)
        assert clean == chaos, \
            "kill -9 mid-hand-off broke greedy token identity"
