"""Cross-process serving fleet (ISSUE 16).

The contract under test: the in-process fleet's router/supervisor run
UNCHANGED over process-isolated replicas — ``WorkerEngineProxy`` objects
speaking the length-prefixed wire protocol to ``python -m
paddle_tpu.serving.worker`` processes booted off ONE shared AOT
artifact.  The PR 11/12 chaos guarantees must transfer verbatim:
``kill -9`` a worker mid-stream → reroute, respawn onto the shared
artifact, ZERO lost requests, greedy token identity with the fault-free
run, exactly one ``engine_death`` flight trigger — plus the new actuator
layer (SLO-driven autoscaling, cache-aware ring reweighting) and the
wire-robustness surface (malformed/truncated/oversized frames and
handshake mismatches are connection-scoped, never process-fatal).

(Named ``zzzzzz`` to sort after ``test_zzzzz_aot.py`` — the tier-1
suite overruns its timeout, so new dots must only append.)
"""

import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability.alerts import AlertRule, AlertRuleSet
from paddle_tpu.serving import (
    AotArtifact,
    AutoscalerConfig,
    CacheRebalancer,
    EngineConfig,
    EngineCore,
    FaultPlan,
    FaultSpec,
    FleetConfig,
    FleetRouter,
    ProcessFleet,
    ProcessFleetConfig,
    RebalancerConfig,
    SamplingParams,
    ScaleDecider,
    SchedulerConfig,
    SupervisorConfig,
)
from paddle_tpu.serving import wire
from paddle_tpu.serving.fleet import FleetDown, _build_ring
from paddle_tpu.serving.procfleet import WorkerHandle

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the worker engine shape every test shares (and the AOT artifact is
# saved with): small enough to boot fast, big enough to chunk prefills
POOL = dict(num_blocks=32, block_size=4)
SCHED = dict(max_num_seqs=4, max_prefill_tokens_per_step=8)

_RNG = np.random.default_rng(0)
PREFIX = _RNG.integers(0, 256, 8).tolist()   # 2 full blocks shared
PROMPTS = [PREFIX + _RNG.integers(0, 256, 4).tolist() for _ in range(6)]

SUP = dict(backoff_initial_s=0.02, backoff_max_s=0.5, poll_interval_s=0.01)


@pytest.fixture(scope="module")
def aot_dir(tmp_path_factory):
    """ONE artifact on disk, shared by every worker boot AND respawn."""
    path = str(tmp_path_factory.mktemp("procfleet") / "aot")
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=2))
    eng = EngineCore(model, config=EngineConfig(
        **POOL, scheduler=SchedulerConfig(**SCHED)))
    art = AotArtifact.save(eng, path, max_seq_len=32)
    assert art.program_count > 0
    return path


def _cfg(aot_dir, dp=2, **kw):
    kw.setdefault("heartbeat_interval_s", 0.1)
    kw.setdefault("heartbeat_timeout_s", 1.0)
    return ProcessFleetConfig(
        dp=dp, layers=2, num_blocks=POOL["num_blocks"],
        block_size=POOL["block_size"],
        max_num_seqs=SCHED["max_num_seqs"],
        max_prefill_tokens_per_step=SCHED["max_prefill_tokens_per_step"],
        aot_path=aot_dir, **kw)


def _csum(registry, name, **match) -> float:
    total = 0.0
    for row in wire.dump_registry(registry):
        if row["name"] != name:
            continue
        lbls = dict(row["labels"])
        if all(lbls.get(k) == v for k, v in match.items()):
            total += row.get("value", 0.0)
    return total


def _stream(router, prompts, max_new=12, prefix="r", **kw):
    return [router.submit_request(
        p, SamplingParams(max_new_tokens=max_new),
        request_id=f"{prefix}{i}", retryable=True, **kw)
        for i, p in enumerate(prompts)]


# --- pure actuator cores (no processes) -------------------------------------

class TestScaleDecider:
    def test_decision_sequence_bounds_and_replay(self):
        cfg = AutoscalerConfig(min_replicas=1, max_replicas=2,
                               cooldown_samples=2, calm_samples=3)
        inputs = [(0, ()), (1, ("goodput_burn",)),
                  (2, ("goodput_burn",)), (3, ("goodput_burn",)),
                  (4, ()), (5, ()), (6, ()), (7, ())]
        d = ScaleDecider(cfg, start_replicas=1, min_replicas=1,
                         max_replicas=2)
        live = [d.decide(i, f) for i, f in inputs]
        # up on first breach; pinned at max through the rest of the
        # incident; down only after calm_samples firing-free samples
        assert live == [None, "up", None, None, None, None, "down", None]
        assert [x["direction"] for x in d.decisions] == ["up", "down"]
        # replay determinism: a fresh decider over the same inputs
        # reproduces the sequence exactly
        d2 = ScaleDecider(cfg, 1, 1, 2)
        assert [d2.decide(i, f) for i, f in inputs] == live

    def test_never_scales_past_bounds(self):
        cfg = AutoscalerConfig(min_replicas=1, max_replicas=2,
                               cooldown_samples=1, calm_samples=1)
        d = ScaleDecider(cfg, start_replicas=2, min_replicas=1,
                         max_replicas=2)
        assert d.decide(0, ("pool_exhaustion",)) is None  # at max
        assert d.decide(5, ()) == "down"
        assert d.decide(9, ()) is None                    # at min
        # a rule outside scale_up_rules never scales up
        assert d.decide(12, ("compile_storm",)) is None


class TestRingReweight:
    def test_weighted_ring_moves_vnode_share_only(self):
        base = _build_ring(2, 16)

        def count(ring, i):
            return sum(1 for _, r in ring if r == i)

        assert count(base, 0) == 16 and count(base, 1) == 16
        skew = _build_ring(2, 16, weights={0: 2.0, 1: 0.5})
        assert count(skew, 0) == 32 and count(skew, 1) == 8
        # vnode hashes depend only on (replica, j): the surviving
        # points are IDENTICAL, so reweighting remaps only the
        # added/removed slices — the consistent-hash property
        assert {p for p in skew if p[1] == 1} <= {p for p in base
                                                 if p[1] == 1}
        assert {p for p in base if p[1] == 0} <= {p for p in skew
                                                  if p[1] == 0}
        # even a near-zero weight keeps one vnode: a replica never
        # silently leaves the ring
        assert count(_build_ring(2, 16, weights={1: 0.001}), 1) == 1


def _inproc_engine(i, registry):
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=2))
    return EngineCore(model, config=EngineConfig(
        **POOL, scheduler=SchedulerConfig(**SCHED)),
        registry=registry, metrics_labels={"replica": str(i)})


class TestCacheRebalancer:
    def test_reweights_cold_replica_heavier(self):
        """The actuator closes the PR 12 signal loop: past the
        imbalance threshold the COLD replica (low cached-token ratio)
        gets the heavier vnode weight, so affinity keys migrate toward
        it.  Works over the stock in-process router — the actuator is
        fleet-flavor agnostic."""
        router = FleetRouter.build(_inproc_engine, dp=2)
        try:
            router.start()
            rng = np.random.default_rng(1)
            wave = [rng.integers(0, 256, 12).tolist() for _ in range(12)]
            router.wait(_stream(router, wave, max_new=2, prefix="w"),
                        timeout=120)
            ratios = router.cached_token_ratios()
            assert all(v is not None for v in ratios.values()), \
                f"both replicas must have prefilled: {ratios}"
            # re-run ONE prompt: only its affinity owner gets hits
            router.wait(_stream(router, [wave[0]] * 4, max_new=2,
                                prefix="h"), timeout=120)
            imb = router.cache_imbalance()
            assert imb is not None and imb > 0.01
            reb = CacheRebalancer(router, RebalancerConfig(
                threshold=0.01, min_interval_samples=50))
            try:
                router.history.sample()
                assert reb.last_weights is not None
                ratios = router.cached_token_ratios()
                warm = max(ratios, key=lambda k: ratios[k])
                cold = min(ratios, key=lambda k: ratios[k])
                assert (reb.last_weights[int(cold)]
                        > reb.last_weights[int(warm)])
                assert _csum(router.registry,
                             "serving_fleet_ring_reweights_total") == 1
                # min_interval guard: the next sample must not re-act
                router.history.sample()
                assert _csum(router.registry,
                             "serving_fleet_ring_reweights_total") == 1
                # the reweighted ring still routes
                h = router.submit_request(wave[1], SamplingParams(
                    max_new_tokens=2), request_id="post")
                router.wait([h], timeout=120)
                assert h.finish_reason == "length"
            finally:
                reb.close()
        finally:
            router.stop()


# --- wire-protocol robustness (satellite 4) ---------------------------------

_SPEC_SMALL = {
    "layers": 2, "num_blocks": 16, "block_size": 4, "max_num_seqs": 2,
    "max_prefill_tokens_per_step": 4, "unified_step": False, "seed": 0,
    "audit_enabled": False, "audit_sample_every": 1,
    "lifecycle_events": False, "history": False,
}


class TestWireRobustness:
    @pytest.fixture(scope="class")
    def worker(self):
        wh = WorkerHandle.spawn(
            ProcessFleetConfig(dp=1, **{k: v for k, v in
                                        _SPEC_SMALL.items()
                                        if k in ("layers", "num_blocks",
                                                 "block_size",
                                                 "max_num_seqs")}),
            0, _SPEC_SMALL)
        try:
            yield wh
        finally:
            wh.stop()

    def _raw(self, worker):
        sock = socket.create_connection(("127.0.0.1", worker.port),
                                        timeout=10)
        conn = wire.Connection(sock, side="router")
        conn.settimeout(10)
        return conn

    def _alive_and_serving(self, worker):
        assert worker.alive, "worker process died on a bad connection"
        conn = wire.connect("127.0.0.1", worker.port, role="control",
                            aot_hash=None)
        try:
            assert conn.request({"type": "health"})["type"] == "health_ok"
        finally:
            conn.close()

    def test_version_mismatch_is_connection_scoped(self, worker):
        conn = self._raw(worker)
        try:
            conn.send({"type": "hello", "version": 99, "role": "control",
                       "aot_hash": None})
            reply = conn.recv()
            assert reply["type"] == "error"
            assert reply["code"] == "version_mismatch"
        finally:
            conn.close()
        self._alive_and_serving(worker)

    def test_aot_hash_mismatch_refused_both_sides(self, worker):
        conn = self._raw(worker)
        try:
            conn.send(wire.hello_frame("control", "deadbeef"))
            reply = conn.recv()
            assert reply["type"] == "error"
            assert reply["code"] == "aot_mismatch"
        finally:
            conn.close()
        # the client-side helper surfaces the same refusal as a typed
        # exception (what WorkerEngineProxy.spawn would hit on drift)
        with pytest.raises(wire.HandshakeMismatch) as ei:
            wire.connect("127.0.0.1", worker.port, role="engine",
                         aot_hash="deadbeef")
        assert ei.value.code == "aot_mismatch"
        self._alive_and_serving(worker)

    def test_unknown_role_is_protocol_error(self, worker):
        conn = self._raw(worker)
        try:
            conn.send({"type": "hello", "version": wire.WIRE_VERSION,
                       "role": "root", "aot_hash": None})
            reply = conn.recv()
            assert (reply["type"], reply["code"]) == ("error", "protocol")
        finally:
            conn.close()
        self._alive_and_serving(worker)

    def test_malformed_frames_answered_and_isolated(self, worker):
        for payload in (b"this is not json!", b"[1, 2, 3]"):
            conn = self._raw(worker)
            try:
                conn._sock.sendall(
                    wire._HEADER.pack(len(payload)) + payload)
                reply = conn.recv()
                assert (reply["type"], reply["code"]) == ("error",
                                                          "malformed")
            finally:
                conn.close()
            self._alive_and_serving(worker)

    def test_oversized_frame_refused(self, worker):
        conn = self._raw(worker)
        try:
            conn._sock.sendall(wire._HEADER.pack(wire.MAX_FRAME_BYTES + 1))
            reply = conn.recv()
            assert (reply["type"], reply["code"]) == ("error", "oversized")
        finally:
            conn.close()
        self._alive_and_serving(worker)

    def test_truncated_frame_never_kills_the_process(self, worker):
        conn = self._raw(worker)
        conn._sock.sendall(wire._HEADER.pack(64) + b"only ten b")
        conn.close()  # EOF mid-frame: the kill -9 signature
        time.sleep(0.1)
        self._alive_and_serving(worker)

    def test_wire_errors_are_counted_worker_side(self, worker):
        conn = wire.connect("127.0.0.1", worker.port, role="control",
                            aot_hash=None)
        try:
            reply = conn.request({"type": "debug", "what": "metrics"})
            assert reply["type"] == "debug_ok"
            kinds = {dict(r["labels"]).get("kind")
                     for r in reply["data"]
                     if r["name"] == "serving_wire_errors_total"
                     and r.get("value", 0) > 0}
        finally:
            conn.close()
        assert {"version_mismatch", "aot_mismatch", "malformed",
                "oversized", "truncated"} <= kinds, kinds


# --- the headline cross-process chaos contract ------------------------------

class TestProcessChaos:
    def test_kill9_midstream_zero_loss_token_identity(self, aot_dir):
        """kill -9 replica 0's worker process mid-stream at dp=2 →
        reroute, supervisor respawn onto the SHARED artifact (zero
        traces), zero lost requests, greedy token identity with the
        fault-free run, exactly one engine_death flight trigger."""
        def run(kill):
            pf = ProcessFleet(_cfg(aot_dir))
            pf.supervise(SupervisorConfig(**SUP))
            pf.start()
            router = pf.router
            try:
                hs = _stream(router, PROMPTS)
                victim = victim_pid = None
                if kill:
                    time.sleep(0.15)
                    # the shared prefix is ONE affinity key: a single
                    # replica owns the whole stream — kill that one, so
                    # the death really strands in-flight work
                    victim = next(r.index for r in router.replicas
                                  if r.in_flight)
                    victim_pid = pf.worker_pid(victim)
                    os.kill(victim_pid, signal.SIGKILL)
                router.wait(hs, timeout=300)
                lost = [h.rid for h in hs if h.finish_reason != "length"]
                assert not lost, f"requests lost under chaos: {lost}"
                if kill:
                    deadline = time.monotonic() + 120
                    while time.monotonic() < deadline:
                        if (all(r.healthy for r in router.replicas)
                                and pf.worker_pid(victim) != victim_pid):
                            break
                        time.sleep(0.02)
                    assert all(r.healthy for r in router.replicas), \
                        "fleet did not heal after kill -9"
                    assert pf.worker_pid(victim) != victim_pid
                    desc = pf.proxy(victim).debug_fetch("describe")
                    assert desc is not None, "respawned worker dead"
                    assert sum(desc["traces"].values()) == 0, \
                        f"respawned worker traced: {desc['traces']}"
                    assert desc["aot_hash"] == \
                        pf.shared.aot_handle.model_hash
                tokens = {h.rid: list(h.output_tokens) for h in hs}
                deaths = int(_csum(router.registry,
                                   "serving_flight_dumps_total",
                                   trigger="engine_death"))
                respawns = int(_csum(
                    router.registry,
                    "serving_fleet_worker_respawns_total"))
                return tokens, deaths, respawns
            finally:
                pf.stop()

        clean, clean_deaths, clean_respawns = run(kill=False)
        assert clean_deaths == 0 and clean_respawns == 0
        chaos, deaths, respawns = run(kill=True)
        assert deaths == 1, f"expected exactly one engine_death, {deaths}"
        assert respawns == 1
        mismatched = [rid for rid in clean if chaos[rid] != clean[rid]]
        assert not mismatched, \
            f"token identity broken after kill -9: {mismatched}"

    def test_fault_plan_fires_exactly_once_across_respawn(self, aot_dir):
        """An injected engine_step_raise crosses the wire: the worker
        reports step_error and exits, the supervisor respawns it, and
        the fired-index transfer keeps the plan entry exactly-once —
        a second stream through the healed fleet hits no re-fire."""
        # the shared-prefix stream's ONE affinity key routes every
        # request to replica 1 on the dp=2 ring (deterministic: vnode
        # hashes are sha256 of fixed strings) — target the replica that
        # actually steps, or the fault would never reach its step
        owner = 1
        plan = FaultPlan(faults=(FaultSpec(point="engine_step_raise",
                                           step=6,
                                           replica=str(owner)),))
        pf = ProcessFleet(_cfg(aot_dir, fleet=FleetConfig(
            fault_plan=plan)))
        pf.supervise(SupervisorConfig(**SUP))
        pf.start()
        router = pf.router
        try:
            hs = _stream(router, PROMPTS)
            router.wait(hs, timeout=300)
            assert all(h.finish_reason == "length" for h in hs)
            deadline = time.monotonic() + 120
            while (not all(r.healthy for r in router.replicas)
                   and time.monotonic() < deadline):
                time.sleep(0.02)
            assert all(r.healthy for r in router.replicas)
            snap = router.fault_injectors[owner].snapshot()
            assert snap["fired"] == 1
            assert snap["fired_plan_indexes"] == [0]
            assert int(_csum(router.registry,
                             "serving_flight_dumps_total",
                             trigger="engine_death")) == 1
            # second stream: the respawned worker carries the fired set
            hs2 = _stream(router, PROMPTS[:4], prefix="again")
            router.wait(hs2, timeout=300)
            assert all(h.finish_reason == "length" for h in hs2)
            assert router.fault_injectors[owner].snapshot()["fired"] == 1
            assert int(_csum(router.registry,
                             "serving_flight_dumps_total",
                             trigger="engine_death")) == 1
        finally:
            pf.stop()

    def test_idle_kill9_detected_by_heartbeat(self, aot_dir):
        """An IDLE worker's death has no step to fail on: the heartbeat
        marks it dead within the timeout, the replica loop's has_work
        poll raises WorkerDied through the standard death path, and an
        unsupervised one-replica fleet then refuses submits."""
        pf = ProcessFleet(_cfg(aot_dir, dp=1))
        pf.start()
        router = pf.router
        try:
            assert router.replicas[0].healthy
            os.kill(pf.worker_pid(0), signal.SIGKILL)
            deadline = time.monotonic() + 15
            while (router.replicas[0].healthy
                   and time.monotonic() < deadline):
                time.sleep(0.02)
            assert not router.replicas[0].healthy, \
                "idle worker death not detected"
            assert _csum(router.registry,
                         "serving_fleet_heartbeat_timeouts_total") >= 1
            with pytest.raises(FleetDown):
                router.submit_request(PROMPTS[0], SamplingParams(
                    max_new_tokens=2))
        finally:
            pf.stop()


# --- mid-rebuild debug rows over HTTP (satellite 1) -------------------------

def _http(port, method, path, body=None, timeout=120):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    payload = None if body is None else json.dumps(body)
    conn.request(method, path, payload,
                 {"Content-Type": "application/json"} if payload else {})
    resp = conn.getresponse()
    data = resp.read()
    status = resp.status
    conn.close()
    return status, data


class TestRestartingDebugRows:
    def test_debug_endpoints_degrade_to_restarting_rows(self, aot_dir):
        import asyncio

        from paddle_tpu.serving.server import (CompletionServer,
                                               ServerConfig)

        pf = ProcessFleet(_cfg(aot_dir))
        loop = asyncio.new_event_loop()
        thread = threading.Thread(target=loop.run_forever, daemon=True)
        thread.start()

        def run(coro, timeout=120):
            return asyncio.run_coroutine_threadsafe(
                coro, loop).result(timeout)

        server = CompletionServer(pf.router, ServerConfig())
        run(server.start())
        try:
            status, _ = _http(server.port, "GET", "/readyz")
            assert status == 200
            os.kill(pf.worker_pid(1), signal.SIGKILL)
            deadline = time.monotonic() + 15
            while (pf.router.replicas[1].healthy
                   and time.monotonic() < deadline):
                time.sleep(0.02)
            assert not pf.router.replicas[1].healthy

            status, data = _http(server.port, "GET", "/v1/debug/audit")
            assert status == 200
            body = json.loads(data)
            assert {"replica": "1", "enabled": False,
                    "status": "restarting"} in body["data"]
            # scoped to the mid-rebuild replica: still 200, not 404/500
            status, data = _http(server.port, "GET",
                                 "/v1/debug/audit?replica=1")
            assert status == 200
            assert json.loads(data)["data"][0]["status"] == "restarting"

            status, data = _http(server.port, "GET", "/v1/debug/cache")
            assert status == 200
            body = json.loads(data)
            rows = {d["replica"]: d for d in body["data"]}
            assert rows["1"]["status"] == "restarting"
            assert rows["0"].get("status") != "restarting"

            status, data = _http(server.port, "GET",
                                 "/v1/debug/compiles")
            assert status == 200
            body = json.loads(data)
            assert body["aot"]["1"] == {"status": "restarting"}
            # the healthy replica still serves completions throughout
            status, data = _http(
                server.port, "POST", "/v1/completions",
                {"prompt": PROMPTS[0], "max_tokens": 2})
            assert status == 200
            assert len(json.loads(data)["choices"][0]["token_ids"]) == 2
        finally:
            try:
                run(server.shutdown(drain_timeout=1.0), timeout=60)
            finally:
                loop.call_soon_threadsafe(loop.stop)
                thread.join(10)
                loop.close()
                pf.shared.close_all()


# --- SLO-driven autoscaling actuator (tentpole d) ---------------------------

class TestAutoscaler:
    def test_goodput_burn_scales_up_then_drains_and_replays(self, aot_dir):
        """An injected sustained goodput burn (every request violates a
        microscopic SLO) fires the frozen small-window burn rule → the
        actuator provisions the parked replica (bounded at max);
        post-incident calm drains it back; the recorded (sample, firing)
        log replays to the identical decision sequence."""
        rules = AlertRuleSet(rules=(AlertRule(
            name="goodput_burn", kind="burn_rate", objective=0.95,
            threshold=4.0, fast_window=2, slow_window=4,
            for_samples=1, cooldown=2),))
        pf = ProcessFleet(_cfg(aot_dir, fleet=FleetConfig(
            alert_rules=rules)), initial_replicas=1)
        pf.start()
        router = pf.router
        try:
            assert pf.live_replica_count() == 1
            scaler = pf.enable_autoscaler(AutoscalerConfig(
                min_replicas=1, max_replicas=2, cooldown_samples=2,
                calm_samples=4))
            hs = [router.submit_request(
                p, SamplingParams(max_new_tokens=8),
                request_id=f"slo{i}", slo_ms=0.001)
                for i, p in enumerate(PROMPTS[:4])]
            router.wait(hs, timeout=300)
            assert all(h.finish_reason == "length" for h in hs)
            # drive rule evaluation: each manual sample re-evaluates the
            # frozen rule set over the merged worker-side SLO counters.
            # Stop sampling the moment the decider acts — the decision
            # clock is sample-indexed, so pausing it freezes the
            # decider while the actuator boots the worker
            deadline = time.monotonic() + 90
            while (not scaler.decider.decisions
                   and time.monotonic() < deadline):
                router.history.sample()
                time.sleep(0.02)
            assert scaler.decider.decisions, \
                "burn firing never produced a scale decision"
            assert scaler.decider.decisions[0]["direction"] == "up"
            assert "goodput_burn" in scaler.decider.decisions[0]["firing"]
            deadline = time.monotonic() + 90
            while (pf.live_replica_count() < 2
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            assert pf.live_replica_count() == 2, \
                "burn firing did not provision the parked replica"
            assert _csum(pf.registry,
                         "serving_fleet_scale_events_total",
                         direction="up") == 1
            # the scaled-up fleet still serves (note: the request's own
            # engine steps tick the shared history, so the calm clock
            # may already be running here)
            h = router.submit_request(PROMPTS[4], SamplingParams(
                max_new_tokens=4), request_id="post-up")
            router.wait([h], timeout=300)
            assert h.finish_reason == "length"
            # calm: windows move past the burn, the rule resolves, and
            # calm_samples later the actuator drains an idle replica
            deadline = time.monotonic() + 90
            while (len(scaler.decider.decisions) < 2
                   and time.monotonic() < deadline):
                router.history.sample()
                time.sleep(0.02)
            assert len(scaler.decider.decisions) == 2, \
                "post-incident calm never produced a drain decision"
            deadline = time.monotonic() + 90
            while (pf.live_replica_count() > 1
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            assert pf.live_replica_count() == 1, \
                "post-incident calm did not drain the scale-up"
            assert _csum(pf.registry,
                         "serving_fleet_scale_events_total",
                         direction="down") == 1
            # replay determinism under the frozen rule set
            live = [d["direction"] for d in scaler.decider.decisions]
            assert live == ["up", "down"]
            replayed = [x for x in scaler.replay() if x is not None]
            assert replayed == live
        finally:
            pf.stop()


# --- cross-process compile reuse (satellite 3) ------------------------------

class TestCompileCacheReuse:
    def test_second_worker_boots_on_sibling_cache_entries(
            self, aot_dir, tmp_path, monkeypatch):
        """Two sequential workers inherit one JAX_COMPILATION_CACHE_DIR:
        the first warm-boot compiles every AOT program into the
        persistent cache; the second's boot log shows those entries
        pre-existing and adds NONE — every warm compile was a cache hit.
        (The floors are dropped through the environment too: the toy
        programs compile in milliseconds.)"""
        cache = str(tmp_path / "jaxcache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache)
        monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
        monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")

        def boot(serve=False):
            pf = ProcessFleet(_cfg(aot_dir, dp=1, warm_boot=True))
            try:
                wh = pf.proxy(0).worker
                assert wh.compile_cache is not None, \
                    "worker printed no compile-cache boot line"
                assert wh.compile_cache["dir"] == cache
                if serve:
                    # the warm boot's own gauge reaches the router's
                    # registry with the worker's first reply
                    pf.start()
                    h = pf.router.submit_request(
                        PROMPTS[0], SamplingParams(max_new_tokens=4),
                        request_id="wave-0")
                    pf.router.wait([h], timeout=300)
                    assert h.finish_reason == "length"
                    assert _csum(pf.registry,
                                 "serving_aot_warm_seconds") > 0
                return dict(wh.compile_cache), wh.boot_s
            finally:
                pf.stop()

        first, first_boot = boot()
        assert first["entries_before"] == 0
        if first["entries_after"] == 0:
            pytest.skip("jax persistent compilation cache wrote no "
                        "entries on this jax build")
        # (serving writes cache entries of its own, so only the last
        # boot serves)
        second, second_boot = boot(serve=True)
        assert second["entries_before"] == first["entries_after"]
        assert second["entries_after"] == second["entries_before"], \
            "second worker re-compiled despite the shared cache"


# --- CLI mode selection (server frontend) -----------------------------------

class TestServerCli:
    def test_workers_and_dp_are_mutually_exclusive(self):
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.serving.server",
             "--workers", "2", "--dp", "2"],
            capture_output=True, text=True, timeout=120,
            env=dict(os.environ, JAX_PLATFORMS="cpu",
                     PYTHONPATH=_REPO + os.pathsep
                     + os.environ.get("PYTHONPATH", "")))
        assert proc.returncode == 2
        assert "two fleet modes" in proc.stderr
